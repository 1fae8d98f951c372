"""NSAI workload tests: symbolic reasoning correctness, quantization
degradation ordering, data-generator invariants, MIMONet superposition."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _numerics import assert_logprobs_close
from _hypothesis_compat import given, settings, st

from repro.data import raven
from repro.models import lvrf, mimonet, nvsa, prae
from repro.nn import init as nninit


@pytest.fixture(scope="module")
def problem_batch():
    # d=128 keeps the Pallas kernel path active (d >= 128) at 4x less
    # interpret-mode cost than the default 256
    cfg = nvsa.NVSAConfig(d=128)
    return cfg, raven.generate_batch(cfg.raven, seed=5, n=16)


def _oracle(cfg, batch):
    ctx = [jnp.asarray(x) for x in nvsa.oracle_pmfs(
        cfg, jnp.asarray(batch["context_attrs"]))]
    cand = [jnp.asarray(x) for x in nvsa.oracle_pmfs(
        cfg, jnp.asarray(batch["candidate_attrs"]))]
    return ctx, cand


def test_generator_rules_consistent():
    cfg = raven.RavenConfig()
    for seed in range(20):
        p = raven.generate_problem(cfg, seed)
        grid = p["panel_attrs"].reshape(3, 3, 3)
        for ai in range(3):
            rule = int(p["rules"][ai])
            n = cfg.attr_sizes[ai]
            for row in range(3):
                a1, a2, a3 = (int(v) for v in grid[row, :, ai])
                assert raven.N_RULES
                assert a3 == raven._apply_rule(rule, a1, a2, n), \
                    (seed, ai, rule, grid[row, :, ai])
        # answer present exactly once among candidates
        matches = (p["candidate_attrs"] == p["panel_attrs"][8]).all(1).sum()
        assert matches == 1
        assert (p["candidate_attrs"][p["answer"]] == p["panel_attrs"][8]).all()


def test_nvsa_oracle_reasoning_near_perfect(problem_batch):
    cfg, batch = problem_batch
    ctx, cand = _oracle(cfg, batch)
    logp, rules = nvsa.reason(cfg, codebooks=nvsa.nvsa_codebooks(
        cfg, jax.random.PRNGKey(1)), ctx_pmfs=ctx, cand_pmfs=cand)
    acc = float(np.mean(np.argmax(np.asarray(logp), -1) == batch["answer"]))
    assert acc >= 0.95, acc


def test_prae_oracle_reasoning_near_perfect(problem_batch):
    cfg, batch = problem_batch
    ctx, cand = _oracle(cfg, batch)
    acc, racc = prae.accuracy(prae.PrAEConfig(), ctx, cand,
                              jnp.asarray(batch["answer"]), batch["rules"])
    # 16-problem sample: allow one rule-ambiguous miss (e.g. a constant row
    # that a PMF engine also explains as arith-minus with a2=0)
    assert acc >= 0.90, acc
    assert racc >= 0.8, racc


@pytest.mark.slow
def test_nvsa_quantization_monotone_degradation(problem_batch):
    """Tab. IV ordering on the symbolic side: int8/mp ≈ fp32 >> int4-everything
    degrades — with oracle perception so only precision varies."""
    cfg0, batch = problem_batch
    ctx, cand = _oracle(cfg0, batch)
    accs = {}
    for label, sy in [("fp32", "fp32"), ("int8", "int8"), ("int4", "int4")]:
        cfg = dataclasses.replace(cfg0, symb_precision=sy)
        books = nvsa.nvsa_codebooks(cfg, jax.random.PRNGKey(1))
        if sy in ("int8", "int4"):
            books = {
                "books": [nvsa.fake_quant(b, sy) for b in books["books"]],
                "shifts": [nvsa.fake_quant(s, sy) for s in books["shifts"]],
                "roles": nvsa.fake_quant(books["roles"], sy),
            }
        logp, _ = nvsa.reason(cfg, books, ctx, cand)
        accs[label] = float(np.mean(np.argmax(np.asarray(logp), -1)
                                    == batch["answer"]))
    assert accs["fp32"] >= 0.95
    assert accs["int8"] >= accs["fp32"] - 0.1   # int8 ~ lossless (Tab. IV)
    assert accs["int4"] <= accs["int8"] + 1e-9  # int4 strictly no better


def test_nvsa_memory_savings_ratio():
    cfg_fp = nvsa.NVSAConfig()
    cfg_mp = dataclasses.replace(cfg_fp, nn_precision="int8",
                                 symb_precision="int4")
    params = nninit.materialize(nvsa.nvsa_spec(cfg_fp), jax.random.PRNGKey(0))
    r = nvsa.nvsa_memory_bytes(cfg_fp, params) / nvsa.nvsa_memory_bytes(cfg_mp, params)
    assert 3.5 < r < 8.5  # paper: 5.8x


@pytest.mark.slow
def test_lvrf_learns_rules_quickly(problem_batch):
    """A few hundred LVRF steps on oracle PMFs beat chance by a wide margin."""
    cfg0, batch = problem_batch
    ctx, cand = _oracle(cfg0, batch)
    # d=64 keeps binds on the fast XLA ref path (kernel itself is
    # covered by test_kernels.py); 60 full-batch steps stay CPU-cheap
    lcfg = lvrf.LVRFConfig(d=64)
    params = nninit.materialize(lvrf.lvrf_spec(lcfg), jax.random.PRNGKey(0))
    books = lvrf.lvrf_codebooks(lcfg, jax.random.PRNGKey(1))
    answers = jnp.asarray(batch["answer"])
    loss_g = jax.jit(jax.value_and_grad(
        lambda p: lvrf.loss_fn(p, books, lcfg, ctx, cand, answers)))
    lr = 0.5
    for _ in range(60):
        loss, g = loss_g(params)
        params = jax.tree.map(lambda p, gg: p - lr * gg, params, g)
    acc = lvrf.accuracy(params, books, lcfg, ctx, cand, answers)
    assert acc > 0.5, acc  # chance = 0.125


def test_mimonet_unbinding_separates_channels():
    """With unitary keys, unbinding the superposition recovers per-channel
    codes (before the trunk): the core MIMONet property."""
    cfg = mimonet.MIMONetConfig()
    keys = mimonet.mimonet_keys(cfg, jax.random.PRNGKey(3))
    from repro.vsa import ops as vsa
    codes = vsa.random_codebook(jax.random.PRNGKey(4), cfg.n_channels,
                                cfg.blocks, cfg.d)
    bound = vsa.bind(codes, keys)
    sup = jnp.sum(bound, axis=0, keepdims=True)
    for c in range(cfg.n_channels):
        rec = vsa.unbind(keys[c][None], sup)[0]
        sims = [float(vsa.similarity(rec[None], codes[i][None])[0])
                for i in range(cfg.n_channels)]
        assert np.argmax(sims) == c
        assert sims[c] > 0.6


@settings(max_examples=10, deadline=None)
@given(style=st.sampled_from(["raven", "iraven", "pgm"]),
       seed=st.integers(0, 10_000))
def test_generator_candidates_unique(style, seed):
    cfg = raven.RavenConfig(style=style)
    p = raven.generate_problem(cfg, seed)
    cands = {tuple(c) for c in p["candidate_attrs"]}
    assert len(cands) == 8


# ---------------------------------------------------------------------------
# Served (compiled StagedSchedule) vs offline equivalence + determinism
# ---------------------------------------------------------------------------


def _reason_engine(cfg, batch_size, model="nvsa", consts=None,
                   variants=None, buckets=None):
    from repro.configs import base as cbase
    from repro.serve.reason import ReasonConfig

    # trace_graph=False: these tests exercise execution equivalence; the
    # graph/buffer lowering itself is covered by test_schedule.py
    return cbase.reason_engine(model, cfg,
                               ReasonConfig(batch_size=batch_size,
                                            buckets=buckets),
                               consts=consts, variants=variants,
                               trace_graph=False)


def test_served_nvsa_oracle_matches_offline(problem_batch):
    """Batched served NVSA (oracle variant, 2 pipeline batches) must
    reproduce the offline ``nvsa.reason`` answer distribution exactly and
    hit accuracy 1.0 on unambiguous RAVEN grids."""
    from repro.serve.reason import requests_from_batch

    cfg, batch = problem_batch
    books = nvsa.nvsa_codebooks(cfg, jax.random.PRNGKey(1))
    ctx, cand = _oracle(cfg, batch)
    off_logp, _ = nvsa.reason(cfg, books, ctx, cand)
    off_logp = np.asarray(off_logp)

    consts = {"params": None, "books": books}
    eng = _reason_engine(cfg, batch_size=8, consts=consts,
                         variants=("oracle",))
    res = eng.run(requests_from_batch(batch), variant="oracle")
    n = len(batch["answer"])
    served = np.stack([res[i].answer_logprobs for i in range(n)])
    np.testing.assert_allclose(served, off_logp, atol=1e-5)
    answers = np.array([res[i].answer for i in range(n)])
    np.testing.assert_array_equal(answers, np.argmax(off_logp, -1))
    assert float(np.mean(answers == batch["answer"])) == 1.0


def test_served_prae_oracle_accuracy(problem_batch):
    """The PrAE symbolic stream behind the same engine interface."""
    from repro.serve.reason import requests_from_batch

    cfg, batch = problem_batch
    consts = {"params": None, "books": None}
    eng = _reason_engine(cfg, batch_size=8, model="prae", consts=consts,
                         variants=("oracle",))
    res = eng.run(requests_from_batch(batch), variant="oracle")
    n = len(batch["answer"])
    acc = float(np.mean([res[i].answer == batch["answer"][i]
                         for i in range(n)]))
    assert acc >= 0.90, acc  # same floor as the offline PrAE oracle test


@pytest.mark.parametrize("nn,sy,qmm", [("fp32", "fp32", False),
                                       ("int8", "int4", True)])
def test_served_nvsa_cnn_matches_offline(nn, sy, qmm):
    """Full CNN path: the served pipeline must reproduce the offline
    ``nvsa.solve`` answer distributions — also under Tab. IV mixed
    precision with the nn stream on the Pallas qmatmul kernel and the
    symbolic stream at int4.  With eval-mode BN this holds across ragged
    admission groups, not just when the group equals the offline batch."""
    from repro.serve.reason import requests_from_batch

    # d=64 keeps binds on the XLA path (kernel conformance is covered by
    # test_kernel_conformance.py)
    cfg = nvsa.NVSAConfig(d=64, nn_precision=nn, symb_precision=sy,
                          use_qmatmul=qmm)
    params = nninit.materialize(nvsa.nvsa_spec(cfg), jax.random.PRNGKey(0))
    books = nvsa.nvsa_codebooks(cfg, jax.random.PRNGKey(1))
    batch = raven.generate_batch(cfg.raven, seed=11, n=6)
    off_logp, _ = nvsa.solve(params, books, cfg,
                             jnp.asarray(batch["context"]),
                             jnp.asarray(batch["candidates"]))
    off_logp = np.asarray(off_logp)

    consts = {"params": params, "books": books}
    # batch_size=4 -> 6 requests split into a full + ragged pipeline batch
    eng = _reason_engine(cfg, batch_size=4, consts=consts,
                         variants=("cnn",))
    res = eng.run(requests_from_batch(batch))
    served = np.stack([res[i].answer_logprobs for i in range(6)])
    np.testing.assert_allclose(served, off_logp, atol=1e-5)
    np.testing.assert_array_equal(
        np.array([res[i].answer for i in range(6)]),
        np.argmax(off_logp, -1))


def test_served_answer_independent_of_admission_group():
    """Eval-mode BN regression (ROADMAP): a request's served answer
    distribution must not depend on which other requests it was admitted
    with — serve a problem alone and inside a mixed group, byte-compare."""
    from repro.serve.reason import requests_from_batch

    cfg = nvsa.NVSAConfig(d=64)
    params = nninit.materialize(nvsa.nvsa_spec(cfg), jax.random.PRNGKey(0))
    books = nvsa.nvsa_codebooks(cfg, jax.random.PRNGKey(1))
    consts = {"params": params, "books": books}
    batch = raven.generate_batch(cfg.raven, seed=17, n=5)
    reqs = requests_from_batch(batch)

    eng = _reason_engine(cfg, batch_size=5, consts=consts, variants=("cnn",))
    grouped = eng.run(reqs)
    solo_eng = _reason_engine(cfg, batch_size=1, consts=consts,
                              variants=("cnn",))
    for req in reqs:
        solo = solo_eng.run([req])
        np.testing.assert_allclose(solo[req.uid].answer_logprobs,
                                   grouped[req.uid].answer_logprobs,
                                   atol=1e-5)
        assert solo[req.uid].answer == grouped[req.uid].answer


@pytest.mark.parametrize("model,variant", [
    ("nvsa", "cnn"), ("prae", "oracle"), ("mimonet", "default"),
    ("lvrf", "oracle")])
def test_served_answer_bitwise_invariant_across_buckets(model, variant):
    """Shape-bucketing regression (extends the PR 3 admission-group
    independence test): a request's served answer must be identical, and
    its logprobs within the cross-batch ulp bound (``_numerics``),
    whether it arrives in a full batch, a padded partial batch, or any
    compiled bucket size >= 2 — for every registered workload.  (Bucket 1
    is excluded from the default ladder because XLA's degenerate-batch
    lowerings move results further; see frontdoor.pow2_buckets.)"""
    from repro.configs import base as cbase

    entry = cbase.REASON_WORKLOADS[model]
    cfg = entry.make_config(d=64)
    consts = {"params": None, "books": None} if (model, variant) == \
        ("prae", "oracle") else entry.make_consts(cfg, jax.random.PRNGKey(0))
    factory, _ = entry.make_requests(cfg, 5, seed=21)
    reqs = list(factory())

    # reference: all 5 requests in one full (unpadded) admission group
    full = _reason_engine(cfg, batch_size=5, model=model, consts=consts,
                          variants=(variant,)).run(reqs,
                                                   variant=variant)
    # bucketed: groups of 4 (bucket 4) and 1 (bucket 2, one padded row)
    eng = _reason_engine(cfg, batch_size=4, model=model, consts=consts,
                         variants=(variant,), buckets=(2, 4))
    bucketed = eng.run(reqs, variant=variant)
    # padded partial at the same bucket: 3 requests ride bucket 4
    partial = eng.run(reqs[:3], variant=variant)
    assert eng.schedules[variant].batch_buckets == (2, 4)
    assert len({r.batch for r in bucketed.values()}) == 2  # two groups

    for uid in range(5):
        assert_logprobs_close(
            bucketed[uid].answer_logprobs, full[uid].answer_logprobs,
            err_msg=f"{model}/{variant} uid {uid} full-vs-bucketed")
        assert np.array_equal(full[uid].answer, bucketed[uid].answer)
    for uid in range(3):
        assert_logprobs_close(
            partial[uid].answer_logprobs, full[uid].answer_logprobs,
            err_msg=f"{model}/{variant} uid {uid} full-vs-padded-partial")
        assert np.array_equal(full[uid].answer, partial[uid].answer)


def test_bn_ema_updates_running_stats():
    """The functional BN-EMA plumbing: one train step's batch statistics
    fold into the running stats (NVSA frontend and MIMONet encoder), so
    eval-mode BN sees trained statistics."""
    from repro.models import mimonet

    cfg = nvsa.NVSAConfig(d=64, cnn_width=8, cnn_feat=32)
    params = nninit.materialize(nvsa.nvsa_spec(cfg), jax.random.PRNGKey(0))
    imgs, attrs = raven.panel_dataset(cfg.raven, seed=1, n_problems=1)
    (loss, stats), _ = jax.value_and_grad(nvsa.frontend_loss, has_aux=True)(
        params, cfg, jnp.asarray(imgs[:8]), jnp.asarray(attrs[:8]))
    assert np.isfinite(float(loss)) and stats
    new = nvsa.frontend_apply_bn_stats(params, stats, momentum=0.5)
    stem_old = params["frontend"]["stem_bn"]
    stem_new = new["frontend"]["stem_bn"]
    assert not np.allclose(stem_new["mean"], stem_old["mean"])
    assert not np.allclose(stem_new["var"], stem_old["var"])
    # scale/bias untouched; deep (list-indexed) paths updated too
    np.testing.assert_array_equal(stem_new["scale"], stem_old["scale"])
    deep_old = params["frontend"]["stages"][1][0]["bn1"]["mean"]
    deep_new = new["frontend"]["stages"][1][0]["bn1"]["mean"]
    assert not np.allclose(deep_new, deep_old)

    mcfg = mimonet.MIMONetConfig(d=32, cnn_width=4)
    mparams = nninit.materialize(mimonet.mimonet_spec(mcfg),
                                 jax.random.PRNGKey(0))
    keys = mimonet.mimonet_keys(mcfg, jax.random.PRNGKey(1))
    mimgs = jnp.asarray(imgs[: 2 * mcfg.n_channels].reshape(
        2, mcfg.n_channels, *imgs.shape[1:]))
    labels = jnp.asarray(attrs[: 2 * mcfg.n_channels, 0].reshape(
        2, mcfg.n_channels))
    mloss, mstats = mimonet.loss_fn(mparams, keys, mcfg, mimgs, labels)
    assert np.isfinite(float(mloss)) and mstats
    mnew = mimonet.apply_bn_stats(mparams, mstats, momentum=0.5)
    assert not np.allclose(mnew["encoder"]["stem_bn"]["mean"],
                           mparams["encoder"]["stem_bn"]["mean"])


def test_reason_pipeline_deterministic_and_order_invariant():
    """The reasoning-pipeline determinism golden test: identical answer
    distributions across two runs and across request submission orders
    (oracle variant — per-problem PMFs carry no cross-batch coupling)."""
    from repro.serve.reason import requests_from_batch

    cfg = nvsa.NVSAConfig(d=64)
    books = nvsa.nvsa_codebooks(cfg, jax.random.PRNGKey(1))
    batch = raven.generate_batch(cfg.raven, seed=13, n=10)
    reqs = requests_from_batch(batch)
    consts = {"params": None, "books": books}
    # 10 reqs -> ragged last batch
    eng = _reason_engine(cfg, batch_size=4, consts=consts,
                         variants=("oracle",))
    golden = eng.run(reqs, variant="oracle")
    rerun = eng.run(reqs, variant="oracle")
    shuffled = eng.run(list(reversed(reqs)), variant="oracle")
    for res in (rerun, shuffled):
        assert sorted(res) == sorted(golden)
        for uid in golden:
            np.testing.assert_array_equal(res[uid].answer_logprobs,
                                          golden[uid].answer_logprobs)
            assert res[uid].answer == golden[uid].answer
