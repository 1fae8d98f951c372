"""Schedule-compilation tests: the dataflow-graph -> StagedSchedule lowering
(serve/schedule.py) and the workload registry (configs/base.py).

Covers the tier-1 compilation smoke for all four registered workloads, the
bit-exact equivalence of the compiled NVSA schedule with PR 2's hand-wired
two-stage pipeline, and served-vs-offline equivalence for the two workloads
the refactor newly opened (MIMONet, LVRF)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as cbase
from repro.nn import init as nninit
from repro.serve.reason import ReasonConfig, ReasonEngine, ReasonRequest
from repro.serve.schedule import (STREAMS, StageSpec, StagedSchedule,
                                  _fmt_bytes, compile_schedule,
                                  predicted_overlap)


def test_registry_covers_all_workloads():
    """The four paper workloads serve through one registry; every consumer
    (launcher --model choices, examples, benchmarks) derives its model list
    from it."""
    assert set(cbase.REASON_WORKLOADS) == {"nvsa", "prae", "mimonet", "lvrf"}
    assert cbase.REASON_MODELS == tuple(cbase.REASON_WORKLOADS)
    for name, entry in cbase.REASON_WORKLOADS.items():
        assert entry.name == name
        assert entry.variants, name
        assert entry.describe


def test_schedule_compilation_smoke():
    """Fast tier-1 smoke: every workload's default variant compiles to a
    StagedSchedule with stream-tagged stages, inter-stage buffer specs and
    a traced DataflowGraph (consts shapes only — nothing materialized)."""
    for model, entry in cbase.REASON_WORKLOADS.items():
        cfg = entry.make_config(d=64)
        sched = cbase.compile_reason_schedule(model, cfg, batch_size=2)
        assert isinstance(sched, StagedSchedule)
        assert len(sched.stages) >= 2, model
        assert all(s in STREAMS for s in sched.streams), model
        # input buffer + one output buffer per stage, all sized
        assert len(sched.buffers) == len(sched.stages) + 1, model
        assert all(b.nbytes > 0 for b in sched.buffers), model
        # per-stage traced op statistics (the stream-tag audit)
        assert len(sched.stage_costs) == len(sched.stages), model
        # the composed pipeline traced into the same graph IR the DSE uses
        assert sched.source == "trace" and sched.graph is not None, model
        assert len(sched.graph.graph) > 0 and sched.graph.critical_path
        assert sched.describe()  # human-readable pipeline rendering
        ovl = predicted_overlap(sched, n_batches=4)
        assert ovl["speedup"] >= 1.0, (model, ovl)


def test_nvsa_schedule_has_two_streams():
    """The compiled NVSA pipeline is the paper's two-stream split: an nn
    perception stage feeding a vsa symbolic stage, with the PMF buffer in
    between sized B*8*sum(V)*2*4 bytes."""
    entry = cbase.REASON_WORKLOADS["nvsa"]
    cfg = entry.make_config(d=64)
    b = 4
    sched = cbase.compile_reason_schedule("nvsa", cfg, batch_size=b)
    assert sched.stage_names == ("frontend", "symbolic")
    assert sched.streams == ("nn", "vsa")
    pmf_bytes = 2 * 4 * b * 8 * sum(cfg.raven.attr_sizes)  # ctx+cand f32
    assert sched.buffers[1].nbytes == pmf_bytes
    # the traced graph sees both unit classes of the composed pipeline
    assert sched.graph.graph.nn_nodes(), "conv/matmul nodes"
    assert sched.graph.graph.simd_nodes(), "softmax/similarity chains"


def test_compiled_nvsa_matches_handwired_pipeline_bitexact():
    """The compiled schedule must reproduce PR 2's hand-wired two-stage
    pipeline byte-identically: same stage functions, same jit boundaries,
    same answers."""
    from repro.models import nvsa as nv
    from repro.serve.reason import requests_from_batch
    from repro.data import raven

    cfg = nv.NVSAConfig(d=64)
    params = nninit.materialize(nv.nvsa_spec(cfg), jax.random.PRNGKey(0))
    books = nv.nvsa_codebooks(cfg, jax.random.PRNGKey(1))
    consts = {"params": params, "books": books}
    batch = raven.generate_batch(cfg.raven, seed=23, n=8)

    # PR 2's hand-wired pipeline: jit(neural) then jit(symbolic), one
    # admission group per dispatch
    def neural(params, ctx, cand):
        n, _, h, w, c = ctx.shape
        ctx_p, _ = nv.frontend_pmfs(params, cfg, ctx.reshape(n * 8, h, w, c))
        cand_p, _ = nv.frontend_pmfs(params, cfg,
                                     cand.reshape(n * 8, h, w, c))
        return (tuple(p.reshape(n, 8, -1) for p in ctx_p),
                tuple(p.reshape(n, 8, -1) for p in cand_p))

    def symbolic(codebooks, ctx_pmfs, cand_pmfs):
        codebooks = nv.quantize_codebooks(cfg, codebooks)
        return nv.reason(cfg, codebooks, list(ctx_pmfs), list(cand_pmfs))

    jit_neural, jit_symbolic = jax.jit(neural), jax.jit(symbolic)
    hand = []
    for lo in range(0, 8, 4):
        ctx = jnp.asarray(batch["context"][lo:lo + 4], jnp.float32)
        cand = jnp.asarray(batch["candidates"][lo:lo + 4], jnp.float32)
        logp, _ = jit_symbolic(books, *jit_neural(params, ctx, cand))
        hand.append(np.asarray(logp))
    hand = np.concatenate(hand)

    eng = cbase.reason_engine("nvsa", cfg, ReasonConfig(batch_size=4),
                              consts=consts, variants=("cnn",),
                              trace_graph=False)
    res = eng.run(requests_from_batch(batch))
    served = np.stack([res[i].answer_logprobs for i in range(8)])
    np.testing.assert_array_equal(served, hand)  # bit-exact


def test_mimonet_served_matches_offline():
    """MIMONet's compiled 5-stage pipeline (encode -> superpose -> trunk ->
    unbind -> classify) reproduces the offline single-jit ``forward``."""
    from repro.models import mimonet as mm

    entry = cbase.REASON_WORKLOADS["mimonet"]
    cfg = entry.make_config(d=64)
    consts = entry.make_consts(cfg, jax.random.PRNGKey(0))
    eng = cbase.reason_engine("mimonet", cfg, ReasonConfig(batch_size=3),
                              consts=consts, trace_graph=False)
    factory, _ = entry.make_requests(cfg, 5, seed=0)
    reqs = list(factory())
    res = eng.run(iter(reqs))  # 5 reqs -> full + ragged batch

    imgs = jnp.asarray(np.stack([r.images for r in reqs]), jnp.float32)
    off = np.asarray(mm.forward(consts["params"], consts["keys"], cfg, imgs))
    served_ans = np.stack([res[i].answer for i in range(5)])
    np.testing.assert_array_equal(served_ans, np.argmax(off, -1))
    for i in range(5):
        shifted = off[i] - off[i].max(-1, keepdims=True)
        off_logp = shifted - np.log(np.exp(shifted).sum(-1, keepdims=True))
        np.testing.assert_allclose(res[i].answer_logprobs, off_logp,
                                   atol=1e-5)


def test_lvrf_served_matches_offline(capsys):
    """LVRF's compiled pipeline (frontend/oracle -> abduce -> execute)
    reproduces the offline ``solve_from_pmfs`` on the oracle variant."""
    from repro.data import raven
    from repro.models import lvrf as lv
    from repro.models import nvsa as nv
    from repro.serve.reason import requests_from_batch

    entry = cbase.REASON_WORKLOADS["lvrf"]
    cfg = entry.make_config(d=64)
    consts = entry.make_consts(cfg, jax.random.PRNGKey(0))
    eng = cbase.reason_engine("lvrf", cfg, ReasonConfig(batch_size=4),
                              consts=consts, variants=("oracle",),
                              trace_graph=False)
    batch = raven.generate_batch(cfg.raven, seed=3, n=6)
    res = eng.run(requests_from_batch(batch), variant="oracle")

    ctx = [jnp.asarray(x) for x in nv.oracle_pmfs(
        cfg, jnp.asarray(batch["context_attrs"]))]
    cand = [jnp.asarray(x) for x in nv.oracle_pmfs(
        cfg, jnp.asarray(batch["candidate_attrs"]))]
    off_logp, off_posts = lv.solve_from_pmfs(consts["params"],
                                             consts["books"], cfg, ctx, cand)
    served = np.stack([res[i].answer_logprobs for i in range(6)])
    np.testing.assert_allclose(served, np.asarray(off_logp), atol=1e-5)
    posts = np.stack([res[i].rule_posteriors for i in range(6)], axis=1)
    np.testing.assert_allclose(posts, np.asarray(off_posts), atol=1e-5)


def test_registry_and_engine_errors():
    entry = cbase.REASON_WORKLOADS["nvsa"]
    cfg = entry.make_config(d=64)
    with pytest.raises(KeyError, match="unknown reasoning workload"):
        cbase.compile_reason_schedule("resnetzilla", cfg)
    with pytest.raises(KeyError, match="variant"):
        cbase.compile_reason_schedule("mimonet",
                                      cbase.REASON_WORKLOADS["mimonet"]
                                      .make_config(d=64), variant="oracle")
    # a mimonet request without images fails loudly with the uid
    mcfg = cbase.REASON_WORKLOADS["mimonet"].make_config(d=64)
    mconsts = cbase.REASON_WORKLOADS["mimonet"].make_consts(
        mcfg, jax.random.PRNGKey(0))
    eng = cbase.reason_engine("mimonet", mcfg, ReasonConfig(batch_size=2),
                              consts=mconsts, trace_graph=False)
    with pytest.raises(ValueError, match="request 7"):
        eng.run([ReasonRequest(uid=7)])
    with pytest.raises(ValueError, match="unknown variant"):
        eng.run([], variant="oracle")
    with pytest.raises(ValueError, match="duplicate request uid"):
        eng.run([ReasonRequest(uid=1), ReasonRequest(uid=1)])


def test_compile_schedule_rejects_bad_stages():
    with pytest.raises(ValueError, match="unknown stream"):
        StageSpec("s", "gpu", lambda c, b: b)
    with pytest.raises(ValueError, match="at least one stage"):
        compile_schedule("w", [], lambda r: r, lambda o, i: {})
    dup = [StageSpec("s", "nn", lambda c, b: b),
           StageSpec("s", "vsa", lambda c, b: b)]
    with pytest.raises(ValueError, match="duplicate stage names"):
        compile_schedule("w", dup, lambda r: r, lambda o, i: {})
    one = [StageSpec("s", "nn", lambda c, b: b)]
    for bad in ((4, 2), (2, 2, 4), (0, 2)):
        with pytest.raises(ValueError, match="batch_buckets"):
            compile_schedule("w", one, lambda r: r, lambda o, i: {},
                             batch_buckets=bad)


# -- fused pipeline: one dispatch per admission group ------------------------


@pytest.mark.parametrize("override", ["", "xla"])
@pytest.mark.parametrize("model", sorted(cbase.REASON_WORKLOADS))
def test_fused_schedule_bitexact_vs_staged(model, override):
    """The whole-pipeline fused jit must reproduce the staged schedule
    bit-for-bit for every workload, across batch buckets (full group of 4
    plus the ragged 2) and under the forced-xla backend override.  At d=64
    on CPU every kernel negotiates an exact lowering, so the fused path
    engages for all four workloads; the dispatch counter must drop from K
    per group to 1."""
    from repro.backend import registry

    entry = cbase.REASON_WORKLOADS[model]
    cfg = entry.make_config(d=64)
    consts = entry.make_consts(cfg, jax.random.PRNGKey(0))
    variant = "oracle" if "oracle" in entry.variants else entry.variants[0]
    with registry.use_plan(registry.negotiate(platform="cpu",
                                              override=override)):
        eng = cbase.reason_engine(
            model, cfg,
            ReasonConfig(batch_size=4, buckets=(2, 4), variant=variant),
            consts=consts, variants=(variant,), trace_graph=False)
        sched = eng.schedules[variant]
        assert sched.jit_fused is not None
        assert sched.fused_equivalence == "exact", (
            model, override, sched.fused_lowering_diff)
        assert sched.fused_ok
        fused_eng = ReasonEngine(
            eng.schedules, dataclasses.replace(eng.cfg, schedule="fused"),
            consts=consts)

        factory, _ = entry.make_requests(cfg, 6, seed=11)
        reqs = list(factory())
        staged = eng.run(iter(reqs))
        k = len(sched.jit_stages)
        assert eng.stats["dispatches"] == 2 * k       # 2 groups x K stages
        fused = fused_eng.run(iter(reqs))
        assert fused_eng.stats["dispatches"] == 2     # 2 groups x 1 launch
        assert fused_eng.stats["fused_groups"] == 2
        assert fused_eng.stats["fused_fallback_groups"] == 0

    assert set(staged) == set(fused)
    for uid, r_s in staged.items():
        r_f = fused[uid]
        np.testing.assert_array_equal(np.asarray(r_s.answer),
                                      np.asarray(r_f.answer))
        np.testing.assert_array_equal(r_s.answer_logprobs,
                                      r_f.answer_logprobs)
        if r_s.rule_posteriors is not None:
            np.testing.assert_array_equal(r_s.rule_posteriors,
                                          r_f.rule_posteriors)


def test_fused_epsilon_negotiation_falls_back_stagewise():
    """mimonet at d=128 on CPU: the staged trace routes unbind through the
    circ_conv interpret lowering while the fused trace routes the
    epsilon-class unbind_classify kernel — the negotiation must come out
    epsilon, the executor must refuse the substitution and serve stage by
    stage (counting the fallback), and the answers must stay identical to
    the staged schedule; ``fused=True`` overrides the refusal."""
    from repro.backend import registry

    plan = registry.negotiate(platform="cpu", override="")
    entry = cbase.REASON_WORKLOADS["mimonet"]
    cfg = entry.make_config(d=128)
    consts = entry.make_consts(cfg, jax.random.PRNGKey(0))
    with registry.use_plan(plan):
        eng = cbase.reason_engine("mimonet", cfg, ReasonConfig(batch_size=2),
                                  consts=consts, trace_graph=False)
        sched = eng.schedules["default"]
        assert sched.jit_fused is not None
        assert sched.fused_equivalence == "epsilon"
        assert sched.fused_epsilon > 0
        assert "unbind_classify" in sched.fused_lowering_diff
        assert not sched.fused_ok
        fused_eng = ReasonEngine(
            eng.schedules, dataclasses.replace(eng.cfg, schedule="fused"),
            consts=consts)

        factory, _ = entry.make_requests(cfg, 2, seed=0)
        reqs = list(factory())
        staged = eng.run(iter(reqs))
        fused = fused_eng.run(iter(reqs))
        assert fused_eng.stats["fused_groups"] == 0
        assert fused_eng.stats["fused_fallback_groups"] == 1
    for uid in staged:
        np.testing.assert_array_equal(staged[uid].answer_logprobs,
                                      fused[uid].answer_logprobs)

    # an explicit fused=True accepts the epsilon class
    forced = cbase.compile_reason_schedule("mimonet", cfg, consts=consts,
                                           batch_size=2, trace_graph=False,
                                           plan=plan, fused=True)
    assert forced.fused_forced and forced.fused_ok
    assert forced.fused_equivalence == "epsilon"


def test_fmt_bytes_boundaries():
    """Unit boundaries must never render a value >= 1024 of the smaller
    unit (1048575 bytes is '1.0MB', not '1024.0KB')."""
    assert _fmt_bytes(0) == "0B"
    assert _fmt_bytes(1023) == "1023B"
    assert _fmt_bytes(1024) == "1.0KB"
    assert _fmt_bytes(1048575) == "1.0MB"          # the old '1024.0KB' bug
    assert _fmt_bytes(1048576) == "1.0MB"
    assert _fmt_bytes(1024 ** 3 - 1) == "1.0GB"
    assert _fmt_bytes(1024 ** 3) == "1.0GB"
    assert _fmt_bytes(1536) == "1.5KB"
    # GB is the cap unit: values >= 1024GB stay in GB by design
    assert _fmt_bytes(2 ** 40) == "1024.0GB"
    assert _fmt_bytes(5 * 1024 ** 4) == "5120.0GB"
    # values just inside the rounding window promote instead of rendering
    # "1024.0" of the smaller unit
    assert _fmt_bytes(1048524) == "1023.9KB"        # 1023.949KB: stays KB
    assert _fmt_bytes(1048526) == "1.0MB"           # 1023.951KB: promotes
    assert _fmt_bytes(int(1023.96 * 1024 ** 2)) == "1.0GB"


def test_predicted_overlap_traces_lazily_without_trace_graph():
    """A schedule compiled with input_specs but trace_graph=False used to
    raise a misleading 'compiled without input_specs'; stage costs (and
    the composed-pipeline graph) must instead be traced on first use."""
    entry = cbase.REASON_WORKLOADS["nvsa"]
    cfg = entry.make_config(d=64)
    sched = cbase.compile_reason_schedule("nvsa", cfg, batch_size=2,
                                          trace_graph=False)
    assert sched.stage_costs == () and sched.graph is None
    ovl = predicted_overlap(sched, n_batches=4)
    assert ovl["speedup"] >= 1.0
    # memoized on the schedule, matching an eagerly-traced compile
    assert len(sched.stage_costs) == len(sched.stages)
    assert sched.graph is not None and sched.source == "trace"
    eager = cbase.compile_reason_schedule("nvsa", cfg, batch_size=2)
    assert predicted_overlap(eager, n_batches=4) == ovl
    # no input specs at all is still a (correctly-worded) error
    bare = compile_schedule("w", [StageSpec("s", "nn", lambda c, b: b)],
                            lambda r: r, lambda o, i: {})
    with pytest.raises(ValueError, match="without input_specs"):
        predicted_overlap(bare)
