"""Continuous-batching engine tests: fused-scan equivalence with the
lockstep reference, EOS early-stop, sampling determinism, ragged prefill,
slot reuse after retirement, the runtime-protocol submit/drain surface,
and the warmup-aware stats split."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.configs import base as cbase
from repro.nn import init as nninit
from repro.serve.engine import Engine, LockstepEngine, Request, ServeConfig

MAX_LEN = 64


@pytest.fixture(scope="module")
def llama():
    arch = ARCHS["llama3.2-3b"]
    cfg = arch.make_smoke()
    params = nninit.materialize(cbase.model_spec(arch, cfg),
                                jax.random.PRNGKey(0))
    step, init_caches = cbase.serve_fns(arch, cfg, max_len=MAX_LEN)
    return cfg, params, step, init_caches


def _engine(llama, **kw):
    _, params, step, init_caches = llama
    defaults = dict(max_new_tokens=8, max_slots=4, max_len=MAX_LEN,
                    decode_block=4)
    defaults.update(kw)
    return Engine(step, init_caches, ServeConfig(**defaults), params=params)


@pytest.fixture(scope="module")
def greedy_engine(llama):
    """Shared greedy engine — jit caches are per-instance, so reuse."""
    return _engine(llama)


def test_fused_matches_lockstep_reference(llama, greedy_engine):
    """The scan-fused greedy decode must reproduce the per-token loop."""
    cfg, params, step, init_caches = llama
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, (4, 12)).astype(np.int32)
    scfg = ServeConfig(max_new_tokens=8, max_slots=4, max_len=MAX_LEN,
                       decode_block=4)
    ref = LockstepEngine(step, init_caches, scfg).generate(params, prompts)
    out = greedy_engine.generate(prompts)
    np.testing.assert_array_equal(out, ref)


def test_eos_early_stop_matches_reference(llama, greedy_engine):
    """Tokens before EOS match the no-EOS run; pads follow; slot retires."""
    cfg, params, _, _ = llama
    # pick an "EOS" token whose FIRST occurrence is mid-sequence (greedy
    # smoke decodes loop, so full[k] may also appear earlier, and some
    # prompts decode one token throughout): take the first seeded prompt
    # whose decode has one
    for seed in range(1, 33):
        prompt = np.random.default_rng(seed).integers(
            0, cfg.vocab, (9,)).astype(np.int32)
        full = greedy_engine.generate([prompt])[0]
        k = next((i for i in range(1, len(full)) if full[i] not in full[:i]),
                 None)
        if k is not None:
            break
    assert k is not None, "no seeded prompt decodes a new mid-sequence token"
    eos = int(full[k])
    eng = _engine(llama, eos_id=eos, pad_id=0)
    res = eng.run([Request(uid=0, prompt=prompt)])[0]
    assert res.finished_by_eos
    np.testing.assert_array_equal(res.tokens, full[: k + 1])  # EOS included
    out = eng.generate([prompt])[0]
    np.testing.assert_array_equal(out[: k + 1], full[: k + 1])
    assert (out[k + 1:] == 0).all()  # retired slot emits pad after EOS


def test_sampled_decode_deterministic_under_fixed_key(llama, greedy_engine):
    cfg, params, _, _ = llama
    prompts = np.random.default_rng(2).integers(
        0, cfg.vocab, (3, 10)).astype(np.int32)
    eng = _engine(llama, temperature=0.7, top_k=16, seed=11)
    a = eng.generate(prompts)
    b = eng.generate(prompts)  # run() re-seeds from cfg.seed
    np.testing.assert_array_equal(a, b)
    greedy = greedy_engine.generate(prompts)
    assert not np.array_equal(a, greedy)  # temperature is actually live
    c = _engine(llama, temperature=0.7, top_k=16, seed=12).generate(prompts)
    assert not np.array_equal(a, c)  # and keyed by the seed


def test_ragged_batch_matches_single_requests(llama, greedy_engine):
    """3 ragged prompts admitted together == 3 single-request runs."""
    cfg, params, _, _ = llama
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, (n,)).astype(np.int32)
               for n in (5, 12, 9)]
    batch = greedy_engine.generate(prompts)
    for i, p in enumerate(prompts):
        single = greedy_engine.generate([p])[0]
        np.testing.assert_array_equal(batch[i], single)


def test_slots_reused_after_retirement(llama, greedy_engine):
    """6 requests through 4 slots: the queue drains into freed slots."""
    cfg, params, _, _ = llama
    rng = np.random.default_rng(4)
    eng = greedy_engine
    before = list(eng.stats["slots_served"])
    reqs = [Request(uid=i, prompt=rng.integers(
        0, cfg.vocab, (7,)).astype(np.int32), max_new_tokens=6)
        for i in range(6)]
    results = eng.run(reqs)
    assert sorted(results) == list(range(6))
    assert all(len(r.tokens) == 6 for r in results.values())
    served = [a - b for a, b in zip(eng.stats["slots_served"], before)]
    assert sum(served) == 6
    assert max(served) >= 2  # a freed slot picked up a queued request


def test_per_request_budget_and_validation(llama, greedy_engine):
    cfg, params, _, _ = llama
    rng = np.random.default_rng(5)
    eng = greedy_engine
    short = Request(uid=0, prompt=rng.integers(0, cfg.vocab, (4,)).astype(
        np.int32), max_new_tokens=3)
    res = eng.run([short])[0]
    assert len(res.tokens) == 3 and not res.finished_by_eos
    with pytest.raises(ValueError):  # prompt + budget must fit the slot
        eng.run([Request(uid=1, prompt=rng.integers(
            0, cfg.vocab, (MAX_LEN,)).astype(np.int32))])


def test_duplicate_request_uids_rejected(llama, greedy_engine):
    """results are keyed by uid — a duplicate would silently drop one."""
    cfg, params, _, _ = llama
    rng = np.random.default_rng(8)
    reqs = [Request(uid=7, prompt=rng.integers(0, cfg.vocab, (5,)).astype(
        np.int32)) for _ in range(2)]
    with pytest.raises(ValueError, match="duplicate request uids"):
        greedy_engine.run(reqs)


def test_windowed_ring_cache_padded_prefill_matches_lockstep():
    """Bucketed prefill must not corrupt ring-buffer (sliding-window) KV
    caches: with window=16 a length-20 prompt pads to 32, and unclamped pad
    positions would wrap the ring and clobber real prompt entries. The
    lockstep reference scans exact lengths, so any corruption diverges."""
    arch = ARCHS["starcoder2-3b"]
    cfg = arch.make_smoke()  # window=16 < padded prefill length
    params = nninit.materialize(cbase.model_spec(arch, cfg),
                                jax.random.PRNGKey(0))
    step, init_caches = cbase.serve_fns(arch, cfg, max_len=MAX_LEN)
    scfg = ServeConfig(max_new_tokens=8, max_slots=2, max_len=MAX_LEN,
                       decode_block=4, prefill_bucket=16)
    prompts = np.random.default_rng(7).integers(
        0, cfg.vocab, (2, 20)).astype(np.int32)
    ref = LockstepEngine(step, init_caches, scfg).generate(params, prompts)
    out = Engine(step, init_caches, scfg, params=params).generate(prompts)
    np.testing.assert_array_equal(out, ref)


def test_sampled_run_golden_deterministic_and_order_invariant(llama):
    """Fixed seed + fixed request set => byte-identical token streams across
    Engine.run invocations AND across submission orders: sampling is keyed
    by (seed, uid, token index), so admission order, slot assignment, and
    co-resident requests must not leak into any request's stream."""
    cfg, params, _, _ = llama
    rng = np.random.default_rng(9)
    eng = _engine(llama, temperature=0.9, top_k=24, seed=21, max_slots=3)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, (n,)).astype(np.int32),
                    max_new_tokens=b)
            for i, (n, b) in enumerate([(5, 8), (11, 4), (7, 6), (9, 8),
                                        (4, 5), (13, 7), (6, 8)])]
    golden = eng.run(reqs)
    rerun = eng.run(reqs)
    orders = [list(reversed(reqs)),
              [reqs[i] for i in np.random.default_rng(0).permutation(7)]]
    for results in [rerun] + [eng.run(order) for order in orders]:
        assert sorted(results) == sorted(golden)
        for uid in golden:
            np.testing.assert_array_equal(results[uid].tokens,
                                          golden[uid].tokens)
            assert results[uid].finished_by_eos == golden[uid].finished_by_eos


def test_vector_pos_decode_matches_scalar(llama):
    """attention.decode_step with a uniform (B,) pos == scalar pos."""
    cfg, params, step, init_caches = llama
    caches = init_caches(4)
    tok = jnp.arange(4, dtype=jnp.int32) + 5
    c1, l1 = jax.jit(step)(params, caches, tok, jnp.int32(0))
    c2, l2 = jax.jit(step)(params, init_caches(4), tok,
                           jnp.zeros((4,), jnp.int32))
    np.testing.assert_allclose(np.asarray(l1, np.float32),
                               np.asarray(l2, np.float32), atol=1e-5)
    for a, b in zip(jax.tree.leaves(c1), jax.tree.leaves(c2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)


def test_serve_fns_tag_forces_stateful_prefill():
    """rwkv/griffin served with a default ServeConfig must not silently run
    bucketed pad steps through cumulative state: serve_fns tags init_caches
    and the Engine flips the flag itself."""
    arch = ARCHS["rwkv6-7b"]
    step, init_caches = cbase.serve_fns(arch, arch.make_smoke(),
                                        max_len=MAX_LEN)
    assert init_caches.stateful_prefill
    eng = Engine(step, init_caches, ServeConfig(max_len=MAX_LEN))
    assert eng.cfg.stateful_prefill
    arch = ARCHS["llama3.2-3b"]  # positional KV caches keep bucketed prefill
    step, init_caches = cbase.serve_fns(arch, arch.make_smoke(),
                                        max_len=MAX_LEN)
    assert not init_caches.stateful_prefill
    eng = Engine(step, init_caches, ServeConfig(max_len=MAX_LEN))
    assert not eng.cfg.stateful_prefill


# -- the runtime protocol (submit / drain, the front-door surface) -----------


def test_submit_drain_matches_run_bit_exactly(llama):
    """Serving the same uids through the online submit/drain path must be
    byte-identical to the offline run() loop (the acceptance regression
    for folding the LM engine under the unified protocol)."""
    cfg, params, _, _ = llama
    rng = np.random.default_rng(10)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, (n,)).astype(np.int32),
                    max_new_tokens=5)
            for i, n in enumerate((5, 9, 7, 4, 11, 6))]
    eng = _engine(llama, temperature=0.8, top_k=12, seed=3, max_slots=2)
    offline = eng.run(reqs)
    # online: dribble groups in, pump with drain_ready, finish with drain_all
    recs = [eng.submit(reqs[0:2])]
    got = dict(eng.drain_ready())
    recs.append(eng.submit(reqs[2:4]))
    got.update(eng.drain_ready())
    recs.append(eng.submit(reqs[4:6]))
    got.update(eng.drain_all())
    assert sorted(got) == sorted(offline)
    for uid in offline:
        np.testing.assert_array_equal(got[uid].tokens, offline[uid].tokens)
    for rec in recs:
        assert rec.dispatch_t is not None and rec.done_t is not None
        assert rec.done_t >= rec.dispatch_t
    assert eng.inflight == 0


def test_submit_queues_past_slot_pool(llama):
    """A submit beyond the free slots queues; drain calls admit + decode
    one block at a time (bounded work per call)."""
    eng = _engine(llama, max_slots=2, max_new_tokens=6)
    cfg, params, _, _ = llama
    rng = np.random.default_rng(11)
    reqs = [Request(uid=i, prompt=rng.integers(
        0, cfg.vocab, (6,)).astype(np.int32)) for i in range(4)]
    r1 = eng.submit(reqs[:2])
    assert r1.dispatch_t is not None      # prefilled immediately
    r2 = eng.submit(reqs[2:])
    assert r2.dispatch_t is None          # pool full: queued, not dispatched
    assert eng.inflight == 2
    blocks0 = eng.stats["decode_blocks"]
    eng.drain_ready()
    assert eng.stats["decode_blocks"] == blocks0 + 1  # exactly one block
    results = eng.drain_all()
    assert sorted(results) == [0, 1, 2, 3]
    assert r2.dispatch_t is not None and r2.done_t is not None
    assert eng.inflight == 0


def test_submit_rejections(llama):
    eng = _engine(llama, max_slots=2)
    cfg, params, _, _ = llama
    rng = np.random.default_rng(12)
    reqs = [Request(uid=i, prompt=rng.integers(
        0, cfg.vocab, (5,)).astype(np.int32)) for i in range(3)]
    with pytest.raises(ValueError, match="empty admission group"):
        eng.submit([])
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(reqs)                   # 3 > 2-slot pool
    eng.submit(reqs[:2])
    with pytest.raises(ValueError, match="duplicate request uids"):
        eng.submit(reqs[:1])               # still resident
    with pytest.raises(ValueError, match="undrained in-flight"):
        eng.run(reqs[2:])
    eng.drain_all()
    eng.submit(reqs[:1])                   # drained uids may be reused
    eng.drain_all()
    step, init_caches = cbase.serve_fns(ARCHS["llama3.2-3b"],
                                        ARCHS["llama3.2-3b"].make_smoke(),
                                        max_len=MAX_LEN)
    unbound = Engine(step, init_caches, ServeConfig(max_len=MAX_LEN))
    with pytest.raises(ValueError, match="no params bound"):
        unbound.submit(reqs[:1])


# -- stats: warmup split + per-run records (ReasonEngine parity) -------------


def test_stats_warmup_split_and_per_run_records(llama):
    """First run compiles prefill+decode -> warmup; repeat run at the same
    shapes is measured, so tokens_per_s no longer folds jit compile into
    throughput."""
    cfg, params, _, _ = llama
    rng = np.random.default_rng(13)
    eng = _engine(llama, max_slots=2, max_new_tokens=6)
    reqs = [Request(uid=i, prompt=rng.integers(
        0, cfg.vocab, (6,)).astype(np.int32)) for i in range(2)]
    eng.run(reqs)
    assert eng.last_run["warmup"] is True          # compiled prefill+decode
    assert eng.stats["warmup"]["requests"] == 2
    assert eng.stats["warmup"]["work"] == eng.stats["tokens"]
    assert eng.stats["measured"]["requests"] == 0
    warm_tps = eng.tokens_per_s()                  # warmup-only fallback
    assert warm_tps > 0
    eng.run(reqs)                                  # same shapes: no compile
    assert eng.last_run["warmup"] is False
    assert eng.stats["measured"]["requests"] == 2
    # compile time no longer in the denominator
    assert eng.tokens_per_s() > warm_tps
    assert eng.stats["measured"]["wall_time_s"] < \
        eng.stats["warmup"]["wall_time_s"]
    assert [r["warmup"] for r in eng.runs] == [True, False]
    # a new padded prefill length is a fresh shape -> warmup again
    long_req = [Request(uid=9, prompt=rng.integers(
        0, cfg.vocab, (20,)).astype(np.int32), max_new_tokens=6)]
    eng.run(long_req)
    assert eng.last_run["warmup"] is True
    # reset zeroes totals but remembers compiled shapes
    eng.reset_stats()
    assert eng.runs == [] and eng.tokens_per_s() == 0.0
    eng.run(reqs)
    assert eng.last_run["warmup"] is False


@pytest.mark.slow
def test_stateful_prefill_ragged_rwkv():
    """Cumulative recurrent state needs exact-length prefill scans: a ragged
    batch under stateful_prefill matches exact single-request runs."""
    arch = ARCHS["rwkv6-7b"]
    cfg = arch.make_smoke()
    params = nninit.materialize(cbase.model_spec(arch, cfg),
                                jax.random.PRNGKey(0))
    step, init_caches = cbase.serve_fns(arch, cfg, max_len=MAX_LEN)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab, (n,)).astype(np.int32)
               for n in (5, 12, 9)]
    kw = dict(max_new_tokens=6, max_slots=4, max_len=MAX_LEN, decode_block=4,
              stateful_prefill=True)
    eng = Engine(step, init_caches, ServeConfig(**kw), params=params)
    batch = eng.generate(prompts)
    assert eng.stats["prefills"] == 3  # one exact-length scan per length
    for i, p in enumerate(prompts):
        single = eng.generate([p])[0]
        np.testing.assert_array_equal(batch[i], single)
