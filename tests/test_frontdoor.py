"""Online-admission front-door tests: traffic models, the batch-full-or-
deadline policy (driven deterministically on a virtual clock), shape
bucketing, the engine's depth-k in-flight window + protocol submit/drain
API, and the warmup-aware stats split."""

import time

import jax
import numpy as np
import pytest
from _numerics import assert_logprobs_close

from repro.configs import base as cbase
from repro.models import nvsa
from repro.serve import frontdoor as fd
from repro.serve.reason import ReasonConfig, ReasonRequest, requests_from_batch


class VirtualClock:
    """Deterministic clock + sleep pair for driving the serve loop."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def sleep(self, dt: float):
        assert dt >= 0
        self.t += dt


def _oracle_engine(model="nvsa", batch_size=4, buckets=(2, 4),
                   max_inflight=1, schedule="overlap", d=64):
    """Cheap symbolic-stream-only engine (no CNN params needed)."""
    cfg = cbase.REASON_WORKLOADS[model].make_config(d=d)
    consts = {"params": None,
              "books": nvsa.nvsa_codebooks(cfg, jax.random.PRNGKey(1))}
    eng = cbase.reason_engine(
        model, cfg,
        ReasonConfig(batch_size=batch_size, buckets=buckets,
                     max_inflight=max_inflight, schedule=schedule),
        consts=consts, variants=("oracle",), trace_graph=False)
    return cfg, consts, eng


def _oracle_requests(cfg, n, seed=3):
    from repro.data import raven

    return requests_from_batch(raven.generate_batch(cfg.raven, seed=seed,
                                                    n=n))


# -- traffic models ----------------------------------------------------------


def test_pow2_buckets():
    assert fd.pow2_buckets(8) == (2, 4, 8)
    assert fd.pow2_buckets(6) == (2, 4, 6)
    assert fd.pow2_buckets(2) == (2,)
    assert fd.pow2_buckets(1) == (1,)
    assert fd.pow2_buckets(8, min_bucket=1) == (1, 2, 4, 8)
    with pytest.raises(ValueError):
        fd.pow2_buckets(0)


def test_poisson_arrivals_rate_and_determinism():
    reqs = [ReasonRequest(uid=i) for i in range(400)]
    a = list(fd.poisson_arrivals("m", reqs, rate_rps=50.0, seed=7))
    b = list(fd.poisson_arrivals("m", reqs, rate_rps=50.0, seed=7))
    assert [x.t for x in a] == [x.t for x in b]  # seeded => reproducible
    gaps = np.diff([0.0] + [x.t for x in a])
    assert (gaps > 0).all()
    assert 1 / 50.0 * 0.8 < gaps.mean() < 1 / 50.0 * 1.2
    with pytest.raises(ValueError, match="rate_rps"):
        next(fd.poisson_arrivals("m", reqs, rate_rps=0.0))


def test_poisson_arrivals_pull_requests_lazily():
    pulled = []

    def stream():
        for i in range(5):
            pulled.append(i)
            yield ReasonRequest(uid=i)

    it = fd.poisson_arrivals("m", stream(), rate_rps=10.0)
    assert pulled == []          # nothing rendered before the first pull
    next(it)
    assert len(pulled) == 1


def test_trace_arrivals_validation():
    reqs = [ReasonRequest(uid=i) for i in range(2)]
    out = list(fd.trace_arrivals("m", [0.1, 0.4], reqs))
    assert [a.t for a in out] == [0.1, 0.4]
    with pytest.raises(ValueError, match="nondecreasing"):
        list(fd.trace_arrivals("m", [0.4, 0.1], reqs))
    with pytest.raises(ValueError, match="more times"):
        list(fd.trace_arrivals("m", [0.1, 0.2, 0.3], reqs))


def test_merge_arrivals_orders_streams():
    r = lambda u: ReasonRequest(uid=u)
    s1 = fd.trace_arrivals("a", [0.0, 0.3], [r(0), r(1)])
    s2 = fd.trace_arrivals("b", [0.1, 0.2], [r(0), r(1)])
    merged = list(fd.merge_arrivals(s1, s2))
    assert [(a.model, a.t) for a in merged] == \
        [("a", 0.0), ("b", 0.1), ("b", 0.2), ("a", 0.3)]


def test_merge_arrivals_tie_break_is_stable():
    """Equal timestamps across models must preserve per-stream FIFO order
    AND earlier-argument stream priority: heapq.merge is stable, and the
    admission policy (which model's group a simultaneous arrival joins
    first) depends on that.  Pinned so a future reimplementation (e.g. a
    naive sort on t alone) cannot silently reorder simultaneous traffic."""
    r = lambda u: ReasonRequest(uid=u)
    # all four arrivals of each stream collide pairwise at t=0.0/0.1/0.1/0.2
    times = [0.0, 0.1, 0.1, 0.2]
    s1 = fd.trace_arrivals("a", times, [r(0), r(1), r(2), r(3)])
    s2 = fd.trace_arrivals("b", times, [r(0), r(1), r(2), r(3)])
    merged = [(a.model, a.request.uid, a.t) for a in
              fd.merge_arrivals(s1, s2)]
    # ties: stream "a" (first argument) wins, each stream stays FIFO
    assert merged == [
        ("a", 0, 0.0), ("b", 0, 0.0),
        ("a", 1, 0.1), ("a", 2, 0.1), ("b", 1, 0.1), ("b", 2, 0.1),
        ("a", 3, 0.2), ("b", 3, 0.2),
    ]
    for model in ("a", "b"):
        uids = [u for m, u, _ in merged if m == model]
        assert uids == sorted(uids)      # per-stream FIFO preserved


# -- the admission policy (virtual clock) ------------------------------------


def test_admission_full_deadline_flush_and_buckets():
    """4 back-to-back arrivals close `full`; a pair closes at the 20ms
    deadline through the bucket-2 shape; stream-end flushes the tail."""
    cfg, consts, eng = _oracle_engine(batch_size=4, buckets=(2, 4))
    reqs = _oracle_requests(cfg, 9)
    times = [0.0, 0.001, 0.002, 0.003,      # -> full group of 4
             0.05, 0.051,                   # -> deadline group of 2
             0.2, 0.21, 0.22]               # -> flush group of 3
    clock = VirtualClock()
    door = fd.FrontDoor({"nvsa": eng}, fd.FrontDoorConfig(deadline_s=0.02),
                        clock=clock, sleep=clock.sleep)
    rep = door.serve(fd.trace_arrivals("nvsa", times, reqs))

    assert eng.clock is time.perf_counter  # serve restored the engine clock
    assert [(g.size, g.bucket, g.close_reason) for g in rep.groups] == \
        [(4, 4, "full"), (2, 2, "deadline"), (3, 4, "flush")]
    assert len(rep.latencies) == 9
    assert all(l.queue_s >= -1e-9 and l.service_s >= -1e-9
               for l in rep.latencies)
    # the deadline group's first (oldest) request waited exactly the deadline
    dl = [l for l in rep.latencies if l.close_reason == "deadline"]
    assert max(l.queue_s for l in dl) == pytest.approx(0.02, abs=1e-6)
    # full group dispatched immediately on the closing arrival
    full = [l for l in rep.latencies if l.close_reason == "full"]
    assert max(l.queue_s for l in full) <= 0.004 + 1e-6
    # answers match the offline engine run exactly, logprobs to the
    # cross-batch ulp bound (the offline run batches differently)
    offline = eng.run(_oracle_requests(cfg, 9), variant="oracle")
    for uid, res in rep.results["nvsa"].items():
        assert res.answer == offline[uid].answer
        assert_logprobs_close(res.answer_logprobs,
                              offline[uid].answer_logprobs)


def test_frontdoor_multiplexes_models():
    """nvsa + prae behind one front-door: per-model groups, per-model
    results, one time-ordered feed."""
    ncfg, nconsts, neng = _oracle_engine("nvsa")
    pcfg = cbase.REASON_WORKLOADS["prae"].make_config(d=64)
    pconsts = {"params": None, "books": None}
    peng = cbase.reason_engine(
        "prae", pcfg, ReasonConfig(batch_size=4, buckets=(2, 4)),
        consts=pconsts, variants=("oracle",), trace_graph=False)
    clock = VirtualClock()
    door = fd.FrontDoor({"nvsa": neng, "prae": peng},
                        fd.FrontDoorConfig(deadline_s=0.01),
                        clock=clock, sleep=clock.sleep)
    streams = [
        fd.poisson_arrivals("nvsa", _oracle_requests(ncfg, 6, seed=5),
                            rate_rps=300.0, seed=0),
        fd.poisson_arrivals("prae", _oracle_requests(pcfg, 5, seed=6),
                            rate_rps=300.0, seed=1),
    ]
    rep = door.serve(fd.merge_arrivals(*streams))
    assert sorted(rep.results) == ["nvsa", "prae"]
    assert len(rep.results["nvsa"]) == 6 and len(rep.results["prae"]) == 5
    assert {g.model for g in rep.groups} == {"nvsa", "prae"}
    assert rep.throughput_rps() > 0
    # NSAI rows report in problems: one work unit per request
    assert rep.work_unit("nvsa") == "prob"
    assert rep.work_per_s("nvsa") == pytest.approx(rep.throughput_rps("nvsa"))
    assert rep.summary()  # renders without blowing up
    p = rep.percentiles("queue_s", "prae")
    assert set(p) == {"p50", "p95", "p99"} and p["p50"] <= p["p99"]


def test_frontdoor_empty_stream_well_formed_report():
    """An empty arrival stream must return a well-formed empty report, not
    crash or hang: per-model result dicts present, no latencies/groups,
    NaN percentiles, zero throughput, empty summary."""
    cfg, consts, eng = _oracle_engine()
    clock = VirtualClock()
    door = fd.FrontDoor({"nvsa": eng}, clock=clock, sleep=clock.sleep)
    rep = door.serve(iter([]))
    assert rep.results == {"nvsa": {}}
    assert rep.latencies == [] and rep.groups == []
    assert rep.wall_time_s >= 0 and np.isfinite(rep.wall_time_s)
    assert rep.throughput_rps() == 0.0 and rep.work_per_s() == 0.0
    assert all(np.isnan(v) for v in rep.percentiles().values())
    assert rep.bucket_histogram() == {}
    assert rep.summary() == ""
    assert eng.inflight == 0


def test_frontdoor_validation_errors():
    cfg, consts, eng = _oracle_engine()
    with pytest.raises(ValueError, match="at least one engine"):
        fd.FrontDoor({})
    with pytest.raises(ValueError, match="deadline_s"):
        fd.FrontDoor({"nvsa": eng}, fd.FrontDoorConfig(deadline_s=-1.0))
    clock = VirtualClock()
    door = fd.FrontDoor({"nvsa": eng}, clock=clock, sleep=clock.sleep)
    reqs = _oracle_requests(cfg, 2)
    with pytest.raises(ValueError, match="unknown model"):
        door.serve(fd.trace_arrivals("mystery", [0.0], reqs[:1]))
    with pytest.raises(ValueError, match="not time-ordered"):
        door.serve(iter([fd.ArrivalRequest(0.5, "nvsa", reqs[0]),
                         fd.ArrivalRequest(0.1, "nvsa", reqs[1])]))


def test_frontdoor_rejects_duplicate_uid_across_whole_serve():
    """Engines allow uid reuse after a drain, so the front-door must
    guard serve-lifetime uniqueness itself: a duplicate arriving after
    its predecessor was already served would otherwise silently
    overwrite the earlier answer in the report's results dict."""
    cfg, consts, eng = _oracle_engine(batch_size=2, buckets=(2,))
    reqs = _oracle_requests(cfg, 4)
    dup = reqs[:2] + reqs[:1]           # uid 0 arrives again much later
    clock = VirtualClock()
    door = fd.FrontDoor({"nvsa": eng}, fd.FrontDoorConfig(deadline_s=0.01),
                        clock=clock, sleep=clock.sleep)
    with pytest.raises(ValueError, match="duplicate request uid"):
        door.serve(fd.trace_arrivals("nvsa", [0.0, 0.001, 5.0], dup))


# -- engine group-level API (the runtime protocol) ---------------------------


def test_engine_inflight_window_depth():
    """max_inflight=2: the third submit must drain the first group."""
    cfg, consts, eng = _oracle_engine(batch_size=2, buckets=(2,),
                                      max_inflight=2)
    reqs = _oracle_requests(cfg, 6)
    r1 = eng.submit(reqs[0:2])
    r2 = eng.submit(reqs[2:4])
    assert eng.inflight == 2 and r1.done_t is None and r2.done_t is None
    r3 = eng.submit(reqs[4:6])
    assert r1.done_t is not None          # drained to make room
    assert eng.inflight == 2              # r2, r3 still resident
    results = eng.drain_all()
    assert sorted(results) == list(range(6))
    assert all(r.done_t >= r.dispatch_t for r in (r1, r2, r3))


def test_engine_drain_ready_nonblocking():
    cfg, consts, eng = _oracle_engine(batch_size=2, buckets=(2,),
                                      max_inflight=4)
    reqs = _oracle_requests(cfg, 4)
    eng.submit(reqs[:2])
    eng.submit(reqs[2:])
    results = {}
    deadline = time.time() + 30
    while eng.inflight and time.time() < deadline:
        results.update(eng.drain_ready())
        time.sleep(0.005)
    results.update(eng.drain_all())  # collect stragglers deterministically
    assert eng.inflight == 0 and len(results) == 4


def test_engine_submit_rejections():
    cfg, consts, eng = _oracle_engine(batch_size=2, buckets=(2,))
    reqs = _oracle_requests(cfg, 4)
    with pytest.raises(ValueError, match="empty admission group"):
        eng.submit([])
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(reqs[:3])
    with pytest.raises(ValueError, match="duplicate request uid"):
        eng.submit([reqs[0], reqs[0]])     # duplicate inside one group
    eng.submit(reqs[:2])
    with pytest.raises(ValueError, match="duplicate request uid"):
        eng.submit(reqs[:2])      # still in flight
    with pytest.raises(ValueError, match="undrained in-flight"):
        eng.run(reqs[2:])
    undrained = eng.drain_all()
    assert sorted(undrained) == [0, 1]
    with pytest.raises(ValueError, match="max_inflight"):
        cbase.reason_engine(
            "nvsa", cfg, ReasonConfig(max_inflight=0),
            consts=consts, variants=("oracle",), trace_graph=False)
    with pytest.raises(ValueError, match="largest compiled bucket"):
        cbase.reason_engine(
            "nvsa", cfg, ReasonConfig(batch_size=8, buckets=(2, 4)),
            consts=consts, variants=("oracle",), trace_graph=False)
    nc, _, unbound = _oracle_engine(batch_size=2, buckets=(2,))
    unbound.consts = None
    with pytest.raises(ValueError, match="no consts bound"):
        unbound.submit(reqs[:2])


def test_covering_bucket():
    cfg, consts, eng = _oracle_engine(batch_size=4, buckets=(2, 4))
    sched = eng.schedules["oracle"]
    assert sched.batch_buckets == (2, 4)
    assert [sched.covering_bucket(n) for n in (1, 2, 3, 4)] == [2, 2, 4, 4]
    with pytest.raises(ValueError, match="exceeds the largest"):
        sched.covering_bucket(5)


class CountingClock:
    """Monotone counter: every read advances, so stamp *ordering* is the
    observable (no wall-time ambiguity)."""

    def __init__(self):
        self.n = 0.0

    def __call__(self) -> float:
        self.n += 1.0
        return self.n


@pytest.mark.parametrize("drain_stage", [0, 1])
def test_window_block_never_charged_to_new_group_service(drain_stage):
    """Regression: with a full in-flight window, the new group's
    ``dispatch_t`` must be stamped BEFORE the engine blocks draining the
    oldest group — the window wait is queueing, never the new group's
    service time.  Earlier revisions drained mid-pipeline at the
    schedule's ``drain_stage``, which reordered the stamps whenever
    ``drain_stage > 0``; the ordering must now be independent of it."""
    cfg, consts, eng = _oracle_engine(batch_size=2, buckets=(2,),
                                      max_inflight=1)
    eng.schedules["oracle"].drain_stage = drain_stage
    eng.clock = CountingClock()
    reqs = _oracle_requests(cfg, 4)
    r1 = eng.submit(reqs[:2])
    r2 = eng.submit(reqs[2:])  # window full: dispatch r2, THEN drain r1
    assert r1.done_t is not None          # drained to keep the window at 1
    assert r2.done_t is None
    assert r2.dispatch_t < r1.done_t      # dispatched before the block
    eng.drain_all()
    assert r2.done_t > r2.dispatch_t


def test_protocol_path_accumulates_measured_stats():
    """Regression: engines driven purely through submit/drain (the
    front-door path — ``run()`` never called) used to accumulate zero
    measured requests/wall time, so ``problems_per_s()`` reported the
    warmup-fallback rate forever.  Groups are now accounted at collect
    time, keyed off each group's own cold flag."""
    cfg, consts, eng = _oracle_engine(batch_size=2, buckets=(2,),
                                      max_inflight=1)
    reqs = _oracle_requests(cfg, 8)
    for lo in range(0, 8, 2):
        eng.submit(reqs[lo:lo + 2])
    results = eng.drain_all()
    assert len(results) == 8
    assert eng.stats["warmup"]["requests"] == 2    # the one cold group
    assert eng.stats["measured"]["requests"] == 6  # warm groups measured
    assert eng.stats["measured"]["work"] == 6
    assert eng.stats["measured"]["wall_time_s"] > 0
    assert eng.problems_per_s() > 0


def test_drain_ready_probe_is_conservative():
    """A buffer leaf with no ``is_ready()`` that is not host-side data
    must probe NOT ready — ``drain_ready`` skips the group instead of
    vacuously treating it as finished and then blocking in collect."""
    from repro.serve.reason import ReasonEngine

    class OpaqueLeaf:  # e.g. a donated-buffer surrogate
        pass

    class FakeArray:
        def __init__(self, ready):
            self._ready = ready

        def is_ready(self):
            return self._ready

    assert ReasonEngine._leaf_ready(np.zeros(2))
    assert ReasonEngine._leaf_ready(1.5) and ReasonEngine._leaf_ready(3)
    assert ReasonEngine._leaf_ready(FakeArray(True))
    assert not ReasonEngine._leaf_ready(FakeArray(False))
    assert not ReasonEngine._leaf_ready(OpaqueLeaf())

    # an in-flight group whose buffers are opaque must not drain
    cfg, consts, eng = _oracle_engine(batch_size=2, buckets=(2,),
                                      max_inflight=4)
    reqs = _oracle_requests(cfg, 4)
    eng.submit(reqs[:2])
    group, bufs, rec, sched, cold, t0 = eng._inflight[0]
    eng._inflight[0] = (group, {"x": OpaqueLeaf()}, rec, sched, cold, t0)
    assert eng.drain_ready() == {}
    assert eng.inflight == 1
    eng._inflight[0] = (group, bufs, rec, sched, cold, t0)
    out = eng.drain_all()
    assert sorted(out) == [0, 1]


def test_drain_ready_under_fused_schedule():
    """The fused (one-jit, donation-eligible) pipeline serves through the
    same non-blocking probe loop the front-door drives."""
    cfg, consts, eng = _oracle_engine(batch_size=2, buckets=(2,),
                                      max_inflight=4, schedule="fused")
    assert eng.schedules["oracle"].fused_ok
    reqs = _oracle_requests(cfg, 4)
    eng.submit(reqs[:2])
    eng.submit(reqs[2:])
    results = {}
    deadline = time.time() + 30
    while eng.inflight and time.time() < deadline:
        results.update(eng.drain_ready())
        time.sleep(0.005)
    results.update(eng.drain_all())
    assert sorted(results) == list(range(4))
    assert eng.stats["fused_groups"] == 2
    assert eng.stats["dispatches"] == 2            # one launch per group


# -- stats: warmup split + per-variant stage keys ----------------------------


def test_stats_warmup_split_and_per_run_records():
    cfg, consts, eng = _oracle_engine(batch_size=2, buckets=(2,))
    reqs = _oracle_requests(cfg, 4)
    eng.run(reqs[:2])
    assert eng.last_run["warmup"] is True          # compiled bucket 2
    assert eng.stats["warmup"]["requests"] == 2
    assert eng.stats["measured"]["requests"] == 0
    warm_pps = eng.problems_per_s()                # warmup-only fallback
    assert warm_pps > 0
    eng.run(reqs[2:])
    assert eng.last_run["warmup"] is False
    assert eng.stats["measured"]["requests"] == 2
    # now measured-only: compile time no longer in the denominator
    assert eng.problems_per_s() > warm_pps
    # warmup wall time stays out of the measured throughput denominator
    assert eng.stats["measured"]["wall_time_s"] < \
        eng.stats["warmup"]["wall_time_s"]
    assert [r["warmup"] for r in eng.runs] == [True, False]
    # reset zeroes totals but remembers compiled shapes
    eng.reset_stats()
    assert eng.runs == [] and eng.problems_per_s() == 0.0
    eng.run(_oracle_requests(cfg, 2, seed=9))
    assert eng.last_run["warmup"] is False


def test_stage_times_do_not_collide_across_variants():
    """Both nvsa variants end in a stage named `symbolic`; per-variant
    nesting keeps oracle and cnn timings separate."""
    cfg = cbase.REASON_WORKLOADS["nvsa"].make_config(d=64)
    consts = cbase.REASON_WORKLOADS["nvsa"].make_consts(
        cfg, jax.random.PRNGKey(0))
    eng = cbase.reason_engine("nvsa", cfg, ReasonConfig(batch_size=2),
                              consts=consts, trace_graph=False)
    reqs = _oracle_requests(cfg, 2)
    eng.run(reqs, schedule="sequential", variant="cnn")
    eng.run(_oracle_requests(cfg, 2, seed=9),
            schedule="sequential", variant="oracle")
    st = eng.stats["stage_time_s"]
    assert set(st["cnn"]) == {"frontend", "symbolic"}
    assert set(st["oracle"]) == {"oracle", "symbolic"}
    assert st["cnn"]["symbolic"] != st["oracle"]["symbolic"]
    assert eng.last_run["stage_time_s"].keys() == st["oracle"].keys()
