"""Overload control plane tests: SLO vocabulary, bounded priority
queues + shedding policies, the AIMD feedback controller, and the
front-door integration on the deterministic simulated engine — all
driven on a virtual clock, so every assertion (including the two-run
bit-identical one) is exact."""

import numpy as np
import pytest

from repro.serve import frontdoor as fd
from repro.serve import sim
from repro.serve import slo as slo_mod
from repro.serve.control import (ClassQueues, ControlConfig,
                                 OverloadController, ShedRecord)
from repro.serve.slo import SLOEstimator, SLOTarget, slo_targets


class VirtualClock:
    """Deterministic clock + sleep pair for driving the serve loop."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def sleep(self, dt: float):
        assert dt >= 0
        self.t += dt


def _arrival(uid, t=0.0, model="m", priority=None):
    return fd.ArrivalRequest(t=t, model=model,
                             request=sim.SimRequest(uid=uid),
                             priority=priority)


# -- SLO vocabulary ----------------------------------------------------------


def test_validate_priority_named_error():
    assert slo_mod.validate_priority("interactive") == "interactive"
    with pytest.raises(ValueError, match="unknown priority class 'vip'"):
        slo_mod.validate_priority("vip")


def test_slo_targets_scalar_and_mapping():
    t = slo_targets(60.0)
    assert t["interactive"].total_p99_ms == 60.0
    assert t["standard"].total_p99_ms == 240.0   # conventional 4x
    assert "batch" not in t                      # best-effort
    t = slo_targets({"batch": 5000.0})
    assert set(t) == {"batch"}
    assert slo_targets(None) == {}
    with pytest.raises(ValueError, match="unknown priority class"):
        slo_targets({"vip": 1.0})
    with pytest.raises(ValueError, match="total_p99_ms"):
        slo_targets(-1.0)


def test_slo_estimator_windowed_p99():
    est = SLOEstimator(window=100)
    for i in range(150):
        est.observe("m", "standard", total_s=float(i), now=float(i))
    # only the last 100 observations (50..149) are retained
    assert est.count("m", "standard") == 100
    expect = float(np.percentile(np.arange(50, 150), 99)) * 1e3
    assert est.p99_ms("m", "standard") == pytest.approx(expect)
    assert np.isnan(est.p99_ms("m", "interactive"))


def test_slo_estimator_snapshot_against_targets():
    est = SLOEstimator({"interactive": SLOTarget(total_p99_ms=50.0)})
    for _ in range(10):
        est.observe("m", "interactive", total_s=0.01, now=0.0)
    snap = est.snapshot("m")
    assert snap["interactive"]["ok"] is True
    assert snap["interactive"]["target_ms"] == 50.0
    est.observe("m", "interactive", total_s=10.0, now=0.0)
    assert est.snapshot("m")["interactive"]["ok"] is False


def test_attainment_exact_counts():
    targets = {"interactive": SLOTarget(total_p99_ms=50.0,
                                        attainment=0.9)}
    lats = [fd.RequestLatency(uid=i, model="m", arrival_s=0.0,
                              dispatch_s=0.0,
                              done_s=0.01 if i < 9 else 1.0, bucket=1,
                              close_reason="full",
                              priority="interactive")
            for i in range(10)]
    att = slo_mod.attainment(lats, targets)
    row = att["interactive"]
    assert (row["n"], row["met"]) == (10, 9)
    assert row["attainment"] == pytest.approx(0.9)
    assert row["ok"] is True                     # 0.9 >= 0.9


# -- bounded priority queues -------------------------------------------------


def test_class_queues_bound_and_tail_drop():
    q = ClassQueues(depth=2, policy="tail-drop")
    assert q.offer(_arrival(0, t=0.0), "standard", now=0.0) is None
    assert q.offer(_arrival(1, t=0.1), "standard", now=0.1) is None
    rej = q.offer(_arrival(2, t=0.2), "interactive", now=0.2)
    assert isinstance(rej, ShedRecord)
    # tail-drop sheds the arrival itself, even when it outranks the queue
    assert (rej.uid, rej.priority, rej.reason) == (2, "interactive",
                                                  "queue-full")
    assert len(q) == 2 and q.depth_max == 2


def test_class_queues_lowest_priority_pushout():
    q = ClassQueues(depth=2, policy="lowest-priority")
    q.offer(_arrival(0, t=0.0), "standard", now=0.0)
    q.offer(_arrival(1, t=0.1), "batch", now=0.1)
    # an interactive arrival at the bound evicts the newest lowest-class
    # queued request — not itself
    rej = q.offer(_arrival(2, t=0.2), "interactive", now=0.2)
    assert (rej.uid, rej.priority, rej.reason) == (1, "batch", "pushout")
    assert [a.request.uid for a in q.pop(10)] == [2, 0]
    # a bottom-class arrival at the bound sheds itself
    q2 = ClassQueues(depth=1)
    q2.offer(_arrival(0, t=0.0), "batch", now=0.0)
    rej = q2.offer(_arrival(1, t=0.1), "batch", now=0.1)
    assert (rej.uid, rej.reason) == (1, "queue-full")


def test_class_queues_pop_priority_then_fifo():
    q = ClassQueues()
    q.offer(_arrival(0, t=0.0), "batch", now=0.0)
    q.offer(_arrival(1, t=0.1), "interactive", now=0.1)
    q.offer(_arrival(2, t=0.2), "standard", now=0.2)
    q.offer(_arrival(3, t=0.3), "interactive", now=0.3)
    assert q.oldest_t == 0.0
    assert [a.request.uid for a in q.pop(3)] == [1, 3, 2]
    assert [a.request.uid for a in q.pop(3)] == [0]
    with pytest.raises(ValueError, match="unknown priority class"):
        q.offer(_arrival(4), "vip", now=0.0)
    with pytest.raises(ValueError, match="depth bound"):
        ClassQueues(depth=0)


# -- the feedback controller -------------------------------------------------


def test_control_config_validation():
    with pytest.raises(ValueError, match="tick_s"):
        ControlConfig(tick_s=0.0)
    with pytest.raises(ValueError, match="decrease"):
        ControlConfig(decrease=1.5)
    with pytest.raises(ValueError, match="increase"):
        ControlConfig(increase=1.0)
    with pytest.raises(ValueError, match="unknown shed policy"):
        ControlConfig(shed_policy="coin-flip")
    with pytest.raises(ValueError, match="queue_depth"):
        ControlConfig(queue_depth=0)


def test_controller_bind_is_idempotent_and_clamped():
    ctl = OverloadController(slo_targets(60.0))
    ctl.bind("m", deadline_s=10.0, cap=8, buckets=(2, 4, 8))
    assert ctl.deadline_s("m") == ctl.cfg.max_deadline_s  # clamped
    assert ctl.cap("m") == 8
    ctl.bind("m", deadline_s=0.001, cap=2)   # second bind: no-op
    assert ctl.cap("m") == 8
    assert ctl.bound() == {"m"}


def _fed(ctl, model, total_s, n=16, now=0.0):
    for _ in range(n):
        ctl.observe(model, "interactive", total_s, now)


def test_controller_tightens_on_violation_with_shallow_queue():
    ctl = OverloadController(slo_targets(50.0))
    ctl.bind("m", deadline_s=0.02, cap=8, buckets=(2, 4, 8))
    _fed(ctl, "m", total_s=0.5)              # p99 500ms >> 50ms target
    out = ctl.tick(1.0, {"m": {"queue_depth": 0, "inflight": 0}})
    assert [d.action for d in out] == ["tighten"]
    assert ctl.deadline_s("m") == pytest.approx(0.01)   # halved
    assert ctl.cap("m") == 4                            # stepped down


def test_controller_steps_cap_up_on_violation_with_backlog():
    ctl = OverloadController(slo_targets(50.0))
    ctl.bind("m", deadline_s=0.02, cap=8, buckets=(2, 4, 8))
    _fed(ctl, "m", total_s=0.5)
    # first a shallow-queue violation steps the cap down from the DSE
    # point...
    ctl.tick(1.0, {"m": {"queue_depth": 0, "inflight": 0}})
    assert ctl.cap("m") == 4
    # ...then sustained backlog flips the diagnosis to throughput-bound
    # and steps it back up (the DSE cap stays the ceiling)
    _fed(ctl, "m", total_s=0.5)
    out = ctl.tick(2.0, {"m": {"queue_depth": 16, "inflight": 4}})
    assert [d.action for d in out] == ["throughput"]
    assert ctl.cap("m") == 8                 # amortize dispatch overhead


def test_controller_relaxes_back_when_healthy():
    ctl = OverloadController(slo_targets(50.0))
    ctl.bind("m", deadline_s=0.02, cap=8, buckets=(2, 4, 8))
    _fed(ctl, "m", total_s=0.5)
    ctl.tick(1.0, {"m": {"queue_depth": 0, "inflight": 0}})
    assert (ctl.deadline_s("m"), ctl.cap("m")) == (0.01, 4)
    # healthy window: deadline multiplies back up, cap drifts to the
    # DSE point
    _fed(ctl, "m", total_s=0.001, n=ctl.cfg.window)
    out = ctl.tick(2.0, {"m": {"queue_depth": 0, "inflight": 0}})
    assert [d.action for d in out] == ["relax"]
    assert ctl.deadline_s("m") == pytest.approx(0.0125)
    assert ctl.cap("m") == 8


def test_controller_holds_below_min_obs_and_without_targets():
    ctl = OverloadController(slo_targets(50.0))
    ctl.bind("m", deadline_s=0.02, cap=8)
    _fed(ctl, "m", total_s=0.5, n=ctl.cfg.min_obs - 1)
    assert ctl.tick(1.0, {}) == []           # too few observations
    free = OverloadController()              # no objectives: observe-only
    free.bind("m", deadline_s=0.02, cap=8)
    _fed(free, "m", total_s=0.5)
    assert free.tick(1.0, {}) == []


def test_maybe_tick_is_phase_locked():
    ctl = OverloadController(slo_targets(50.0),
                             ControlConfig(tick_s=0.1))
    ctl.bind("m", deadline_s=0.02, cap=8)
    ctl.maybe_tick(0.0, {})                  # arms the cadence
    assert ctl.ticks == 0
    ctl.maybe_tick(0.05, {})
    assert ctl.ticks == 0                    # not due yet
    ctl.maybe_tick(0.11, {})
    assert ctl.ticks == 1
    # a long stall consumes the missed phases but runs ONE tick, and the
    # next boundary stays on the original phase grid
    ctl.maybe_tick(0.55, {})
    assert ctl.ticks == 2
    assert ctl._next_tick == pytest.approx(0.6)


# -- front-door integration on the simulated engine --------------------------


def _sim_serve(n=2000, rate=500.0, slo_ms=60.0, queue_depth=32,
               mix=None, seed=0, deadline_s=0.01, cap=8,
               policy="lowest-priority", controller=True):
    vc = VirtualClock()
    # a shallow in-flight window keeps the service tail inside the 60ms
    # interactive budget; the pending backlog lives in the bounded
    # ClassQueues where it can shed
    eng = sim.SimEngine(vc, vc.sleep, cap=cap, max_inflight=2)
    ctl = None
    if controller:
        ctl = OverloadController(
            slo_targets(slo_ms),
            ControlConfig(queue_depth=queue_depth, shed_policy=policy))
    door = fd.FrontDoor({"sim": eng},
                        fd.FrontDoorConfig(deadline_s=deadline_s),
                        clock=vc, sleep=vc.sleep, controller=ctl)
    times = [i / rate for i in range(n)]
    reqs = sim.sim_requests(n, mix=mix, seed=seed)
    return door.serve(fd.trace_arrivals("sim", times, reqs))


def test_flush_order_tracks_arrival_order_across_models():
    """End-of-stream flush regression: open groups must dispatch oldest
    arrival first ACROSS models, not in engine-dict order."""
    vc = VirtualClock()
    engines = {"a": sim.SimEngine(vc, vc.sleep, cap=4),
               "b": sim.SimEngine(vc, vc.sleep, cap=4)}
    door = fd.FrontDoor(engines, fd.FrontDoorConfig(deadline_s=1.0),
                        clock=vc, sleep=vc.sleep)
    arrivals = fd.merge_arrivals(
        fd.trace_arrivals("b", [0.05], [sim.SimRequest(uid=0)]),
        fd.trace_arrivals("a", [0.06], [sim.SimRequest(uid=1)]))
    rep = door.serve(arrivals)
    assert [g.close_reason for g in rep.groups] == ["flush", "flush"]
    # "b" opened first (0.05 < 0.06) so it must dispatch first, even
    # though "a" precedes it in the engines dict
    assert [g.model for g in rep.groups] == ["b", "a"]
    assert rep.groups[0].dispatch_s <= rep.groups[1].dispatch_s


def test_no_controller_is_legacy_unbounded_no_shed():
    rep = _sim_serve(n=500, rate=2000.0, controller=False)
    assert rep.shed == [] and rep.slo == {} and rep.decisions == []
    assert len(rep.latencies) == 500
    assert rep.offered("sim") == 500


def test_offered_equals_admitted_plus_shed_exactly():
    mix = {"interactive": 0.3, "standard": 0.5, "batch": 0.2}
    rep = _sim_serve(n=3000, rate=1400.0, mix=mix)   # ~2x capacity
    assert rep.offered("sim") == 3000
    assert len(rep.latencies) + len(rep.shed) == 3000
    served = {l.uid for l in rep.latencies}
    shed = {s.uid for s in rep.shed}
    assert not served & shed
    assert served | shed == set(range(3000))
    assert len(rep.shed) > 0                 # 2x load must actually shed


def test_overload_sheds_low_priority_and_protects_interactive():
    mix = {"interactive": 0.3, "standard": 0.5, "batch": 0.2}
    rep = _sim_serve(n=3000, rate=1400.0, mix=mix)
    counts = rep.shed_counts("sim")
    assert sum(counts.values()) > 0
    assert "interactive" not in counts       # shedding confined downward
    att = rep.slo_attainment("sim")
    assert att["interactive"]["ok"] is True  # SLO holds through overload
    # boundedness: the pending queue never outgrew its depth bound
    assert rep.queue_depth_max["sim"] <= 32
    assert 0.0 < rep.shed_rate("sim") < 1.0
    assert "shed" in rep.summary() and "slo attainment" in rep.summary()


def test_at_capacity_no_shedding_and_slo_met():
    mix = {"interactive": 0.3, "standard": 0.5, "batch": 0.2}
    rep = _sim_serve(n=2000, rate=500.0, mix=mix)    # ~0.75x capacity
    assert rep.shed == []
    att = rep.slo_attainment("sim")
    assert att["interactive"]["ok"] is True
    assert att["standard"]["ok"] is True


def test_shedding_and_decisions_are_deterministic():
    mix = {"interactive": 0.3, "standard": 0.5, "batch": 0.2}
    a = _sim_serve(n=2500, rate=1400.0, mix=mix)
    b = _sim_serve(n=2500, rate=1400.0, mix=mix)
    assert a.shed == b.shed                  # frozen dataclass equality
    assert a.latencies == b.latencies
    assert a.decisions == b.decisions
    assert a.queue_depth_max == b.queue_depth_max
    assert a.wall_time_s == b.wall_time_s


def test_controller_adapts_during_serve():
    mix = {"interactive": 0.3, "standard": 0.5, "batch": 0.2}
    rep = _sim_serve(n=3000, rate=1400.0, mix=mix)
    assert rep.decisions                     # the loop actually closed
    assert {d.action for d in rep.decisions} <= {"tighten", "throughput",
                                                 "relax"}
    assert rep.slo["interactive"].total_p99_ms == 60.0


def test_priority_resolution_prefers_arrival_stamp():
    """with_priorities overrides the envelope's own class; bare arrivals
    fall back to it."""
    vc = VirtualClock()
    eng = sim.SimEngine(vc, vc.sleep, cap=4)
    door = fd.FrontDoor({"sim": eng}, fd.FrontDoorConfig(deadline_s=0.01),
                        clock=vc, sleep=vc.sleep)
    reqs = [sim.SimRequest(uid=0, priority="batch"),
            sim.SimRequest(uid=1, priority="batch")]
    stream = fd.trace_arrivals("sim", [0.0, 0.0], reqs)
    rep = door.serve(fd.with_priorities(stream, "interactive"))
    assert {l.priority for l in rep.latencies} == {"interactive"}
    rep2 = door.serve(fd.trace_arrivals(
        "sim", [0.0], [sim.SimRequest(uid=7, priority="batch")]))
    assert [l.priority for l in rep2.latencies] == ["batch"]


def test_with_priorities_mix_is_seeded():
    reqs = [sim.SimRequest(uid=i) for i in range(200)]
    mk = lambda: fd.with_priorities(
        fd.trace_arrivals("m", [0.0] * 200, iter(reqs)),
        {"interactive": 1, "batch": 1}, seed=5)
    a = [x.priority for x in mk()]
    assert a == [x.priority for x in mk()]
    assert set(a) == {"interactive", "batch"}
    with pytest.raises(ValueError, match="unknown priority class"):
        list(fd.with_priorities(iter([]), "vip"))
    with pytest.raises(ValueError, match="weights"):
        list(fd.with_priorities(iter([]), {"batch": 0.0}))


def test_bursty_times_diurnal_and_bursts():
    quiet = sim.bursty_times(500, base_rps=100.0, amp=0.0, seed=1)
    assert quiet == sim.bursty_times(500, base_rps=100.0, amp=0.0, seed=1)
    assert all(b > a for a, b in zip(quiet, quiet[1:]))
    burst = sim.bursty_times(
        500, base_rps=100.0, amp=0.0, seed=1,
        bursts=[sim.Burst(t0_s=0.0, dur_s=1e9, mult=4.0)])
    assert burst[-1] < quiet[-1] / 2         # 4x rate compresses the trace
    r0 = sim.diurnal_rate(0.0, 100.0, amp=0.4, period_s=3600.0)
    r_peak = sim.diurnal_rate(900.0, 100.0, amp=0.4, period_s=3600.0)
    assert r0 == pytest.approx(100.0)
    assert r_peak == pytest.approx(140.0)


def test_sim_engine_protocol_and_capacity():
    vc = VirtualClock()
    eng = sim.SimEngine(vc, vc.sleep, cap=8, max_inflight=2)
    rec = eng.submit([sim.SimRequest(uid=0), sim.SimRequest(uid=1)])
    assert rec.bucket == 2 and rec.dispatch_t == 0.0
    assert eng.accepting
    out = eng.drain_all()
    assert set(out) == {0, 1}
    assert eng.stats["warmup"]["requests"] == 2
    svc = sim.ServiceModel(base_s=0.004, per_item_s=0.001)
    assert svc.group_s(8) == pytest.approx(0.012)
    assert svc.capacity_rps(8) == pytest.approx(8 / 0.012)
    with pytest.raises(ValueError, match="admission cap"):
        eng.submit([sim.SimRequest(uid=i) for i in range(9)])
    with pytest.raises(ValueError, match="empty"):
        eng.submit([])
