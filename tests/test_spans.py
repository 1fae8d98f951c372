"""Layer spans of the serving path: the front-door's admit / close / poll
spans and the NSAI engine's stage / enqueue / wait / collect spans, on the
records the front-door returns and in the profiler's trace.

The records are checked under a virtual clock that advances a dyadic tick
on every read, so every span has a length and every stamp difference is
exact in floating point."""

import glob
import os

import jax
import numpy as np
import pytest
from test_frontdoor import _oracle_engine, _oracle_requests
from test_serve_engine import _engine as _lm_engine
from test_serve_engine import llama  # noqa: F401  (fixture)

from repro.serve import frontdoor as fd
from repro.serve import runtime as rt
from repro.serve import sim
from repro.serve.engine import Request

TICK = 2.0 ** -12


class TickingClock:
    """Every read advances one tick; ``sleep`` advances by its argument."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += TICK
        return self.t

    def sleep(self, dt: float):
        assert dt >= 0
        self.t += dt


# full groups, a deadline group and a flush, at dyadic times
TIMES = [0.0, 2 ** -9, 2 ** -8, 3 * 2 ** -9,
         2 ** -4, 2 ** -4 + 2 ** -9,
         2 ** -2, 2 ** -2 + 2 ** -8, 2 ** -2 + 2 ** -7]


def _serve(eng, reqs, clock):
    door = fd.FrontDoor({"nvsa": eng},
                        fd.FrontDoorConfig(deadline_s=2 ** -6,
                                           poll_s=2 ** -9),
                        clock=clock, sleep=clock.sleep)
    return door.serve(fd.trace_arrivals("nvsa", TIMES, reqs))


@pytest.fixture(scope="module", params=["sequential", "overlap", "fused"])
def served(request):
    cfg, _, eng = _oracle_engine(schedule=request.param)
    if request.param == "fused":
        assert eng.schedules["oracle"].fused_ok
    _serve(eng, _oracle_requests(cfg, len(TIMES), seed=7),
           TickingClock())  # warm
    eng.reset_stats()
    rep = _serve(eng, _oracle_requests(cfg, len(TIMES)), TickingClock())
    return request.param, eng, rep


def test_queue_split_sums_to_queue_time(served):
    _, _, rep = served
    assert len(rep.latencies) == len(TIMES)
    assert [g.close_reason for g in rep.groups] == \
        ["full", "deadline", "flush"]
    for lat in rep.latencies:
        assert lat.late_s + lat.batch_s + lat.staging_s == lat.queue_s
        assert lat.late_s >= 0 and lat.batch_s >= 0 and lat.staging_s > 0


def test_service_split_within_service_time(served):
    _, _, rep = served
    for g in rep.groups:
        assert g.enqueue_s > 0 and g.wait_s > 0 and g.collect_s > 0
        assert g.enqueue_s + g.wait_s + g.collect_s <= \
            g.done_s - g.dispatch_s


def test_close_stamped_before_dispatch(served):
    """The close stamp is taken when ``submit`` is called: under the
    synchronous schedule a stamp taken on its return would read the
    group's done time."""
    _, _, rep = served
    for g in rep.groups:
        assert g.close_s < g.dispatch_s < g.done_s
    for lat in rep.latencies:
        group = next(g for g in rep.groups if lat.uid in g.uids)
        assert lat.close_s == group.close_s
        assert lat.arrival_s <= lat.admit_s <= lat.close_s <= lat.dispatch_s


def test_stage_time_keeps_its_keys(served):
    schedule, eng, _ = served
    st = eng.stats["stage_time_s"]
    assert set(st) == {"oracle"}
    if schedule == "sequential":
        names = {s.name for s in eng.schedules["oracle"].stages}
        assert set(st["oracle"]) == names
        assert all(v > 0 for v in st["oracle"].values())
    else:
        assert st["oracle"] == {}


def test_sim_engine_groups_keep_defaults():
    clock = TickingClock()
    eng = sim.SimEngine(clock, clock.sleep, cap=4)
    door = fd.FrontDoor({"sim": eng}, fd.FrontDoorConfig(deadline_s=2 ** -6),
                        clock=clock, sleep=clock.sleep)
    rep = door.serve(fd.trace_arrivals(
        "sim", TIMES, [sim.SimRequest(uid=i) for i in range(len(TIMES))]))
    assert len(rep.groups) == 3
    for g in rep.groups:
        assert (g.enqueue_s, g.wait_s, g.collect_s) == (0.0, 0.0, 0.0)
        assert g.close_s <= g.dispatch_s
    for lat in rep.latencies:
        assert lat.late_s + lat.batch_s + lat.staging_s == lat.queue_s


def test_lm_engine_groups_keep_defaults(llama):
    cfg = llama[0]
    eng = _lm_engine(llama, max_slots=2, max_new_tokens=3)
    rng = np.random.default_rng(0)
    rec = eng.submit([Request(uid=i, prompt=rng.integers(
        0, cfg.vocab, (5,)).astype(np.int32)) for i in range(2)])
    assert len(eng.drain_all()) == 2
    assert rec.done_t is not None
    assert (rec.enqueue_s, rec.wait_s, rec.collect_s) == (0.0, 0.0, 0.0)


def test_span_adds_to_its_field_and_stamps_start():
    clock = TickingClock()
    rec = rt.GroupRecord(uids=(0,), index=5, variant="v", bucket=2, size=1)
    with rt.Span("reason.wait", clock, rec, "wait_s", group=5) as s:
        pass
    with rt.Span("reason.wait", clock, rec, "wait_s", group=5):
        pass
    assert s.start == TICK and s.elapsed == TICK
    assert rec.wait_s == 2 * TICK and rec.enqueue_s == 0.0


def _traced_spans(tmp_path, schedule="overlap"):
    """The program's span events of a warm serve traced on the CPU."""
    from jax.profiler import ProfileData

    cfg, _, eng = _oracle_engine(schedule=schedule)
    _serve(eng, _oracle_requests(cfg, len(TIMES), seed=7),
           TickingClock())  # warm
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(eng, _oracle_requests(cfg, len(TIMES)), TickingClock())
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    return eng, [e for ln in host.lines for e in ln.events
                 if e.name.startswith(("frontdoor.", "reason."))]


def test_profiler_trace_has_the_spans(tmp_path):
    """A traced serve on the CPU shows the front-door's and the engine's
    spans on the host plane, the group's index as a stat; the admit spans
    leave the closes out."""
    _, spans = _traced_spans(tmp_path)
    events = [(e.name, dict(e.stats)) for e in spans]
    names = {n for n, _ in events}
    assert {"frontdoor.admit", "frontdoor.close", "frontdoor.poll",
            "reason.stage", "reason.enqueue", "reason.wait",
            "reason.collect"} <= names
    grouped = {n for n, stats in events if "group" in stats}
    assert grouped == names - {"frontdoor.admit", "frontdoor.poll"}
    closes = sorted(stats["group"] for n, stats in events
                    if n == "frontdoor.close")
    assert len(closes) == 3 and closes == list(range(closes[0],
                                                     closes[0] + 3))
    admit_iv, close_iv = ([(e.start_ns, e.start_ns + e.duration_ns)
                           for e in spans if e.name == name]
                          for name in ("frontdoor.admit", "frontdoor.close"))
    assert admit_iv and not any(a < d and c < b for a, b in admit_iv
                                for c, d in close_iv)


def test_sequential_waits_once_per_stage(tmp_path):
    """Under ``sequential`` each stage's block is the group's only wait:
    the collect does not block on the outputs again."""
    eng, spans = _traced_spans(tmp_path, "sequential")
    waits: dict = {}
    for e in spans:
        if e.name == "reason.wait":
            stats = dict(e.stats)
            waits.setdefault(stats["group"], []).append(stats["stage"])
    names = [s.name for s in eng.schedules["oracle"].stages]
    assert len(waits) == 3
    assert all(stages == names for stages in waits.values())
