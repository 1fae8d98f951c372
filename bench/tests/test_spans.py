"""The span metrics' readers on a hand-built front-door report.

Each reader counts only what finished before the profiler started, reads the
whole window without a trace, and reads nothing (None) where nothing
finished or where the program's records carry no spans.  Run by path:
``python -m pytest bench/tests``."""

import types

import numpy as np
import pytest

from bench import harness

harness.program_path()

from repro.serve.frontdoor import (FrontDoorReport, RequestLatency,  # noqa: E402
                                   ServedGroup)

METRICS = ("admit_late_ms_p50", "batch_wait_ms_p50", "staging_ms_p50",
           "stalls_per_min", "group_host_ms_p50", "device_wait_ms_p50")
FROM_S = 10.0
WALL_S = 40.0


def _group(k: int, done_s: float, wait_s: float, model="nvsa"):
    """Group ``k``: two requests due at ``done_s - 0.02``; every span's
    length grows with ``k``."""
    arrival = done_s - 0.02
    admit, close = arrival + 0.001 * k, arrival + 0.002 * k
    dispatch = close + 0.0005 * k
    g = ServedGroup(model=model, uids=(2 * k, 2 * k + 1), bucket=2, size=2,
                    close_reason="full", close_s=close, dispatch_s=dispatch,
                    done_s=done_s, enqueue_s=0.0001 * k, wait_s=wait_s,
                    collect_s=0.0002 * k)
    lats = [RequestLatency(uid=u, model=model, arrival_s=arrival,
                           dispatch_s=dispatch, done_s=done_s, bucket=2,
                           close_reason="full", admit_s=admit,
                           close_s=close) for u in g.uids]
    return g, lats


# groups 1-3 finish before the profiler starts (one of them stalls),
# groups 4-5 inside the traced seconds, group 6 is another model's
PLAN = [(1, 2.0, 0.001), (2, 5.0, 0.25), (3, 9.5, 0.003),
        (4, 11.0, 0.5), (5, 13.0, 0.004)]


@pytest.fixture(scope="module")
def report():
    groups, lats = [], []
    for k, done, wait in PLAN:
        g, ls = _group(k, done, wait)
        groups.append(g)
        lats += ls
    g, ls = _group(6, 3.0, 0.9, model="other")
    return FrontDoorReport(results={}, latencies=lats + ls,
                           groups=groups + [g], wall_time_s=WALL_S)


def _ctx(report, trace_from_s):
    trace = None if trace_from_s is None else \
        {"from_s": trace_from_s, "to_s": trace_from_s + 4.0,
         "busy_s": 0.8, "window_s": 4.0, "ops_s": {}, "top_ops": [],
         "idle_gaps": []}
    return {"report": report, "model": "nvsa", "trace": trace,
            "seconds": 30.0}


def expected(metric: str, ks: list[int], minutes: float) -> float:
    wait = {k: w for k, _, w in PLAN}
    staging = [0.0005 * k for k in ks]
    values = {
        "admit_late_ms_p50": [0.001 * k for k in ks],
        "batch_wait_ms_p50": [0.001 * k for k in ks],
        "staging_ms_p50": staging,
        "group_host_ms_p50": [0.0005 * k + 0.0001 * k + 0.0002 * k
                              for k in ks],
        "device_wait_ms_p50": [wait[k] for k in ks],
    }
    if metric == "stalls_per_min":
        return sum(wait[k] > 0.1 for k in ks) / minutes
    return float(np.median(values[metric])) * 1e3


@pytest.mark.parametrize("metric", METRICS)
def test_reads_only_before_the_profiler(report, metric):
    got = harness.reader(metric)(_ctx(report, FROM_S))
    assert got == pytest.approx(expected(metric, [1, 2, 3], FROM_S / 60))


@pytest.mark.parametrize("metric", METRICS)
def test_reads_the_whole_window_without_a_trace(report, metric):
    got = harness.reader(metric)(_ctx(report, None))
    assert got == pytest.approx(
        expected(metric, [1, 2, 3, 4, 5], WALL_S / 60))


@pytest.mark.parametrize("metric", METRICS)
def test_nothing_finished_reads_none(report, metric):
    assert harness.reader(metric)(_ctx(report, 1.0)) is None


@pytest.mark.parametrize("metric", METRICS)
def test_program_without_spans_reads_none(metric):
    """Records as a program without the spans makes them: no admit stamp
    on a request, no service split on a group."""
    lat = types.SimpleNamespace(uid=0, model="nvsa", arrival_s=0.0,
                                dispatch_s=0.01, done_s=0.02)
    grp = types.SimpleNamespace(model="nvsa", uids=(0,), close_s=0.03,
                                dispatch_s=0.01, done_s=0.02)
    rep = types.SimpleNamespace(latencies=[lat], groups=[grp],
                                wall_time_s=WALL_S)
    assert harness.reader(metric)(_ctx(rep, FROM_S)) is None
    assert harness.reader(metric)(_ctx(rep, None)) is None
