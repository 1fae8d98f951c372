"""The trace reduction against a small trace recorded on the chip.

``data/nvsa-oracle.xplane.pb`` is 20 ms of the ``nvsa-oracle.steady``
window on a TPU v5 lite (``harness.TRACE_S = 0.02``, seed 7, ``trace_keep``).
The expected numbers below were read from the file by hand, independently of
``bench.trace``: the ``bench.window`` span on the host plane, and the
``XLA Ops`` line of ``/device:TPU:0`` laid on a nanosecond grid.
Run by path: ``python -m pytest bench/tests``."""

import pathlib

import numpy as np
import pytest

from bench import trace

FIXTURE = pathlib.Path(__file__).parent / "data" / "nvsa-oracle.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace.reduce_file(str(FIXTURE))


def _grid():
    """Busy nanoseconds of the window, by a boolean grid."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(FIXTURE))
    host = pd.find_plane_with_name("/host:CPU")
    (lo, hi), = [(e.start_ns, e.start_ns + e.duration_ns)
                 for ln in host.lines for e in ln.events
                 if e.name == trace.WINDOW]
    dev = pd.find_plane_with_name("/device:TPU:0")
    ops = next(ln for ln in dev.lines if ln.name == "XLA Ops")
    busy = np.zeros(int(hi - lo) + 1, bool)
    for e in ops.events:
        a = max(int(e.start_ns - lo), 0)
        b = min(int(e.start_ns + e.duration_ns - lo), len(busy))
        if b > a:
            busy[a:b] = True
    return (hi - lo) * 1e-9, busy.sum() * 1e-9


def test_window_and_busy(summary):
    window_s, busy_s = _grid()
    assert summary["devices"] == 1
    assert summary["window_s"] == pytest.approx(window_s, abs=1e-9)
    assert summary["busy_s"] == pytest.approx(busy_s, abs=1e-9)
    assert 0 < summary["busy_s"] < summary["window_s"]
    assert summary["window_s"] == pytest.approx(EXPECTED["window_s"], rel=1e-6)
    assert summary["busy_s"] == pytest.approx(EXPECTED["busy_s"], rel=1e-6)


def test_kernels_and_top_ops(summary):
    assert trace.kernel_s(summary, "circ_") == pytest.approx(
        EXPECTED["circ_s"], rel=1e-6)
    assert summary["top_ops"][0][0] == EXPECTED["top_op"]
    assert sum(v for _, v in summary["idle_gaps"]) == pytest.approx(
        summary["window_s"] - summary["busy_s"], rel=1e-6)


def test_op_names():
    assert trace.op_name("%circ_elem_conv.48 = f32[32,256]{1,0} "
                         "custom-call(f32[32,256] %a)") == "circ_elem_conv"
    assert trace.op_name("fusion.3") == "fusion"
    assert trace.op_name("copy-start") == "copy-start"


EXPECTED = {"window_s": 0.022908105, "busy_s": 0.004638449,
            "circ_s": 0.00440345, "top_op": "circ_elem_conv"}
