"""One reader per metric, ``<metric name>.py``, each with ``read(ctx)``: the
metric's value, or None where the run holds nothing for it to read.  The
harness loads a reader by the metric's name in ``BENCHMARK.json``.

``ctx`` holds ``seconds`` (the window), ``setup_s``, ``report`` (the
front door's ``FrontDoorReport``), ``model``, ``system`` (the cell's
system, with ``units_done``), ``stats`` (the engine's counters over the
window), ``peaks`` (``bench.peaks``), ``trace`` (the reduced trace of a
``--trace 1`` run, else None) and what the system adds (``flops_per_unit``,
``circ_conv`` for NSAI cells).
"""

from __future__ import annotations

import numpy as np


def latencies_ms(ctx: dict, field: str) -> np.ndarray:
    """``field`` (``total_s``, ``queue_s``, ``service_s``) of every request
    due in the window, in ms."""
    return np.array([getattr(l, field) for l in ctx["report"].latencies
                     if l.model == ctx["model"]]) * 1e3


def percentile(ctx: dict, field: str, q: float):
    x = latencies_ms(ctx, field)
    return float(np.percentile(x, q)) if len(x) else None


def rate(ctx: dict) -> float:
    """Work units (problems) finished in the window, per second."""
    return ctx["system"].units_done(ctx["seconds"]) / ctx["seconds"]


def idle_share(ctx: dict):
    t = ctx["trace"]
    if t is None or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
