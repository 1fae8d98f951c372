"""What the program's layer spans left on the front door's records, for the
readers of the span metrics.

A reader counts the requests (``RequestLatency``) and groups
(``ServedGroup``) of the cell's model that finished before the profiler
started (``ctx["trace"]["from_s"]``), so the profiler's cost is left out;
without a trace it reads the whole window.  Records of a program without
the spans (no ``admit_s`` on a request, no ``wait_s`` on a group) are not
counted, so such a program reads as nothing.
"""

from __future__ import annotations

import numpy as np

# a group whose host blocked on the device longer than this stalled
STALL_S = 0.1


def until_s(ctx: dict) -> float:
    """The end of what is read, in seconds of the window."""
    t = ctx["trace"]
    return t["from_s"] if t is not None else ctx["report"].wall_time_s


def requests(ctx: dict, field: str) -> list[float]:
    """``field`` (``late_s``, ``batch_s``) of each request read."""
    cut = until_s(ctx)
    return [getattr(l, field) for l in ctx["report"].latencies
            if l.model == ctx["model"] and l.done_s <= cut
            and getattr(l, "admit_s", None) is not None]


def groups(ctx: dict) -> list:
    """The groups read, with the engine's service split."""
    cut = until_s(ctx)
    return [g for g in ctx["report"].groups
            if g.model == ctx["model"] and g.done_s <= cut
            and hasattr(g, "wait_s")]


def staging_s(g) -> float:
    """Close to dispatch: ingest, stack and copy to the device."""
    return g.dispatch_s - g.close_s


def median_ms(values) -> float | None:
    return float(np.median(values)) * 1e3 if len(values) else None
