"""Reason engine: median, per group, of the time the host blocked on the
device (``reason.wait`` spans, ``ServedGroup.wait_s``), over the groups
finished before the profiler started."""

from bench import spans


def read(ctx):
    return spans.median_ms([g.wait_s for g in spans.groups(ctx)])
