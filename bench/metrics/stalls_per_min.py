"""Reason engine: groups whose host blocked on the device (``reason.wait``
spans, ``ServedGroup.wait_s``) for more than 100 ms, per minute read, over
the groups finished before the profiler started."""

from bench import spans


def read(ctx):
    gs = spans.groups(ctx)
    if not gs:
        return None
    stalls = sum(1 for g in gs if g.wait_s > spans.STALL_S)
    return stalls * 60.0 / spans.until_s(ctx)
