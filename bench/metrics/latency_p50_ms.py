"""Median, over every request due in the window, of due time to answer on
the host (the front door's stamps; the due time is the schedule's, so a
stall counts against the requests behind it)."""

from bench.metrics import percentile


def read(ctx):
    return percentile(ctx, "total_s", 50)
