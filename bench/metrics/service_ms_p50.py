"""Reason engine: median time from a group's dispatch to its answers on
the host (``RequestLatency.service_s``)."""

from bench.metrics import percentile


def read(ctx):
    return percentile(ctx, "service_s", 50)
