"""Front door: median wait from due time to the dispatch of the request's
group (``RequestLatency.queue_s``)."""

from bench.metrics import percentile


def read(ctx):
    return percentile(ctx, "queue_s", 50)
