"""Front door: median time from a request's admission to the close of its
group (``RequestLatency.batch_s``: the admission policy's wait and the
loop's time inside other groups' service), over the requests finished
before the profiler started."""

from bench import spans


def read(ctx):
    return spans.median_ms(spans.requests(ctx, "batch_s"))
