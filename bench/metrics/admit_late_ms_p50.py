"""Front door: median time from a request's due time to the serving loop
taking it off the stream (``RequestLatency.late_s``), over the requests
finished before the profiler started."""

from bench import spans


def read(ctx):
    return spans.median_ms(spans.requests(ctx, "late_s"))
