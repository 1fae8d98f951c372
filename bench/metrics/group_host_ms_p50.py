"""Reason engine: median, per group, of the host's own work on it: staging
(close to dispatch) plus the dispatch of its stages (``enqueue_s``) plus
the copy back and unpack (``collect_s``), over the groups finished before
the profiler started."""

from bench import spans


def read(ctx):
    return spans.median_ms([spans.staging_s(g) + g.enqueue_s + g.collect_s
                            for g in spans.groups(ctx)])
