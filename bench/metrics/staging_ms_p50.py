"""Reason engine: median, per group, of close to dispatch (the
``reason.stage`` span: ingest, stack, pad, ``device_put``), over the groups
finished before the profiler started."""

from bench import spans


def read(ctx):
    return spans.median_ms([spans.staging_s(g) for g in spans.groups(ctx)])
