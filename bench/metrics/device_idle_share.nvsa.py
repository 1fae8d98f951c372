"""Device: the share of the traced window in which no operation ran on the
chip (``1 - busy / window``, from the profiler trace)."""

from bench.metrics import idle_share


def read(ctx):
    return idle_share(ctx) if ctx["unit"] == "problem" else None
