"""NSAI problems answered in the window, over the window (host clock)."""

from bench.metrics import rate


def read(ctx):
    return rate(ctx) if ctx["unit"] == "problem" else None
