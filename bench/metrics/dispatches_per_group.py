"""Reason engine: device dispatches per admission group over the window
(``stats["dispatches"] / stats["batches"]``)."""


def read(ctx):
    s = ctx["stats"]
    return s["dispatches"] / s["batches"] if s.get("batches") else None
