"""Process start to warm-up done, compiles included (host clock)."""


def read(ctx):
    return ctx["setup_s"]
