"""Model step: the share of the chip's bfloat16 peak that the problems
answered in the window need (operations per problem from the model's
shapes, ``bench.work.nvsa``).  The stages run float32 at highest precision,
so this reads low against the bfloat16 peak."""

from bench.metrics import rate


def read(ctx):
    if ctx["peaks"] is None:
        return None
    if ctx["unit"] != "problem":
        return None
    return 100.0 * ctx["flops_per_unit"] * rate(ctx) / ctx["peaks"]["bf16_flops"]
