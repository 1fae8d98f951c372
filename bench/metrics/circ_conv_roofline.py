"""Kernels: the circular-convolution kernels' share of their roofline.

The least time the chip could take for the problems answered inside the
traced window (their direct-form operations over the bfloat16 peak, or
their bytes over HBM bandwidth, whichever is longer), over the device time
of the ``circ_*`` kernels in the trace.  The kernels run float32, so the
bfloat16 peak bounds them from above."""

from bench.trace import kernel_s


def read(ctx):
    if ctx["peaks"] is None:
        return None
    t = ctx["trace"]
    if t is None or ctx.get("circ_conv") is None:
        return None
    busy = kernel_s(t, "circ_")
    n = ctx["system"].units_done(t["to_s"], t["from_s"])
    if busy <= 0 or n <= 0:
        return None
    p, w = ctx["peaks"], ctx["circ_conv"]
    least = max(n * w["flops"] / p["bf16_flops"],
                n * w["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / busy
