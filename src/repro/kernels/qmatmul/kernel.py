"""Pallas TPU kernel: mixed-precision (int8 / packed-int4) matmul.

TPU adaptation of NSFlow Sec IV-D (adaptive compute for mixed precision):
the MXU natively multiplies int8 at 2× bf16 rate; int4 operands are stored
packed two-per-byte in HBM (halving the memory-bound symbolic stream's
traffic — the same goal as the paper's DSP packing trick [30]) and unpacked
to int8 in VMEM right before the dot.

Layout:  y[m, n] = (Σ_k x_q[m, k] · w_q[k, n]) · x_scale[m] · w_scale[n]

Grid (M/bm, N/bn, K/bk); int32 accumulation in a VMEM scratch tile carried
across the K grid dimension, scales applied on the last K step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


class Int4NotLowerable(NotImplementedError):
    """The compiled (Mosaic) qmatmul was asked for packed-int4 weights."""


def unpack_int4(w: jax.Array) -> jax.Array:
    """(K, N//2) int8, two nibbles per byte -> (K, N) int8 in [-8, 7]."""
    low = jax.lax.shift_right_arithmetic(jax.lax.shift_left(w, jnp.int8(4)), jnp.int8(4))
    high = jax.lax.shift_right_arithmetic(w, jnp.int8(4))
    return jnp.stack([low, high], axis=-1).reshape(w.shape[0], w.shape[1] * 2)


def _qmm_kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref, *, n_k: int,
                int4: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x, w = x_ref[...], w_ref[...]
    if int4:
        # interpret mode only (see qmatmul): widen to int32, since XLA:CPU
        # emits invalid IR for the int8 dot at a 1-column packed block
        # over several K steps
        x, w = x.astype(jnp.int32), unpack_int4(w).astype(jnp.int32)
    acc_ref[...] += jax.lax.dot_general(
        x, w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        scale = xs_ref[...] * ws_ref[...]  # (bm, 1) * (1, bn)
        o_ref[...] = (acc_ref[...].astype(jnp.float32) * scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("int4", "interpret", "bm", "bn", "bk",
                                             "out_dtype"))
def qmatmul(x_q: jax.Array, w_q: jax.Array, x_scale: jax.Array, w_scale: jax.Array,
            *, int4: bool = False, interpret: bool, bm: int = 128,
            bn: int = 128, bk: int = 128, out_dtype=jnp.float32) -> jax.Array:
    """x_q: (M, K) int8; w_q: (K, N) int8 — or (K, N//2) packed when int4.

    x_scale: (M,) f32 per-row; w_scale: (N,) f32 per-column. -> (M, N).

    Packed int4 runs in interpret mode only: Mosaic cannot lower the
    nibble interleave in :func:`unpack_int4` (a lane-dim shape cast), so
    the compiled path raises :class:`Int4NotLowerable`.
    """
    if int4 and not interpret:
        raise Int4NotLowerable(
            "qmatmul int4: Mosaic has no lowering for the packed-nibble "
            "unpack; serve int4 weights through the xla lowering "
            "(REPRO_BACKEND=qmatmul=xla) or use int8")
    m, k = x_q.shape
    n = w_q.shape[1] * (2 if int4 else 1)
    bm, bk = min(bm, m), min(bk, k)
    bn = min(bn, n)
    if int4 and bn % 2:
        bn += 1
    pad_m, pad_n, pad_k = (-m) % bm, (-n) % bn, (-k) % bk
    if pad_m or pad_k:
        x_q = jnp.pad(x_q, ((0, pad_m), (0, pad_k)))
        x_scale = jnp.pad(x_scale, (0, pad_m))
    if pad_k or pad_n:
        w_q = jnp.pad(w_q, ((0, pad_k), (0, pad_n // 2 if int4 else pad_n)))
        w_scale = jnp.pad(w_scale, (0, pad_n))
    mm, nn, kk = m + pad_m, n + pad_n, k + pad_k
    n_k = kk // bk
    wbn = bn // 2 if int4 else bn
    out = pl.pallas_call(
        functools.partial(_qmm_kernel, n_k=n_k, int4=int4),
        name=f"qmm_int{4 if int4 else 8}",
        grid=(mm // bm, nn // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, q: (i, q)),
            pl.BlockSpec((bk, wbn), lambda i, j, q: (q, j)),
            pl.BlockSpec((bm, 1), lambda i, j, q: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, q: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, q: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x_q, w_q, x_scale[:, None], w_scale[None, :])
    return out[:m, :n]
