"""Pallas TPU kernel: fused VSA unbind -> dense classify head.

The symbolic tail of the MIMONet pipeline — per-channel circular
correlation against the binding keys followed by the shared dense head —
is two separate launches in the staged schedule (``unbind`` then
``classify``), each a host-visible dispatch per admission group.  This
kernel runs the whole tail in one ``pallas_call``: each grid step
materializes one key block's correlation circulant in VMEM (the same
log2(d) roll-select builder as the circ_conv kernel), unbinds the query
tile against it on the MXU and immediately multiplies into the classify
head, accumulating logits across blocks without ever writing the unbound
codes back to HBM.

Grid: (N / tile_n, K, B) with the VSA block axis innermost so each output
tile stays resident while its B partial products accumulate.  The wrapper
lays the operands out so every block's last two dims are Mosaic-tileable:
queries as ``(B, N, d)`` tiles of ``(tn, d)``, keys as ``(K, B, 1, d)``
rows, logits as ``(K, N, C)`` tiles of ``(tn, C)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.circ_conv.kernel import _circulant, rev_fixed0


def _unbind_classify_kernel(x_ref, k_ref, w_ref, b_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)            # (tn, d): reversed queries
    key = k_ref[...].astype(jnp.float32)          # (1, d): reversed key
    # corr(key, x)[n] = Σ_m x[m]·key[(m-n)%d] = Σ_p x_rev[p]·key_rev[(n+p)%d]
    c = _circulant(key)[0]                        # (d, d): c[n, p] = key_rev[n+p]
    unbound = jax.lax.dot_general(
        x, c,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                             # (tn, d)
    w = w_ref[...].astype(jnp.float32)            # (d, C)
    part = jax.lax.dot_general(
        unbound, w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                             # (tn, C)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = b_ref[...] + part

    @pl.when(pl.program_id(2) > 0)
    def _accumulate():
        o_ref[...] += part


@functools.partial(jax.jit, static_argnames=("interpret", "tile_n"))
def fused_unbind_classify(keys: jax.Array, x: jax.Array, w: jax.Array,
                          b: jax.Array, *, interpret: bool,
                          tile_n: int = 128) -> jax.Array:
    """keys: (K, B, d), x: (N, B, d), w: (B, d, C), b: (1, C) -> (N, K, C)."""
    n, blocks, d = x.shape
    k = keys.shape[0]
    c_dim = w.shape[-1]
    tn = min(tile_n, -(-n // 8) * 8)
    pad = (-n) % tn
    xt = jnp.pad(jnp.swapaxes(rev_fixed0(x), 0, 1),
                 ((0, 0), (0, pad), (0, 0)))
    out = pl.pallas_call(
        _unbind_classify_kernel,
        name="fused_unbind_classify",
        grid=((n + pad) // tn, k, blocks),
        in_specs=[
            pl.BlockSpec((None, tn, d), lambda i, kc, blk: (blk, i, 0)),
            pl.BlockSpec((None, None, 1, d),
                         lambda i, kc, blk: (kc, blk, 0, 0)),
            pl.BlockSpec((None, d, c_dim), lambda i, kc, blk: (blk, 0, 0)),
            pl.BlockSpec((1, c_dim), lambda i, kc, blk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, tn, c_dim),
                               lambda i, kc, blk: (kc, i, 0)),
        out_shape=jax.ShapeDtypeStruct((k, n + pad, c_dim), jnp.float32),
        interpret=interpret,
    )(xt, rev_fixed0(keys)[:, :, None, :], w, b)
    return jnp.swapaxes(out[:, :n], 0, 1)
