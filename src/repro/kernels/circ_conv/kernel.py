"""Pallas TPU kernel: blockwise circular convolution / correlation.

TPU adaptation of NSFlow's AdArray passing-register streaming (Sec IV-B).
A TPU has no per-PE register muxes, so instead of skew-streaming the second
operand we *materialize its circulant matrix in VMEM* with log2(d)
roll-select steps (each roll is a static concatenate — VPU-friendly), then
feed the MXU:

    conv:  C[n, k] = y[(n-k) mod d]  ->  out = x @ C^T
    corr:  C[n, k] = y[(n+k) mod d]  ->  out = x @ C^T

Both modes run corr's builder: ``conv(x, y) == corr(x_rev, y)`` with
``x_rev[k] = x[(-k) mod d]``.  The reversal happens in the wrapper, before
the ``pallas_call``, because Mosaic has no lowering for ``rev`` and rejects
the forward-rolling builder's layout at d >= 256.

Two grid layouts:
- ``elem``  — pairwise binding of N (x_i, y_i) pairs: per-row circulants,
  batched mat-vec. Low-reuse, the "symbolic stream" of the paper.  Every
  (pair, block) row is independent, so the wrapper flattens ``(N, B, d)``
  to ``(N*B, d)`` rows and tiles them ``tn`` at a time.
- ``dict``  — N queries against M static dictionary entries: one circulant
  per dictionary entry is reused by a whole (tile_n × d) MXU matmul. This is
  the high-reuse path the TPU rewrite unlocks.  The wrapper moves the VSA
  block axis in front (``(B, N, d)``) so each block is a ``(tn, d)`` tile.

Mosaic tiles the last two block dims by (8, 128) unless a dim spans the
whole array, so row tiles are multiples of 8 and ``d`` is either a multiple
of 128 or the whole last dim.  ``d`` must be a power of two on the compiled
path (NVSA block dims are 256/512); the registry routes other shapes to the
XLA gather reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

# Scoped-VMEM planning for the elem kernel: the roll-select builder keeps
# about three (tn, d, d) f32 copies live (the circulant, its roll, the
# select result).  Row tiles come out of _ELEM_BUDGET; a tile of 8 rows
# that still overflows the compiler's 16 MiB default gets an explicit
# limit, capped below the v5e core's 128 MiB.
_LIVE_COPIES = 3
_ELEM_BUDGET = 12 * 2 ** 20
_DEFAULT_SCOPED_VMEM = 16 * 2 ** 20
_MAX_VMEM = 100 * 2 ** 20


def _circulant(base: jax.Array) -> jax.Array:
    """base: (R, d) -> (R, d, d) with out[r, n, k] = base[r, (n+k) mod d].

    Binary-decomposition build: log2(d) static rolls + masked selects.
    """
    r, d = base.shape
    m = jnp.broadcast_to(base[:, None, :], (r, d, d))
    n_idx = jax.lax.broadcasted_iota(jnp.int32, (1, d, 1), 1)
    shift = 1
    while shift < d:
        rolled = jnp.roll(m, -shift, axis=-1)
        take = ((n_idx // shift) % 2) == 1
        m = jnp.where(take, rolled, m)
        shift *= 2
    return m


def rev_fixed0(x: jax.Array) -> jax.Array:
    """x_rev[k] = x[(-k) mod d]: reverse all but the 0th element."""
    return jnp.concatenate([x[..., :1], jnp.flip(x[..., 1:], axis=-1)],
                           axis=-1)


def _corr_operand(x: jax.Array, mode: str) -> jax.Array:
    """The left operand that makes ``mode`` a correlation (XLA, pre-kernel)."""
    if mode == "corr":
        return x
    if mode != "conv":
        raise ValueError(f"mode must be 'conv' or 'corr', got {mode!r}")
    return rev_fixed0(x)


def _elem_kernel(x_ref, y_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)  # (tn, d)
    c = _circulant(y_ref[...].astype(jnp.float32))  # (tn, d, d)
    # out[r, n] = sum_k x[r, k] * c[r, n, k]  — batched matvec
    out = jax.lax.dot_general(
        c, x,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = out.astype(o_ref.dtype)


def _dict_kernel(x_ref, y_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)  # (tn, d)
    c = _circulant(y_ref[...].astype(jnp.float32))[0]  # (d, d)
    # out[r, n] = sum_k x[r, k] * c[n, k]  — (tn, d) @ (d, d)^T  -> MXU
    out = jax.lax.dot_general(
        x, c,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = out.astype(o_ref.dtype)


def _elem_tile(d: int) -> int:
    """Rows per elem tile: a multiple of 8 (Mosaic's sublane tile), at
    least 8, such that the live circulant copies fit ``_ELEM_BUDGET``."""
    rows = _ELEM_BUDGET // (_LIVE_COPIES * d * d * 4)
    return max(8, min(64, rows // 8 * 8))


def _elem_vmem_limit(tn: int, d: int) -> int:
    """Scoped-VMEM limit for one elem tile: the live circulant copies plus
    double-buffered (tn, d) x / y / out tiles, with 25% headroom."""
    need = _LIVE_COPIES * tn * d * d * 4 + 3 * 2 * tn * d * 4
    return min(_MAX_VMEM, max(_DEFAULT_SCOPED_VMEM, need + need // 4))


@functools.partial(jax.jit, static_argnames=("mode", "interpret", "tile_n"))
def circ_elem(x: jax.Array, y: jax.Array, *, mode: str = "conv",
              interpret: bool, tile_n: int | None = None) -> jax.Array:
    """Pairwise binding. x, y: (N, B, d) -> (N, B, d)."""
    n, b, d = x.shape
    x = _corr_operand(x, mode)
    rows = n * b
    tn = tile_n or min(_elem_tile(d), -(-rows // 8) * 8)
    pad = (-rows) % tn
    xr = jnp.pad(x.reshape(rows, d), ((0, pad), (0, 0)))
    yr = jnp.pad(y.reshape(rows, d), ((0, pad), (0, 0)))
    spec = pl.BlockSpec((tn, d), lambda i: (i, 0))
    out = pl.pallas_call(
        _elem_kernel,
        name=f"circ_elem_{mode}",
        grid=((rows + pad) // tn,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows + pad, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_elem_vmem_limit(tn, d)),
        interpret=interpret,
    )(xr, yr)
    return out[:rows].reshape(n, b, d)


@functools.partial(jax.jit, static_argnames=("mode", "interpret", "tile_n"))
def circ_dict(x: jax.Array, dictionary: jax.Array, *, mode: str = "conv",
              interpret: bool, tile_n: int = 128) -> jax.Array:
    """N queries against M dictionary entries.

    x: (N, B, d), dictionary: (M, B, d) -> (N, B, M, d).
    """
    n, b, d = x.shape
    m = dictionary.shape[0]
    x = _corr_operand(x, mode)
    tn = min(tile_n, -(-n // 8) * 8)
    pad = (-n) % tn
    xt = jnp.pad(jnp.swapaxes(x, 0, 1), ((0, 0), (0, pad), (0, 0)))
    bt = jnp.swapaxes(dictionary, 0, 1)[:, :, None, :]  # (B, M, 1, d)
    out = pl.pallas_call(
        _dict_kernel,
        name=f"circ_dict_{mode}",
        grid=(b, m, (n + pad) // tn),
        in_specs=[
            pl.BlockSpec((None, tn, d), lambda j, k, i: (j, i, 0)),
            pl.BlockSpec((None, None, 1, d), lambda j, k, i: (j, k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, tn, d),
                               lambda j, k, i: (j, k, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m, n + pad, d), x.dtype),
        interpret=interpret,
    )(xt, bt)
    return jnp.transpose(out[:, :, :n], (2, 0, 1, 3))  # (N, B, M, d)
