"""Pallas TPU kernels: blockwise circular convolution / correlation.

Two grid layouts:

- ``elem`` — pairwise binding of N (x_i, y_i) pairs, the low-reuse
  "symbolic stream" of the paper.  It computes the direct form on the VPU
  in f32, the TPU counterpart of NSFlow's AdArray passing-register
  streaming (Sec IV-B): where the FPGA passes the second operand one
  register per cycle, the kernel walks it one sublane per step through
  VMEM, and each step is a broadcast multiply-accumulate.

      conv:  out[r, n] = sum_k x[r, k] * y[r, (n - k) mod d]
      corr:  out[r, n] = sum_k x[r, k] * y[r, (n + k) mod d]

  A tile of ``R`` rows is turned on its side in VMEM: ``d`` along
  sublanes, rows along lanes.  A row tile narrower than the 128 lanes is
  repeated in ``G`` lane groups, and group ``c`` computes outputs
  ``n`` in ``[cL, (c+1)L)``, ``L = d / G``, so every vreg is full.  Step
  ``k`` then reads x's column ``k`` as one stride-0 sublane broadcast and
  y's shifted window as one unaligned sublane load: no per-step lane
  shuffles and no per-row d x d circulant.  Each output sums its ``d``
  products into ``_PARTS`` interleaved partial sums, added as a tree at
  the end; one running sum reads three to four times the error of the
  gather reference at d = 256 and 512, so the order is part of the
  kernel.

- ``dict`` — N queries against M static dictionary entries: one circulant
  per dictionary entry (log2(d) roll-select steps) is reused by a whole
  (tile_n x d) MXU matmul.  The wrapper moves the VSA block axis in front
  (``(B, N, d)``) so each block is a ``(tn, d)`` tile; conv runs corr's
  builder on ``x_rev[k] = x[(-k) mod d]`` (``rev_fixed0``), reversed before
  the ``pallas_call`` because Mosaic has no lowering for ``rev``.

Mosaic tiles the last two block dims by (8, 128) unless a dim spans the
whole array, so row tiles are multiples of 8.  ``d`` must be a power of
two >= 8 on the compiled path (NVSA block dims are 256/512); the registry
routes other shapes to the XLA gather reference.  Interpret mode serves
any ``d``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

_LANES = 128
# Interleaved partial sums per output (the accumulation order above); output
# sublanes per pass over k, so the partial sums hold 16 of the 64 vregs; and
# k-steps per loop iteration on the compiled path.  Chosen on the v5e's
# static schedule: 32 sublanes spill, and so does unrolling all d steps.
_PARTS = 8
_CHUNK = 16
_UNROLL = 64


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


def _elem_tile(rows: int, d: int) -> tuple[int, int]:
    """(R, G): rows per tile (a multiple of 8, at most the 128 lanes) and
    lane groups.  G is the largest power of two with G * R <= 128 lanes
    that splits ``d`` into groups of whole 8-sublane vregs."""
    tile = min(_LANES, -(-rows // 8) * 8)
    groups = 1
    while (2 * groups * tile <= _LANES and d % (2 * groups) == 0
           and d // (2 * groups) % 8 == 0):
        groups *= 2
    return tile, groups


def _tree_sum(parts: list[jax.Array]) -> jax.Array:
    """Pairwise sum of a power-of-two number of partial sums."""
    while len(parts) > 1:
        parts = [a + b for a, b in zip(parts[::2], parts[1::2])]
    return parts[0]


def _elem_kernel(x_ref, y_ref, o_ref, xb_ref, z_ref, ot_ref, *,
                 conv: bool, groups: int, unroll: int):
    rows, d = x_ref.shape
    span = d // groups  # outputs per lane group
    f32 = jnp.float32

    def sideways(ref):
        """(R, d) -> (d, 128): lane c*R + r holds row r, for every group c."""
        tile = [ref[...].astype(f32)] * groups
        if groups * rows < _LANES:
            tile.append(jnp.zeros((_LANES - groups * rows, d), f32))
        return jnp.concatenate(tile, axis=0).T

    xb_ref[...] = sideways(x_ref)
    yt = sideways(y_ref)
    yy = jnp.concatenate([yt, yt], axis=0)
    # z[i, (c, r)] = y[r, (c*span + i) mod d]: group c's window of y, where
    # conv finds y[n - k] at z[n - c*span + d - k] and corr y[n + k] at
    # z[n - c*span + k].
    width, block = z_ref.shape[0], min(64, d)
    for i in range(0, width, block):
        n = min(block, width - i)
        group = jax.lax.broadcasted_iota(jnp.int32, (n, _LANES), 1) // rows
        z = yy[i % d:i % d + n]
        for c in range(1, groups):
            s = (c * span + i) % d
            z = jnp.where(group == c, yy[s:s + n], z)
        z_ref[pl.ds(i, n), :] = z

    chunk = min(_CHUNK, span) if span % min(_CHUNK, span) == 0 else span

    def accumulate(j, carry):
        """Outputs m .. m+chunk-1 of every group: k = 0 .. d-1 into partial
        sum k % _PARTS, then their tree sum."""
        m = pl.multiple_of(j * chunk, chunk)

        def steps(base, n, accs):
            zs = z_ref.at[pl.ds(m + d + 1 - n - base if conv else m + base,
                                chunk + n - 1)]
            xs = xb_ref.at[pl.ds(base, n)]
            accs = list(accs)
            for u in range(n):
                xk = jnp.broadcast_to(xs[pl.ds(u, 1), :], (chunk, _LANES))
                zk = zs[pl.ds(n - 1 - u if conv else u, chunk), :]
                accs[u % _PARTS] = accs[u % _PARTS] + xk * zk
            return tuple(accs)

        accs = (jnp.zeros((chunk, _LANES), f32),) * _PARTS
        loops, rest = divmod(d, unroll)
        if loops:
            accs = jax.lax.fori_loop(
                0, loops, lambda i, a: steps(
                    pl.multiple_of(i * unroll, unroll), unroll, a), accs)
        if rest:
            accs = steps(loops * unroll, rest, accs)
        # out[i, (c, r)] = result[r, c*span + m + i]
        ot_ref[pl.ds(m, chunk), :] = _tree_sum(list(accs))
        return carry

    jax.lax.fori_loop(0, span // chunk, accumulate, 0)
    t = ot_ref[...].T  # (128, span): row c*R + r holds result[r, c*span:]
    o_ref[...] = jnp.concatenate(
        [t[c * rows:(c + 1) * rows] for c in range(groups)],
        axis=1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def circ_elem(x: jax.Array, y: jax.Array, *, mode: str = "conv",
              interpret: bool) -> jax.Array:
    """Pairwise binding. x, y: (N, B, d) -> (N, B, d)."""
    if mode not in ("conv", "corr"):
        raise ValueError(f"mode must be 'conv' or 'corr', got {mode!r}")
    n, b, d = x.shape
    rows = n * b
    tile, groups = _elem_tile(rows, d)
    pad = (-rows) % tile
    xr = jnp.pad(x.reshape(rows, d), ((0, pad), (0, 0)))
    yr = jnp.pad(y.reshape(rows, d), ((0, pad), (0, 0)))
    span = d // groups
    spec = pl.BlockSpec((tile, d), lambda i: (i, 0))
    # The interpreter compiles a short loop body faster; the sums' order is
    # the same at any unroll that is a multiple of _PARTS.
    out = pl.pallas_call(
        functools.partial(_elem_kernel, conv=mode == "conv", groups=groups,
                          unroll=_PARTS if interpret else _UNROLL),
        name=f"circ_elem_{mode}",
        grid=((rows + pad) // tile,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows + pad, d), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((d, _LANES), jnp.float32),         # x, sideways
            pltpu.VMEM((d + span, _LANES), jnp.float32),  # y's windows
            pltpu.VMEM((span, _LANES), jnp.float32),      # result, sideways
        ],
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
