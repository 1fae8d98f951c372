"""Pallas TPU kernel: causal flash attention (forward).

The §Roofline table shows every dense train/prefill cell memory-bound, with
score-tensor materialization a dominant contributor — this kernel is the
designed fix (EXPERIMENTS §Perf "identified movers"): online-softmax tiles
keep the (Sq, Skv) scores in VMEM only, one HBM pass over K/V per Q tile.

Grid (B·H, Sq/bq, Skv/bk); the running (m, l, acc) state lives in VMEM
scratch carried across the Skv grid dimension (same pattern as the qmatmul
accumulator); the output tile normalizes on the last KV step. Causal
blocks entirely above the diagonal are masked (their contribution is exp(-inf)=0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -2.0e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  n_k: int, bq: int, bk: int, scale: float, causal: bool,
                  skv: int):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # (bq, hd)
    k = k_ref[0].astype(jnp.float32)  # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = kpos < skv  # mask KV padding
    if causal:
        valid = valid & (kpos <= qpos)
    s = jnp.where(valid, s, NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == n_k - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "causal", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    scale: float, causal: bool = True, bq: int = 128,
                    bk: int = 128, interpret: bool) -> jax.Array:
    """q: (BH, Sq, hd); k, v: (BH, Skv, hd) -> (BH, Sq, hd)."""
    bh, sq, hd = q.shape
    skv = k.shape[1]
    bq, bk = min(bq, sq), min(bk, skv)
    pq, pk = (-sq) % bq, (-skv) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    n_q, n_k = (sq + pq) // bq, (skv + pk) // bk
    out = pl.pallas_call(
        functools.partial(_flash_kernel, n_k=n_k, bq=bq, bk=bk, scale=scale,
                          causal=causal, skv=skv),
        name="flash_attention_fwd",
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq + pq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]
