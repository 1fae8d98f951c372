"""Pallas TPU kernel: fused VSA similarity chain (the paper's SIMD unit).

NSFlow's custom SIMD unit (Sec IV-E) exists because the symbolic
similarity/reduction chain — blockwise normalize → dot against a dictionary
→ scale → softmax — is memory-bound: run as separate XLA ops it makes one
HBM round-trip per stage. This kernel is the TPU analogue: one VMEM pass
per query tile computing ``match_prob`` end-to-end (paper Listing 1's
``match_prob_multi_batched`` + ``sum``/``clamp`` epilogue).

Grid: (N / tile_n,). The dictionary (M entries) is small in NSAI workloads
(rule/attribute codebooks), so it lives in VMEM for the whole call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _match_prob_kernel(q_ref, d_ref, o_ref, *, temp: float, blocks: int):
    q = q_ref[...].astype(jnp.float32)  # (tn, B, d)
    dic = d_ref[...].astype(jnp.float32)  # (M, B, d)
    # blockwise L2 normalize
    qn = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-18)
    dn = dic * jax.lax.rsqrt(jnp.sum(dic * dic, axis=-1, keepdims=True) + 1e-18)
    tn = q.shape[0]
    m = dic.shape[0]
    # mean blockwise cosine == flat dot / blocks
    sims = jax.lax.dot_general(
        qn.reshape(tn, -1), dn.reshape(m, -1),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) / blocks
    z = sims / temp
    z = z - jnp.max(z, axis=-1, keepdims=True)
    e = jnp.exp(z)
    o_ref[...] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("temp", "interpret", "tile_n"))
def fused_match_prob(q: jax.Array, dictionary: jax.Array, temp: float = 1.0,
                     *, interpret: bool, tile_n: int = 128) -> jax.Array:
    """q: (N, B, d), dictionary: (M, B, d) -> probs (N, M)."""
    n, b, d = q.shape
    m = dictionary.shape[0]
    tn = min(tile_n, max(8, n))
    pad = (-n) % tn
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_match_prob_kernel, temp=temp, blocks=b),
        name="fused_match_prob",
        grid=((n + pad) // tn,),
        in_specs=[
            pl.BlockSpec((tn, b, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((m, b, d), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tn, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + pad, m), jnp.float32),
        interpret=interpret,
    )(q, dictionary)
    return out[:n]
