"""Plain references of the benchmark's configurations.  They import nothing
of the program; each one also makes the weights (or tables) that both the
program and the reference are given, from the run's seed."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, 64 bits included."""
    seed = int(seed)
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def bf16_round(x):
    """``x`` rounded to the nearest bfloat16 (ties to even), kept in
    float32.  Done on the bits, so that no compiler can fold the round trip
    away: XLA may drop a float32 -> bfloat16 -> float32 pair as excess
    precision, in some fusions and not in others."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    u = u + jnp.uint32(0x7FFF) + ((u >> 16) & jnp.uint32(1))
    u = u & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def dot(spec: str, a, b, precision: str = "highest"):
    """``einsum`` at float32.  ``highest`` is exact float32 products;
    ``high`` is three bfloat16 passes, the split hi*hi + hi*lo + lo*hi
    written out on the bits so that it reads the same on any backend."""
    hp = jax.lax.Precision.HIGHEST
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=hp)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")

    def split(x):
        hi = bf16_round(x)
        return hi, bf16_round(x - hi)

    ah, al = split(a)
    bh, bl = split(b)
    return (jnp.einsum(spec, ah, bh, precision=hp)
            + jnp.einsum(spec, ah, bl, precision=hp)
            + jnp.einsum(spec, al, bh, precision=hp))
