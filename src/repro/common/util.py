"""Small shared utilities used across the framework."""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


def cdiv(a: int, b: int) -> int:
    """Ceiling division."""
    return -(-a // b)


def pad_to_multiple(x: jax.Array, multiple: int, axis: int) -> jax.Array:
    """Zero-pad ``x`` along ``axis`` up to the next multiple of ``multiple``."""
    size = x.shape[axis]
    target = cdiv(size, multiple) * multiple
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads)


def tree_count(tree: PyTree) -> int:
    """Total number of array elements in a pytree."""
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree) if hasattr(x, "shape"))


def tree_bytes(tree: PyTree) -> int:
    """Total byte size of a pytree of arrays / ShapeDtypeStructs."""
    total = 0
    for x in jax.tree.leaves(tree):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            total += int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
    return total


def split_key(key: jax.Array, n: int) -> list[jax.Array]:
    return list(jax.random.split(key, n))


def human_bytes(n: float) -> str:
    for unit in ["B", "KiB", "MiB", "GiB", "TiB"]:
        if abs(n) < 1024.0:
            return f"{n:.2f} {unit}"
        n /= 1024.0
    return f"{n:.2f} PiB"


def human_flops(n: float) -> str:
    for unit in ["FLOP", "KFLOP", "MFLOP", "GFLOP", "TFLOP", "PFLOP"]:
        if abs(n) < 1000.0:
            return f"{n:.2f} {unit}"
        n /= 1000.0
    return f"{n:.2f} EFLOP"


def round_up_pow2(n: int) -> int:
    return 1 << max(0, math.ceil(math.log2(max(1, n))))


def mesh_context(mesh):
    """Ambient-mesh context manager (``jax.sharding.set_mesh``)."""
    return jax.sharding.set_mesh(mesh)


def shard_map_unreplicated(fn, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and
    this sets nothing.  Otherwise the cache goes to ``.jax_cache`` at the
    root of the checkout: a fixed path, because the path is part of the
    cache key.  Call it from ``main()``, never at import, so tests stay
    cache-free.  Returns the directory in force.
    """
    import os
    import pathlib

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
