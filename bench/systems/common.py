"""Pieces the systems share."""

from __future__ import annotations

import gc

import jax


def same_layout(name: str, have, want) -> None:
    """Raise unless two pytrees have one structure, shapes and dtypes."""
    hs, ws = jax.tree.structure(have), jax.tree.structure(want)
    if hs != ws:
        raise ValueError(f"{name}: the program's tree {hs} is not the "
                         f"benchmark's {ws}")
    for a, b in zip(jax.tree.leaves(have), jax.tree.leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"{name}: program leaf {a.shape} {a.dtype} is "
                             f"not the benchmark's {b.shape} {b.dtype}")


def free() -> None:
    """Return what the program held on the device before the reference
    runs (a process's peak never falls again, so the reference runs after
    the program's state is gone)."""
    gc.collect()
    jax.clear_caches()
    gc.collect()


def stats_delta(before: dict, after: dict, keys) -> dict:
    return {k: after[k] - before[k] for k in keys}
