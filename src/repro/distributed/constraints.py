"""Ambient-mesh-aware sharding constraints.

``maybe_constrain(x, axes)`` applies ``with_sharding_constraint`` when the
named mesh axes exist in the ambient (jit-context) mesh, and is a no-op on
host-only runs — so model code can carry distribution hints without
depending on a mesh being present (smoke tests, examples).
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as PS


def _ambient_axes() -> tuple:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return ()
    return tuple(mesh.axis_names)


def maybe_constrain(x: jax.Array, axes: tuple):
    """axes: per-dim mesh axis name (or tuple of names, or None).

    Dims whose axis is absent from the ambient mesh fall back to None.
    """
    names = _ambient_axes()
    if not names:
        return x
    spec = []
    for a in axes:
        if a is None:
            spec.append(None)
        elif isinstance(a, tuple):
            present = tuple(ax for ax in a if ax in names)
            spec.append(present if present else None)
        else:
            spec.append(a if a in names else None)
    while spec and spec[-1] is None:
        spec.pop()
    return jax.lax.with_sharding_constraint(x, PS(*spec))


def batch_seq_heads(x: jax.Array):
    """(B, S, H, hd) activation: batch over data axes, heads over model."""
    return maybe_constrain(x, (("pod", "data"), None, "model", None))


def batch_only(x: jax.Array):
    return maybe_constrain(x, (("pod", "data"),) + (None,) * (x.ndim - 1))
