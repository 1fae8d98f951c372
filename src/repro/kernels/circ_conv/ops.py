"""Jit'd public wrappers for the circ_conv kernel with shape handling.

Dispatch policy comes from the active :class:`~repro.backend.registry.
LoweringPlan` (``repro.backend.registry``): compiled Pallas on TPU/GPU
(pow2 block dims >= 8 — off-shape call sites degrade past it), interpret
mode on CPU at any shape, and the exact XLA gather reference whenever the
plan forces ``xla``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend import registry
from repro.kernels.circ_conv import kernel, ref


def _circ_elem_dispatch(af: jax.Array, bf: jax.Array, mode: str) -> jax.Array:
    plan = registry.get_plan()
    low = plan.select("circ_conv", size=af.shape[-1])
    if low.is_ref:
        return ref.circ_elem_ref(af, bf, mode)
    return kernel.circ_elem(af, bf, mode=mode,
                            interpret=plan.run_interpret(low))


# Custom VJPs so the Pallas kernels are trainable. Circular-conv calculus:
#   z = conv(a, b):  da = corr(b, g),  db = corr(a, g)
#   z = corr(a, b):  da = corr(g, b),  db = conv(g, a)
# — the backward pass reuses the same kernels.


@jax.custom_vjp
def _conv_flat(a: jax.Array, b: jax.Array) -> jax.Array:
    return _circ_elem_dispatch(a, b, "conv")


def _conv_fwd(a, b):
    return _circ_elem_dispatch(a, b, "conv"), (a, b)


def _conv_bwd(res, g):
    a, b = res
    return (_circ_elem_dispatch(b, g, "corr").astype(a.dtype),
            _circ_elem_dispatch(a, g, "corr").astype(b.dtype))


_conv_flat.defvjp(_conv_fwd, _conv_bwd)


@jax.custom_vjp
def _corr_flat(a: jax.Array, b: jax.Array) -> jax.Array:
    return _circ_elem_dispatch(a, b, "corr")


def _corr_fwd(a, b):
    return _circ_elem_dispatch(a, b, "corr"), (a, b)


def _corr_bwd(res, g):
    a, b = res
    return (_circ_elem_dispatch(g, b, "corr").astype(a.dtype),
            _circ_elem_dispatch(g, a, "conv").astype(b.dtype))


_corr_flat.defvjp(_corr_fwd, _corr_bwd)


def circ_bind(a: jax.Array, b: jax.Array, mode: str = "conv") -> jax.Array:
    """Elementwise blockwise circular conv/corr with leading-dim broadcast.

    a, b: (..., blocks, d) -> (..., blocks, d). Differentiable (custom VJP).
    """
    a, b = jnp.broadcast_arrays(a, b)
    lead = a.shape[:-2]
    blocks, d = a.shape[-2:]
    n = int(np.prod(lead)) if lead else 1
    af = a.reshape(n, blocks, d)
    bf = b.reshape(n, blocks, d)
    out = _conv_flat(af, bf) if mode == "conv" else _corr_flat(af, bf)
    return out.reshape(*lead, blocks, d)


def circ_bind_dict(x: jax.Array, dictionary: jax.Array, mode: str = "conv") -> jax.Array:
    """x: (N, blocks, d) vs dictionary: (M, blocks, d) -> (N, M, blocks, d)."""
    plan = registry.get_plan()
    low = plan.select("circ_conv", size=x.shape[-1])
    if low.is_ref:
        out = ref.circ_dict_ref(x, dictionary, mode)
    else:
        out = kernel.circ_dict(x, dictionary, mode=mode,
                               interpret=plan.run_interpret(low))
    return jnp.swapaxes(out, 1, 2)  # (N, B, M, d) -> (N, M, B, d)
