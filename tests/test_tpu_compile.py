"""Compile rehearsals: the main path's Pallas kernels at published widths,
compiled by the TPU compiler for a described (not attached) v5e chip.

Interpret mode accepts block shapes, VMEM footprints and primitives that
Mosaic refuses; these compiles catch that without a chip.  Nothing runs,
so they say nothing about results or times.  The topology is described
inside a module fixture, never at import, so every test worker collects
the same tests and only the worker given this file loads the TPU library.
JAX's persistent compilation cache is off around the compiles: an entry
written for a described chip cannot be read back here.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.circ_conv import kernel as ck
from repro.kernels.qmatmul import kernel as qk
from repro.kernels.simd_fused import kernel as sk
from repro.kernels.unbind_classify import kernel as uk


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    """Lower and compile ``fn`` for the described chip; the Pallas kernel
    must survive as a Mosaic custom call."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


F32, I8 = jnp.float32, jnp.int8


@pytest.mark.parametrize("shape", [(2, 4, 256), (8, 4, 256), (64, 4, 256),
                                   (16, 4, 512)])
@pytest.mark.parametrize("mode", ["conv", "corr"])
def test_circ_elem_compiles_for_v5e(one_chip, shape, mode):
    # the row counts the served path produces: NVSA's 4 x 256 codes in
    # buckets of 2 and 8 problems and the 8-panel candidate bind, and
    # MIMONet's published width 512
    _compile(lambda x, y: ck.circ_elem(x, y, mode=mode, interpret=False),
             one_chip, (shape, F32), (shape, F32))


@pytest.mark.parametrize("d", [256, 512])
@pytest.mark.parametrize("mode", ["conv", "corr"])
def test_circ_dict_compiles_for_v5e(one_chip, d, mode):
    _compile(lambda x, c: ck.circ_dict(x, c, mode=mode, interpret=False),
             one_chip, ((64, 4, d), F32), ((9, 4, d), F32))


@pytest.mark.parametrize("d", [256, 512])
def test_simd_fused_compiles_for_v5e(one_chip, d):
    _compile(lambda q, c: sk.fused_match_prob(q, c, 0.1, interpret=False),
             one_chip, ((64, 4, d), F32), ((8, 4, d), F32))


@pytest.mark.parametrize("d", [128, 512])
def test_unbind_classify_compiles_for_v5e(one_chip, d):
    # MIMONet: K=2 channels, 4 blocks, 5 shape classes
    _compile(lambda k, x, w, b: uk.fused_unbind_classify(
        k, x, w, b, interpret=False), one_chip,
        ((2, 4, d), F32), ((8, 4, d), F32), ((4, d, 5), F32), ((1, 5), F32))


def test_qmatmul_int8_compiles_for_v5e(one_chip):
    _compile(lambda x, w, xs, ws: qk.qmatmul(x, w, xs, ws, interpret=False),
             one_chip, ((256, 512), I8), ((512, 256), I8), ((256,), F32),
             ((256,), F32))


def test_qmatmul_int4_refuses_compiled_path():
    """Packed int4 has no Mosaic lowering: the compiled path raises a named
    error instead of a compiler failure deep inside the kernel."""
    x = jnp.zeros((8, 16), I8)
    w = jnp.zeros((16, 4), I8)
    with pytest.raises(qk.Int4NotLowerable, match="int4"):
        qk.qmatmul(x, w, jnp.ones((8,)), jnp.ones((8,)), int4=True,
                   interpret=False)
