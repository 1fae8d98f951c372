"""The control of the check, at a size a test holds: the reference in the
precision below the configuration's (three bfloat16 passes for float32 at
``highest``), put in the program's place, comes out not correct, while the
program comes out correct.

On the chip the same comparison runs at the cells' own sizes
(``python3 -m bench.control``).
Run by path: ``python -m pytest bench/tests``."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.references import bf16_round
from bench.tests import tiny

# the tiny cell's limit, 1.5e-5, set from readings on the CPU at d=32
# (program 3.8e-6 to 5.7e-6, control 2.9e-5 to 4.9e-5)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("seed", [3, 2 ** 32 + 5])
def test_control_is_not_correct(root, seed):
    out = harness.run(harness.load("nvsa.raven", root), seed, 1.5,
                      trace=False, require_tpu=False, control=True)
    assert out["correct"], json.dumps(out["checks"])
    assert not out["control"]["correct"], json.dumps(out["control"])
    assert out["control"]["failed"] > 0


def test_bf16_round_is_the_cast():
    x = np.random.default_rng(0).standard_normal(4096).astype(np.float32)
    x = jnp.asarray(np.concatenate([x, x * 1e-3, x * 1e3]))
    want = x.astype(jnp.bfloat16).astype(jnp.float32)
    assert bool((bf16_round(x) == want).all())
