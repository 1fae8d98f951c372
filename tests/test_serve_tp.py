"""Serve-path tensor parallelism: TP decode must be token-for-token
identical to single-device decode.

Runs in a subprocess so we can request 4 host devices without polluting
the main test session's device count.  Covers the preferred-axis TP rules
(stablelm smoke: heads/kv/mlp all divide 2- and 4-way meshes) and the
FALLBACK_TP_AXES path (llama smoke: n_kv_heads=2 does not divide the
4-way model axis, so the kv projection re-shards its embed dim), plus the
tp-exceeds-devices error naming the XLA_FLAGS escape hatch.
"""

import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
from repro.configs import ARCHS
from repro.configs import base as cbase
from repro.serve.engine import Request, ServeConfig

assert jax.device_count() == 4


def toks(arch, tp):
    scfg = ServeConfig(max_new_tokens=8, max_slots=2, max_len=64,
                       decode_block=4)
    eng, cfg = cbase.lm_engine(arch, ARCHS[arch].make_smoke(), scfg, tp=tp)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, (12,))
                    .astype(np.int32)) for i in range(4)]
    res = eng.run(reqs)
    return {u: res[u].tokens.tolist() for u in res}


# preferred-axis TP: every sharded dim divides the 2- and 4-way meshes
ref = toks("stablelm-3b", 1)
assert any(len(t) for t in ref.values())
for tp in (2, 4):
    assert toks("stablelm-3b", tp) == ref, f"stablelm-3b tp={tp} diverged"
    print(f"stablelm-3b tp{tp}: token stream identical")

# FALLBACK_TP_AXES: llama smoke's kv axis (2 heads) does not divide the
# 4-way model axis -> spec_to_pspec re-shards the embed dim instead
from repro.distributed import sharding_rules as sr
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh(1, 4)
ps = sr.spec_to_pspec(("embed", "kv", "hd"), (64, 2, 16), mesh,
                      sr.TP_RULES, min_shard_elems=0)
assert tuple(ps) == ("model",), f"fallback did not engage: {tuple(ps)}"
ref = toks("llama3.2-3b", 1)
assert toks("llama3.2-3b", 4) == ref, "llama3.2-3b tp=4 (fallback) diverged"
print("llama3.2-3b tp4: fallback-sharded token stream identical")

# tp beyond the device pool fails with the escape hatch in the message
try:
    cbase.lm_engine("stablelm-3b", ARCHS["stablelm-3b"].make_smoke(), tp=8)
except ValueError as e:
    assert "xla_force_host_platform_device_count" in str(e), e
else:
    raise AssertionError("tp=8 on 4 devices should have raised")
print("SERVE_TP_OK")
"""


def test_serve_tp_token_identity_subprocess():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert "SERVE_TP_OK" in r.stdout, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
