"""``chip_smoke.py`` rehearsed on the CPU: its phase functions at tiny
sizes under the interpret plan (every kernel selection must be the
interpreter, answers must match the references), and its refusal to
report success anywhere but on a TPU."""

import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke as cs


@pytest.mark.parametrize("name,fn,kwargs,kernels", [
    ("nvsa", cs.nvsa_phase, dict(d=128, requests=4, max_batch=2), True),
    ("mimonet", cs.mimonet_phase, dict(requests=4, max_batch=2), True),
    ("stablelm-3b", cs.lm_phase, dict(size="smoke", requests=2), False),
    ("replicas", cs.replica_phase,
     dict(d=128, requests=6, replicas=2, max_batch=2), True),
])
def test_phase_matches_reference_on_interpret_plan(name, fn, kwargs,
                                                   kernels):
    out = fn(rate_rps=200.0, **kwargs)
    cs.check_selections(name, out, "interpret", kernels)
    assert out["failures"] == [], out


def test_mimonet_phase_runs_the_fused_kernel():
    out = cs.mimonet_phase(requests=2, max_batch=2, rate_rps=200.0)
    assert ("unbind_classify", "interpret") in out["selections"]
    assert out["fused_groups"] >= 1 and out["fused_fallback_groups"] == 0


def test_compare_logprobs_excuses_only_ties():
    ref = [[0.0, -0.0005, -5.0], [0.0, -3.0, -4.0]]
    tie = cs.compare_logprobs([[-0.0005, 0.0, -5.0], [0.0, -3.0, -4.0]],
                              ref, eps=1e-3)
    assert tie["answers_differ"] == 1
    assert tie["answers_differ_outside_ties"] == 0 and tie["within_eps"]
    flip = cs.compare_logprobs([[0.0, -0.0005, -5.0], [-3.0, 0.0, -4.0]],
                               ref, eps=1e-3)
    assert flip["answers_differ_outside_ties"] == 1
    assert not flip["within_eps"]


def test_main_refuses_without_a_tpu(capsys):
    assert cs.main([]) == 2
    out = capsys.readouterr()
    assert "needs a TPU" in out.err and '"ok"' not in out.out


def test_script_alone_fails_without_the_repo(tmp_path):
    shutil.copy(cs.__file__, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
