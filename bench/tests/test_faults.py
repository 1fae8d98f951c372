"""A run with the timed path broken underneath has to come out not correct.

Each test drives the harness end to end on a tiny cell on the CPU (skipping
only its look for the chip), with one fault planted between set-up and the
window.  The NVSA cells keep no state from group to group and use one chip,
so the faults they can have are an altered answer and half of a group left
out.  Run by path: ``python -m pytest bench/tests``."""

import dataclasses

import numpy as np
import pytest

from bench import harness
from bench.tests import tiny

SEED = 2 ** 33 + 11


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.root(tmp_path_factory.mktemp("checkout"))


def run(root, fault):
    return harness.run(harness.load("nvsa.raven", root), SEED, 1.5,
                       trace=False, require_tpu=False, after_setup=fault)


def test_sound_run_is_correct(root):
    out = run(root, None)
    assert out["correct"], out["checks"]


def answer_altered(system):
    """The NSAI engine hands out a wrong answer for one row of each group."""
    eng = system.engine
    collect = eng._collect

    def bad(batch, *args, **kw):
        collect(batch, *args, **kw)
        res = eng._ready[batch[0].uid]
        eng._ready[batch[0].uid] = dataclasses.replace(
            res, answer_logprobs=np.roll(res.answer_logprobs, 1))

    eng._collect = bad


def half_group(system):
    """Half of each group is left out: its rows are computed on the other
    half's inputs."""
    eng = system.engine
    stage = eng._stage

    def bad(batch, sched):
        keep = batch[:(len(batch) + 1) // 2]
        return stage(keep + keep[:len(batch) - len(keep)], sched)

    eng._stage = bad


@pytest.mark.parametrize("fault", [answer_altered, half_group],
                         ids=["nvsa-answer", "nvsa-half-group"])
def test_fault_is_not_correct(root, fault):
    out = run(root, fault)
    assert not out["correct"], out["checks"]
    assert out["failed"] > 0
