"""The benchmark's copy of the RAVEN generator against the program's.

Run by path: ``python -m pytest bench/tests``."""

import itertools

import numpy as np

from bench import harness, raven


def test_panels_render_as_the_program_does():
    harness.program_path()
    from repro.data import raven as program

    cfg = program.RavenConfig(noise=0.0)
    rng = np.random.default_rng(0)
    for attrs in itertools.product(range(5), range(6), range(8)):
        a = np.array(attrs)
        np.testing.assert_array_equal(
            raven._render(a, 32, cfg.attr_sizes, 0.0, rng),
            program.render_panel(cfg, a, rng))


def test_problems_follow_one_rule_per_attribute():
    for i in range(50):
        p = raven.problem((5, 6, 8), 2 ** 40 + 1, i, image_size=16)
        assert p["context"].shape == (8, 16, 16, 1)
        full = np.concatenate([p["context_attrs"],
                               p["candidate_attrs"][p["answer"]][None]])
        grid = full.reshape(3, 3, 3)
        for ai, n in enumerate((5, 6, 8)):
            rows = grid[:, :, ai]
            assert any(all(raven._plain(r, *rows[k, :2]) == rows[k, 2]
                           for k in range(3)) for r in range(5))
        # distractors differ from the answer in exactly one attribute
        ans = p["candidate_attrs"][p["answer"]]
        diffs = (p["candidate_attrs"] != ans).sum(-1)
        assert sorted(diffs.tolist()) == [0] + [1] * 7
