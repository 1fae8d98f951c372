"""The work counts against counts made by hand at small sizes.

Run by path: ``python -m pytest bench/tests``."""

from bench.work import nvsa


def test_circ_conv_counts_direct_form():
    # 1 attribute, 1 block of d=4: per attribute 4 rules x 2 rows + 4
    # executions + 1 role on the prediction + 8 roles on the candidates
    cfg = {"attr_sizes": [3], "blocks": 1, "d": 4}
    assert nvsa.block_binds(cfg) == 21
    assert nvsa.circ_conv(cfg) == {"flops": 21 * 2 * 16, "bytes": 21 * 48}
    cfg = {"attr_sizes": [5, 6, 8], "blocks": 4, "d": 256}
    assert nvsa.block_binds(cfg) == 252


def test_symbolic_counts():
    cfg = {"attr_sizes": [2], "blocks": 1, "d": 2}
    codes = 2 * 8 * 2 * 1 * 2 * 2       # 16 panels x 2 values x d, x2 ops
    circ = 21 * 2 * 4
    mix = 2 * 5 * 1 * 2
    sims = (5 * 2 + 8) * 3 * 2 * 1 * 2
    assert nvsa.symbolic(cfg) == codes + circ + mix + sims


def test_resnet18_counts():
    # width 1, 8x8 image, 1 channel: stem 7x7/2 -> 4x4, pool -> 2x2, stage 1
    # at 2x2 with 1 channel, then 1x1 (2 ch), 1x1 (4 ch), 1x1 (8 ch)
    stem = 2 * 49 * 1 * 1 * 16
    s1 = 4 * 2 * 9 * 1 * 1 * 4
    s2 = 2 * 1 * 2 * 1 + 2 * 9 * 2 * 1 + 3 * 2 * 9 * 4 * 1
    s3 = 2 * 2 * 4 + 2 * 9 * 2 * 4 + 3 * 2 * 9 * 16
    s4 = 2 * 4 * 8 + 2 * 9 * 4 * 8 + 3 * 2 * 9 * 64
    head = 2 * 8 * 3
    assert nvsa.resnet18(1, 8, feat=3) == stem + s1 + s2 + s3 + s4 + head

