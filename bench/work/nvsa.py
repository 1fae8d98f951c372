"""NVSA work per problem (one RAVEN problem: 8 context and 8 candidate
panels), from a configuration's sizes.

Circular convolution is counted in direct form: a block of ``d`` output
values, each a sum of ``d`` products, is ``2 d^2`` operations, and reads
two ``d``-vectors and writes one (float32).
"""

from __future__ import annotations

RULES = 5          # constant, progression +/-1, arithmetic +/-
ROLLED_RULES = 4   # the rules that bind (all but constant)
PANELS = 8         # context panels, and candidate panels


def block_binds(cfg: dict) -> int:
    """Block-wise circular convolutions/correlations per problem: per
    attribute, 4 binding rules scored on 2 rows and executed on 1, the
    predicted code and the 8 candidate codes bound to the attribute role."""
    a = len(cfg["attr_sizes"])
    per_attr = ROLLED_RULES * 2 + ROLLED_RULES + 1 + PANELS
    return a * per_attr * cfg["blocks"]


def circ_conv(cfg: dict) -> dict:
    """``{"flops", "bytes"}`` of the circular convolutions of one problem."""
    n, d = block_binds(cfg), cfg["d"]
    return {"flops": n * 2 * d * d, "bytes": n * 3 * d * 4}


def symbolic(cfg: dict) -> int:
    """Operations of the symbolic stream of one problem: value codes from
    the attribute PMFs, the circular convolutions, the rule mixture and the
    similarities (three dot products of ``d`` per block)."""
    b, d = cfg["blocks"], cfg["d"]
    codes = sum(2 * PANELS * n * b * d * 2 for n in cfg["attr_sizes"])
    a = len(cfg["attr_sizes"])
    mix = a * 2 * RULES * b * d
    sims = (a * RULES * 2 + PANELS) * 3 * 2 * b * d
    return codes + circ_conv(cfg)["flops"] + mix + sims


def resnet18(width: int, image: int, in_ch: int = 1, feat: int = 128) -> int:
    """Operations of one panel through the ResNet-18 frontend: 7x7/2 stem,
    3x3/2 max-pool, four stages of two basic blocks (widths w, 2w, 4w, 8w,
    stride 2 entering stages 2-4, 1x1 projections there), global pool and
    a dense head to ``feat``.  Convolutions are 'SAME' padded."""
    def conv(hw, k, cin, cout):
        return 2 * k * k * cin * cout * hw * hw

    hw = -(-image // 2)
    ops = conv(hw, 7, in_ch, width)
    hw = -(-hw // 2)
    cin = width
    for stage in range(4):
        cout = width * 2 ** stage
        for blk in range(2):
            if blk == 0 and stage > 0:
                hw = -(-hw // 2)
                ops += conv(hw, 1, cin, cout)
            ops += conv(hw, 3, cin, cout) + conv(hw, 3, cout, cout)
            cin = cout
    return ops + 2 * cin * feat


def frontend(cfg: dict) -> int:
    """Operations of the perception frontend of one problem: 16 panels
    through the ResNet-18 and the attribute heads."""
    per_panel = resnet18(cfg["cnn_width"], cfg["image_size"],
                         feat=cfg["cnn_feat"])
    heads = 2 * cfg["cnn_feat"] * sum(cfg["attr_sizes"])
    return 2 * PANELS * (per_panel + heads)


def per_problem(cfg: dict) -> int:
    """Model operations of one problem as the configuration serves it."""
    ops = symbolic(cfg)
    if cfg.get("variant", "cnn") == "cnn":
        ops += frontend(cfg)
    return ops
