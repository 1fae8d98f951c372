"""RAVEN-style progressive-matrix problems, the benchmark's own copy of the
program's generator (``repro.data.raven``), so the yardstick stays fixed.

A problem is a 3x3 grid of panels whose attributes (type, size, color)
follow one rule per attribute across each row: constant, progression +1/-1,
arithmetic +/-.  Eight candidates hold the answer and seven distractors that
each differ from it in one attribute.  Panels are rendered only for traffic
that sends images; symbolic-only traffic sends the attributes.
"""

from __future__ import annotations

import numpy as np

RULES = ("constant", "prog_plus", "prog_minus", "arith_plus", "arith_minus")


def _wrap(rule: int, a1: int, a2: int, n: int) -> int:
    return [a2, (a2 + 1) % n, (a2 - 1) % n, (a1 + a2) % n,
            (a1 - a2) % n][rule]


def _plain(rule: int, a1: int, a2: int) -> int:
    return [a2, a2 + 1, a2 - 1, a1 + a2, a1 - a2][rule]


def _ambiguous(rows: np.ndarray, rule: int, n: int) -> bool:
    """Another rule explains both complete rows but predicts another 9th."""
    for r in range(len(RULES)):
        if r == rule:
            continue
        for predict in (_plain, lambda r, a, b: _wrap(r, a, b, n)):
            if all(predict(r, rows[i, 0], rows[i, 1]) == rows[i, 2]
                   for i in (0, 1)) and \
                    predict(r, rows[2, 0], rows[2, 1]) != rows[2, 2]:
                return True
    return False


def _row(rng: np.random.Generator, rule: int, n: int) -> tuple[int, int, int]:
    for _ in range(64):
        if rule == 0:
            a = int(rng.integers(n))
            row = (a, a, a)
        elif rule == 1:
            a = int(rng.integers(0, n - 2))
            row = (a, a + 1, a + 2)
        elif rule == 2:
            a = int(rng.integers(2, n))
            row = (a, a - 1, a - 2)
        elif rule == 3:
            a = int(rng.integers(0, n - 1))
            b = int(rng.integers(0, n - a))
            row = (a, b, a + b)
        else:
            a = int(rng.integers(0, n))
            b = int(rng.integers(0, a + 1))
            row = (a, b, a - b)
        if all(0 <= v < n for v in row):
            return row
    raise RuntimeError("rule sampling failed")


def _render(attrs, size: int, sizes: tuple, noise: float,
            rng: np.random.Generator) -> np.ndarray:
    t, sz, col = (int(a) for a in attrs)
    n_sizes, n_colors = sizes[1], sizes[2]
    radius = (0.18 + 0.62 * (sz + 1) / n_sizes) * (size / 2 - 1)
    c = (size - 1) / 2.0
    yy, xx = np.mgrid[0:size, 0:size]
    dy, dx = yy - c, xx - c
    r = np.hypot(dx, dy)
    if t == 4:
        mask = r <= radius
    else:
        nv = (3, 4, 5, 6)[t]
        k = np.pi / nv
        off = np.pi / 2 if nv % 2 else k
        theta = np.arctan2(dy, dx)
        mask = r <= radius * np.cos(k) / np.cos(((theta + off) % (2 * k)) - k)
    img = np.zeros((size, size), np.float32)
    img[mask] = 0.25 + 0.75 * (col + 1) / n_colors
    if noise > 0:
        img = img + rng.normal(0, noise, img.shape).astype(np.float32)
    return np.clip(img, 0, 1)[..., None]


def problem(attr_sizes: tuple[int, ...], seed: int, index: int,
            image_size: int | None = None, noise: float = 0.02) -> dict:
    """One problem: ``context_attrs`` (8, A) and ``candidate_attrs`` (8, A)
    int32, ``answer``; with ``image_size``, also rendered ``context`` and
    ``candidates`` panels (8, H, W, 1) float32."""
    rng = np.random.default_rng([int(seed), 7, int(index)])
    a = len(attr_sizes)
    rules = [int(rng.integers(len(RULES))) for _ in range(a)]
    grid = np.zeros((3, 3, a), np.int32)
    for ai in range(a):
        for _ in range(64):
            for row in range(3):
                grid[row, :, ai] = _row(rng, rules[ai], attr_sizes[ai])
            if not _ambiguous(grid[:, :, ai], rules[ai], attr_sizes[ai]):
                break
    panels = grid.reshape(9, a)
    answer = panels[8]
    cands, seen = [answer.copy()], {tuple(answer)}
    for _ in range(256):
        if len(cands) == 8:
            break
        c = answer.copy()
        ai = int(rng.integers(a))
        c[ai] = (c[ai] + int(rng.integers(1, attr_sizes[ai]))) % attr_sizes[ai]
        if tuple(c) not in seen:
            seen.add(tuple(c))
            cands.append(c)
    while len(cands) < 8:
        c = np.array([rng.integers(s) for s in attr_sizes], np.int32)
        if tuple(c) not in seen:
            seen.add(tuple(c))
            cands.append(c)
    perm = rng.permutation(8)
    cands = np.stack(cands)[perm]
    out = {"context_attrs": panels[:8].astype(np.int32),
           "candidate_attrs": cands.astype(np.int32),
           "answer": int(np.where(perm == 0)[0][0])}
    if image_size:
        out["context"] = np.stack([_render(x, image_size, attr_sizes, noise,
                                           rng) for x in panels[:8]])
        out["candidates"] = np.stack([_render(x, image_size, attr_sizes,
                                              noise, rng) for x in cands])
    return out
