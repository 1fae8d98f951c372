"""NVSA (Hersche et al., Nature Machine Intelligence 2023), symbolic stream,
as a plain float32 reference: block codes of ``blocks`` x ``d`` built by
fractional power encoding, rule abduction on the two complete rows of a
RAVEN grid, rule execution on the third row by circular convolution and
correlation, and the answer as a softmax over candidate similarities.

Perception is the oracle: each panel's attributes arrive as exact one-hot
probability mass functions.

Circular convolution is the direct form, ``C[n] = sum_k A[k] B[(n-k) mod d]``,
computed in blocks of problems so that the d x d gather fits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.references import dot, key


def make_tables(cfg: dict, seed: int) -> dict:
    """The static VSA memory, on the device, from the seed: per attribute a
    codebook of values ``0 .. 2n-2`` and the codes of shifts +1 and -1, both
    fractional powers of one random unitary base, and one random unit-norm
    role code per attribute.  Laid out as the program's ``consts["books"]``."""
    return _make_tables(tuple(cfg["attr_sizes"]), cfg["blocks"], cfg["d"],
                        key(seed))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _make_tables(attr_sizes, blocks, d, k):
    keys = jax.random.split(k, len(attr_sizes) + 1)

    def encode(phase, values):
        spec = jnp.exp(1j * values[:, None, None] * phase[None])
        return jnp.fft.irfft(spec, n=d, axis=-1).astype(jnp.float32)

    books, shifts = [], []
    for i, n in enumerate(attr_sizes):
        phase = jax.random.uniform(keys[i], (blocks, d // 2 + 1), jnp.float32,
                                   -np.pi, np.pi)
        phase = phase.at[..., 0].set(0.0).at[..., -1].set(0.0)
        books.append(encode(phase, jnp.arange(2 * n - 1, dtype=jnp.float32)))
        shifts.append(encode(phase, jnp.array([1.0, -1.0])))
    roles = jax.random.normal(keys[-1], (len(attr_sizes), blocks, d))
    roles = roles / jnp.maximum(
        jnp.linalg.norm(roles, axis=-1, keepdims=True), 1e-9)
    return {"books": books, "shifts": shifts, "roles": roles}


def _circ(a, b, mode: str, precision: str):
    """Blockwise circular convolution (``conv``) or correlation (``corr``)
    of ``a`` with ``b`` over the last axis, direct form."""
    d = a.shape[-1]
    a, b = jnp.broadcast_arrays(a, b)
    n = jnp.arange(d)[:, None]
    k = jnp.arange(d)[None, :]
    idx = (n - k) % d if mode == "conv" else (n + k) % d
    return dot("...k,...nk->...n", a, b[..., idx], precision)


def _normalize(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def _similarity(a, b):
    return jnp.mean(jnp.sum(_normalize(a) * _normalize(b), axis=-1), axis=-1)


def _predict(rule: int, c1, c2, shifts, precision):
    if rule == 0:
        return c2
    if rule == 1:
        return _circ(c2, shifts[0][None], "conv", precision)
    if rule == 2:
        return _circ(c2, shifts[1][None], "conv", precision)
    if rule == 3:
        return _circ(c1, c2, "conv", precision)
    return _circ(c2, c1, "corr", precision)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _solve(attr_sizes, rule_temp, answer_temp, precision, tables, ctx, cand):
    """ctx, cand: (N, 8, A) int attributes -> (answer logprobs (N, 8),
    rule posteriors (A, N, R))."""
    posts, pred_codes, cand_codes = [], [], []
    for ai, nv in enumerate(attr_sizes):
        book = tables["books"][ai][:nv]
        shifts = tables["shifts"][ai]
        codes = dot("npv,vbd->npbd", jax.nn.one_hot(ctx[..., ai], nv), book,
                    precision)
        scores = jnp.stack([
            sum(_similarity(_predict(r, codes[:, r0], codes[:, r0 + 1],
                                     shifts, precision), codes[:, r0 + 2])
                for r0 in (0, 3)) / 2.0 for r in range(5)], axis=-1)
        post = jax.nn.softmax(scores / rule_temp, axis=-1)
        posts.append(post)
        preds = jnp.stack([_predict(r, codes[:, 6], codes[:, 7], shifts,
                                    precision) for r in range(5)], axis=1)
        pred_codes.append(dot("nr,nrbd->nbd", post, preds, precision))
        cand_codes.append(dot("npv,vbd->npbd",
                              jax.nn.one_hot(cand[..., ai], nv), book,
                              precision))
    roles = tables["roles"]
    pred = sum(_circ(pred_codes[ai], roles[ai][None], "conv", precision)
               for ai in range(len(attr_sizes)))
    cands = sum(_circ(cand_codes[ai], roles[ai][None, None], "conv",
                      precision) for ai in range(len(attr_sizes)))
    sims = _similarity(pred[:, None], cands)
    return jax.nn.log_softmax(sims / answer_temp, axis=-1), jnp.stack(posts)


def solve(cfg: dict, tables: dict, ctx_attrs: np.ndarray,
          cand_attrs: np.ndarray, precision: str = "highest",
          block: int = 32) -> tuple[np.ndarray, np.ndarray]:
    """Reference answers for (N, 8, A) attribute arrays, ``block`` problems
    at a time (the last block padded).  Returns numpy (N, 8) answer
    log-probabilities and (N, A, R) rule posteriors."""
    n = len(ctx_attrs)
    logps, posts = [], []
    for s in range(0, n, block):
        c, k = ctx_attrs[s:s + block], cand_attrs[s:s + block]
        pad = block - len(c)
        if pad:
            c = np.concatenate([c, np.repeat(c[-1:], pad, 0)])
            k = np.concatenate([k, np.repeat(k[-1:], pad, 0)])
        lp, po = _solve(tuple(cfg["attr_sizes"]), cfg["rule_temp"],
                        cfg["answer_temp"], precision, tables,
                        jnp.asarray(c), jnp.asarray(k))
        logps.append(np.asarray(lp)[:block - pad])
        posts.append(np.asarray(po).transpose(1, 0, 2)[:block - pad])
    return np.concatenate(logps), np.concatenate(posts)
