"""Kernel/oracle conformance suite: every Pallas kernel against its pure-jnp
ref in interpret mode, swept over dtypes, degenerate shapes, dispatch
boundaries (non-power-of-two d -> gather fallback), and int4 edge nibbles.

``test_kernels.py`` covers the happy-path sizes; this suite is the
adversarial sweep the serving pipeline relies on — the ReasonEngine routes
symbolic traffic through whichever path ``vsa.ops`` dispatches to, so the
kernel and the fallback must agree everywhere the dispatcher can land.
Property tests run through ``_hypothesis_compat`` (real hypothesis when
installed, fixed deterministic samples otherwise).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.backend import registry
from repro.kernels.circ_conv import kernel as ck, ops as cops, ref as cref
from repro.kernels.qmatmul import kernel as qk, ops as qops, ref as qref
from repro.kernels.simd_fused import kernel as sk, ref as sref
from repro.vsa import ops as vsa


# -- circ_conv: kernel == gather ref == FFT oracle ---------------------------


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), d=st.sampled_from([8, 16, 32, 64]),
       blocks=st.integers(1, 3), conv=st.booleans(), bf16=st.booleans())
def test_circ_elem_conformance(seed, d, blocks, conv, bf16):
    mode = "conv" if conv else "corr"
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (3, blocks, d)).astype(dtype)
    y = jax.random.normal(jax.random.fold_in(key, 1), (3, blocks, d)).astype(dtype)
    out = ck.circ_elem(x, y, mode=mode, interpret=True)
    ref = cref.circ_elem_ref(x, y, mode)
    tol = 0.25 if bf16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)
    if not bf16:  # cross-validate the gather ref itself against the FFT oracle
        fft = vsa.circ_conv_fft(x, y) if conv else vsa.circ_corr_fft(x, y)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(fft),
                                   atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("d", [12, 20, 33])
def test_nonpow2_d_routes_to_gather_fallback(d):
    """Below the dispatch floor vsa.bind prefers the exact gather ref
    under any plan (the kernel wins nothing at small d); the FFT oracle
    cross-checks the fallback numerics here."""
    assert vsa.dispatch_path(d) == "gather"
    key = jax.random.PRNGKey(d)
    a = jax.random.normal(key, (2, 2, d))
    b = jax.random.normal(jax.random.fold_in(key, 1), (2, 2, d))
    np.testing.assert_allclose(np.asarray(vsa.bind(a, b)),
                               np.asarray(vsa.circ_conv_fft(a, b)),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(vsa.unbind(a, b)),
                               np.asarray(vsa.circ_corr_fft(a, b)),
                               atol=1e-4, rtol=1e-4)
    # the kernel-ops layer falls back too (circ_bind forced on)
    np.testing.assert_allclose(np.asarray(cops.circ_bind(a, b, "conv")),
                               np.asarray(cref.circ_elem_ref(a, b, "conv")),
                               atol=1e-5, rtol=1e-5)


def test_pow2_d_above_threshold_routes_to_kernel():
    # pin the negotiated plan: routing assertions must hold regardless of
    # any REPRO_BACKEND override in the environment (the forced-fallback
    # CI leg runs this suite under REPRO_BACKEND=xla)
    with registry.use_plan(registry.negotiate(override="")):
        assert vsa.dispatch_path(128) == "kernel"
        assert vsa.dispatch_path(256) == "kernel"
        assert vsa.dispatch_path(64) == "gather"   # below size threshold


def test_nonpow2_d_at_dispatch_floor_routes_to_interpret():
    """Pinned by the registry-vs-kernel consistency check (NSF006): the
    interpreter lowering carries no pow2/min-size predicate, so on CPU a
    non-pow2 d at the dispatch floor serves the kernel path — and its
    output matches the FFT oracle.  Only the compiled Pallas lowering
    (TPU/GPU) keeps the conservative pow2 constraint."""
    with registry.use_plan(registry.negotiate(platform="cpu", override="")):
        assert vsa.dispatch_path(130) == "kernel"
        assert vsa.dispatch_path(192) == "kernel"
        d = 130
        key = jax.random.PRNGKey(d)
        a = jax.random.normal(key, (2, 2, d))
        b = jax.random.normal(jax.random.fold_in(key, 1), (2, 2, d))
        np.testing.assert_allclose(np.asarray(vsa.bind(a, b)),
                                   np.asarray(vsa.circ_conv_fft(a, b)),
                                   atol=1e-4, rtol=1e-4)
    with registry.use_plan(registry.negotiate(platform="tpu", override="")):
        assert vsa.dispatch_path(130) == "gather"  # compiled path: pow2 only
        assert vsa.dispatch_path(128) == "kernel"


# -- registry sweep: every registered lowering of every kernel ---------------
#
# The cases parametrize straight from the lowering registry, so a kernel or
# lowering added there is conformance-tested here automatically.  Each case
# drives the *public ops wrapper* under a plan forcing one lowering and
# compares against the same wrapper under the kernel's exact ``xla``
# reference lowering, with the tolerance the registry declares for its
# equivalence class (0.0 = bit-exact).

_LOWERING_CASES = [(name, low.name)
                   for name, spec in registry.KERNELS.items()
                   for low in spec.lowerings]


def _run_kernel_under(kernel, plan):
    key = jax.random.PRNGKey(42)
    if kernel == "circ_conv":
        a = jax.random.normal(key, (3, 2, 32))
        b = jax.random.normal(jax.random.fold_in(key, 1), (3, 2, 32))
        with registry.use_plan(plan):
            return np.asarray(cops.circ_bind(a, b, "conv"))
    if kernel == "qmatmul":
        x = jax.random.normal(key, (5, 24))
        w = jax.random.normal(jax.random.fold_in(key, 1), (24, 9))
        with registry.use_plan(plan):
            return np.asarray(qops.qdense(x, w, out_dtype=jnp.float32))
    if kernel == "simd_fused":
        from repro.kernels.simd_fused import ops as sops
        q = vsa.random_codebook(key, 6, 2, 32)
        dic = vsa.random_codebook(jax.random.fold_in(key, 1), 4, 2, 32)
        with registry.use_plan(plan):
            return np.asarray(sops.fused_match_prob(q, dic, 0.7))
    if kernel == "unbind_classify":
        from repro.kernels.unbind_classify import ops as uops
        keys = vsa.random_codebook(key, 5, 2, 32)
        x = vsa.random_codebook(jax.random.fold_in(key, 1), 3, 2, 32)
        head = {"w": jax.random.normal(jax.random.fold_in(key, 2), (64, 7)),
                "b": jax.random.normal(jax.random.fold_in(key, 3), (7,))}
        with registry.use_plan(plan):
            return np.asarray(uops.unbind_classify(head, keys, x))
    assert kernel == "flash_attn"
    from repro.kernels.flash_attn import ops as fops
    q = jax.random.normal(key, (2, 12, 2, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 12, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 12, 2, 16))
    with registry.use_plan(plan):
        return np.asarray(fops.flash_mha(q, k, v, scale=0.25))


@pytest.mark.parametrize("kernel,lowering", _LOWERING_CASES)
def test_registry_lowering_conformance(kernel, lowering):
    low = registry.KERNELS[kernel].by_name(lowering)
    out = _run_kernel_under(
        kernel, registry.negotiate(override=f"{kernel}={lowering}"))
    ref = _run_kernel_under(
        kernel, registry.negotiate(override=f"{kernel}=xla"))
    if low.equivalence == "epsilon":
        np.testing.assert_allclose(out, ref, atol=low.epsilon,
                                   rtol=low.epsilon)
    else:
        np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("mode", ["conv", "corr"])
def test_circ_elem_degenerate_single_row_block(mode):
    """1 pair, 1 block — the tile is all padding beyond row 0."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1, 1, 16))
    y = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, 16))
    out = ck.circ_elem(x, y, mode=mode, interpret=True, tile_n=8)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(cref.circ_elem_ref(x, y, mode)),
                               atol=1e-5, rtol=1e-5)


def test_circ_dict_degenerate_single_entry():
    """1 query x 1 dictionary entry (grid collapses to one program)."""
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (1, 1, 16))
    dic = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, 16))
    out = ck.circ_dict(x, dic, mode="corr", interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(cref.circ_dict_ref(x, dic, "corr")),
                               atol=1e-5, rtol=1e-5)


# -- qmatmul: int8 / packed-int4 against the integer-exact ref ---------------


def test_qmatmul_int4_edge_nibbles_exact():
    """Every nibble value incl. the extremes (-8, +7) packed/unpacked and
    accumulated exactly: with unit scales the kernel must equal pure int32
    math (the sign bit of the low nibble is where packing goes wrong)."""
    vals = np.arange(-8, 8, dtype=np.int8)          # all 16 nibbles
    w = np.tile(vals, (8, 1))                       # (8, 16)
    x = np.array([[-128, 127, -8, 7, 1, -1, 0, 64]], dtype=np.int8)  # (1, 8)
    exact = x.astype(np.int32) @ w.astype(np.int32)
    packed = qops.pack_int4(jnp.asarray(w))
    ones_m, ones_n = jnp.ones((1,), jnp.float32), jnp.ones((16,), jnp.float32)
    out_k = qk.qmatmul(jnp.asarray(x), packed, ones_m, ones_n, int4=True,
                       interpret=True, bm=8, bn=8, bk=8)
    out_r = qref.qmatmul_ref(jnp.asarray(x), packed, ones_m, ones_n, int4=True)
    np.testing.assert_array_equal(np.asarray(out_k), exact.astype(np.float32))
    np.testing.assert_array_equal(np.asarray(out_r), exact.astype(np.float32))


def test_qmatmul_int4_one_packed_column_over_k_steps():
    """n <= 2 packs to a 1-column block; with k > bk the accumulation runs
    over several K steps (the shape XLA:CPU once mis-compiled)."""
    rng = np.random.default_rng(1)
    x = rng.integers(-128, 128, (4, 9)).astype(np.int8)
    w = rng.integers(-8, 8, (9, 2)).astype(np.int8)
    exact = x.astype(np.int32) @ w.astype(np.int32)
    out = qk.qmatmul(jnp.asarray(x), qops.pack_int4(jnp.asarray(w)),
                     jnp.ones((4,)), jnp.ones((2,)), int4=True,
                     interpret=True, bm=8, bn=8, bk=8)
    np.testing.assert_array_equal(np.asarray(out), exact.astype(np.float32))


def test_qmatmul_int8_full_range_exact():
    """int8 extremes (incl. -128) accumulate exactly in int32."""
    rng = np.random.default_rng(0)
    x = rng.integers(-128, 128, (5, 9)).astype(np.int8)
    w = rng.integers(-128, 128, (9, 7)).astype(np.int8)
    x[0, 0], w[0, 0] = -128, -128  # force the extreme product
    exact = x.astype(np.int32) @ w.astype(np.int32)
    sm, sn = jnp.ones((5,), jnp.float32), jnp.ones((7,), jnp.float32)
    out = qk.qmatmul(jnp.asarray(x), jnp.asarray(w), sm, sn, int4=False,
                     interpret=True, bm=4, bn=4, bk=4)
    np.testing.assert_array_equal(np.asarray(out), exact.astype(np.float32))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), m=st.integers(1, 9),
       k=st.integers(1, 17), n=st.integers(1, 9), int4=st.booleans())
def test_qmatmul_property_matches_ref(seed, m, k, n, int4):
    """Random small shapes (incl. 1-row/1-col/1-k degenerates) through the
    quantize helpers: kernel == ref within fp tolerance."""
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (m, k))
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n))
    xq, xs = qops.quantize_rows(x)
    wq, ws = qops.quantize_cols(w, 4 if int4 else 8)
    if int4:
        wq = qops.pack_int4(wq)
        if n % 2:
            ws = jnp.pad(ws, (0, 1))
    out_k = qops.qmatmul(xq, wq, xs, ws, int4=int4, bm=8, bn=8, bk=8)
    out_r = qref.qmatmul_ref(xq, wq, xs, ws, int4=int4)
    np.testing.assert_allclose(np.asarray(out_k)[:, :n],
                               np.asarray(out_r)[:, :n], atol=1e-4, rtol=1e-4)


def test_pack_int4_odd_n_pads_with_zero():
    q = jnp.asarray(np.array([[7, -8, 3]], np.int8).repeat(4, 0))  # n=3 odd
    packed = qops.pack_int4(q)
    unpacked = qref.unpack_int4_ref(packed)
    np.testing.assert_array_equal(np.asarray(unpacked[:, :3]), np.asarray(q))
    assert (np.asarray(unpacked[:, 3]) == 0).all()


# -- simd_fused: fused normalize/dot/softmax chain ---------------------------


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 20),
       m=st.integers(1, 6), bf16=st.booleans(),
       temp=st.sampled_from([0.1, 1.0]))
def test_fused_match_prob_conformance(seed, n, m, bf16, temp):
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    key = jax.random.PRNGKey(seed)
    q = vsa.random_codebook(key, n, 2, 32, dtype=dtype)
    dic = vsa.random_codebook(jax.random.fold_in(key, 1), m, 2, 32,
                              dtype=dtype)
    out = sk.fused_match_prob(q, dic, temp, interpret=True, tile_n=8)
    ref = sref.fused_match_prob_ref(q, dic, temp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2 if bf16 else 1e-5)
    np.testing.assert_allclose(np.asarray(out).sum(-1), np.ones(n), atol=1e-4)


def test_fused_match_prob_single_query_single_entry():
    """n=1, m=1: softmax over one entry must be exactly 1, padded rows cut."""
    q = vsa.random_codebook(jax.random.PRNGKey(0), 1, 1, 16)
    dic = vsa.random_codebook(jax.random.PRNGKey(1), 1, 1, 16)
    out = np.asarray(sk.fused_match_prob(q, dic, 0.5, interpret=True,
                                         tile_n=8))
    assert out.shape == (1, 1)
    np.testing.assert_allclose(out, np.ones((1, 1)), atol=1e-6)


# -- flash attention: degenerate tiles, padding, bf16 ------------------------


@pytest.mark.parametrize("sq,skv,bq,bk,causal", [
    (1, 1, 16, 16, True),      # single position, blocks clamp to 1
    (10, 6, 4, 4, True),       # non-multiple of block in both axes
    (5, 12, 8, 8, False),      # kv longer than q, non-causal
])
def test_flash_attention_degenerate_shapes(sq, skv, bq, bk, causal):
    from repro.kernels.flash_attn import kernel as fk, ref as fr
    key = jax.random.PRNGKey(sq * 31 + skv)
    q = jax.random.normal(key, (2, sq, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, skv, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, skv, 16))
    o_k = fk.flash_attention(q, k, v, scale=0.3, causal=causal, bq=bq, bk=bk,
                             interpret=True)
    o_r = fr.flash_attention_ref(q, k, v, scale=0.3, causal=causal)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-4)


# -- unbind_classify: fused symbolic-tail kernel -----------------------------


@pytest.mark.parametrize("n,tile_n", [(1, 8), (5, 8), (13, 8)])
def test_unbind_classify_padded_tiles(n, tile_n):
    """Query counts that leave the last tile mostly padding must still match
    the gather ref exactly after the pad rows are cut."""
    from repro.kernels.unbind_classify import kernel as uk, ref as uref
    key = jax.random.PRNGKey(n)
    keys = vsa.random_codebook(key, 3, 2, 16)
    x = vsa.random_codebook(jax.random.fold_in(key, 1), n, 2, 16)
    w = jax.random.normal(jax.random.fold_in(key, 2), (2, 16, 5))
    b = jax.random.normal(jax.random.fold_in(key, 3), (1, 5))
    out = uk.fused_unbind_classify(keys, x, w, b, interpret=True,
                                   tile_n=tile_n)
    head = {"w": w.reshape(32, 5), "b": b.reshape(5)}
    ref = uref.unbind_classify_ref(head, keys, x)
    assert out.shape == (n, 3, 5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_unbind_classify_custom_vjp_matches_ref_grad():
    """Fused forward, reference backward: head gradients must agree with
    differentiating the pure ref chain."""
    from repro.kernels.unbind_classify import ops as uops, ref as uref
    key = jax.random.PRNGKey(7)
    keys = vsa.random_codebook(key, 2, 2, 16)
    x = vsa.random_codebook(jax.random.fold_in(key, 1), 3, 2, 16)
    head = {"w": jax.random.normal(jax.random.fold_in(key, 2), (32, 4)),
            "b": jax.random.normal(jax.random.fold_in(key, 3), (4,))}
    g_k = jax.grad(
        lambda h: uops.unbind_classify(h, keys, x, use_kernel=True).sum()
    )(head)
    g_r = jax.grad(
        lambda h: uref.unbind_classify_ref(h, keys, x).sum())(head)
    for name in g_r:
        np.testing.assert_allclose(np.asarray(g_k[name]),
                                   np.asarray(g_r[name]),
                                   atol=1e-4, rtol=1e-4)


def test_flash_attention_bf16_io():
    from repro.kernels.flash_attn import kernel as fk, ref as fr
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 24, 16)).astype(jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 24, 16)).astype(jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 24, 16)).astype(jnp.bfloat16)
    o_k = fk.flash_attention(q, k, v, scale=0.25, causal=True, bq=8, bk=8,
                             interpret=True)
    o_r = fr.flash_attention_ref(q, k, v, scale=0.25, causal=True)
    assert o_k.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32), atol=3e-2)
