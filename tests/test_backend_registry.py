"""Backend negotiation regression suite.

The load-bearing case is the platform predicate: the pre-registry
dispatchers tested ``jax.default_backend() != "tpu"`` and so forced GPUs
into Pallas *interpret* mode (a silent orders-of-magnitude slowdown).
Negotiation is pure given a platform string, so every platform's plan is
asserted here without needing the hardware.
"""

import numpy as np
import pytest

from repro.backend import registry


# -- platform predicates (the GPU mis-dispatch regression) -------------------


@pytest.mark.parametrize("platform,head,interpret", [
    ("tpu", "pallas", False),
    ("gpu", "pallas", False),   # regression: used to get interpret mode
    ("cpu", "interpret", True),
])
def test_negotiate_per_platform(platform, head, interpret):
    plan = registry.negotiate(platform=platform, override="")
    assert plan.platform == platform
    for kernel in registry.KERNELS:
        low = plan.lowering(kernel)
        assert low.name == head, (kernel, low)
        assert low.interpret is interpret
        # every chain ends in the universally-feasible exact reference
        assert plan.chains[kernel][-1].is_ref


def test_negotiate_unknown_platform_falls_back_to_xla():
    """An unknown platform gets no plan at all: an all-``xla`` fallback
    would serve the reference and hide the device."""
    with pytest.raises(ValueError, match="metal"):
        registry.negotiate(platform="metal", override="")


def test_gpu_plan_never_interprets():
    """No lowering a GPU plan can select runs in interpret mode."""
    plan = registry.negotiate(platform="gpu", override="")
    for kernel in registry.KERNELS:
        for low in plan.chains[kernel]:
            assert not low.interpret
            assert not plan.run_interpret(low)


def test_cpu_run_interpret_degrades_forced_pallas():
    """Forcing the compiled-pallas lowering on CPU must not hand Mosaic a
    CPU compile: run_interpret() degrades it to interpret mode."""
    plan = registry.negotiate(platform="cpu", override="pallas")
    low = plan.lowering("circ_conv")
    assert low.name == "pallas" and not low.interpret
    assert plan.run_interpret(low)


# -- capability predicates within a chain ------------------------------------


def test_select_nonpow2_falls_through_to_ref():
    plan = registry.negotiate(platform="tpu", override="")
    assert plan.select("circ_conv", size=33).is_ref
    assert plan.select("circ_conv", size=4).is_ref      # below min_size
    assert not plan.select("circ_conv", size=32).is_ref


def test_select_unknown_size_is_conservative():
    """A shape-constrained lowering is infeasible when the call site
    cannot state its size."""
    plan = registry.negotiate(platform="tpu", override="")
    assert plan.select("circ_conv").is_ref
    assert not plan.select("qmatmul").is_ref  # unconstrained kernel: fine


def test_dispatch_threshold_only_applies_with_dispatch_flag():
    plan = registry.negotiate(platform="cpu", override="")
    # vsa-level dispatch: small-but-feasible d routes to the exact ref
    assert plan.select("circ_conv", size=64, dispatch=True).is_ref
    assert not plan.select("circ_conv", size=128, dispatch=True).is_ref
    # kernel-wrapper level: an explicit kernel call at d=64 stays a kernel
    assert not plan.select("circ_conv", size=64).is_ref


# -- overrides ---------------------------------------------------------------


def test_override_global_and_per_kernel():
    plan = registry.negotiate(platform="tpu", override="xla")
    assert all(plan.lowering(k).is_ref for k in registry.KERNELS)
    plan = registry.negotiate(platform="tpu",
                              override="circ_conv=xla,qmatmul=interpret")
    assert plan.lowering("circ_conv").is_ref
    assert plan.lowering("qmatmul").name == "interpret"
    assert plan.lowering("simd_fused").name == "pallas"  # untouched
    assert plan.source == "override:circ_conv=xla,qmatmul=interpret"


def test_override_env(monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "xla")
    plan = registry.negotiate(platform="cpu")
    assert plan.source == "env:xla"
    assert all(plan.lowering(k).is_ref for k in registry.KERNELS)
    # the lazily-negotiated default plan re-negotiates on env change
    assert registry.get_plan().lowering("circ_conv").is_ref
    monkeypatch.delenv("REPRO_BACKEND")
    assert not registry.get_plan().lowering("circ_conv").is_ref \
        or registry.get_plan().platform not in ("cpu", "gpu", "tpu")


@pytest.mark.parametrize("bad", ["nope", "circ_conv=nope", "bogus=xla"])
def test_override_rejects_unknown_names(bad):
    with pytest.raises((KeyError, ValueError)):
        registry.negotiate(platform="cpu", override=bad)


def test_forced_nonref_keeps_ref_fallback():
    """A forced compiled-Pallas lowering still degrades to the exact
    reference when the call-site shape is infeasible (non-pow2 d must
    never crash the Mosaic build); the forced *interpreter* carries no
    shape predicate and serves the call itself."""
    plan = registry.negotiate(platform="cpu", override="pallas")
    assert plan.select("circ_conv", size=33).is_ref
    plan = registry.negotiate(platform="cpu", override="interpret")
    assert plan.select("circ_conv", size=33).name == "interpret"


# -- active-plan scoping -----------------------------------------------------


def test_use_plan_stacks_and_restores():
    base = registry.get_plan()
    forced = registry.negotiate(platform="cpu", override="xla")
    with registry.use_plan(forced):
        assert registry.get_plan() is forced
        assert registry.active("circ_conv", size=128).is_ref
        inner = registry.negotiate(platform="tpu", override="")
        with registry.use_plan(inner):
            assert registry.get_plan() is inner
        assert registry.get_plan() is forced
    assert registry.get_plan() is base


# -- replay tolerance (what serve.trace diffs against) -----------------------


def test_replay_tolerance_identical_tags_is_bit_exact():
    tags = registry.negotiate(platform="cpu", override="").tags()
    assert registry.replay_tolerance(tags, dict(tags)) == 0.0


def test_replay_tolerance_changed_kernels_take_max_epsilon():
    a = registry.negotiate(platform="cpu", override="").tags()
    b = dict(a, circ_conv="xla")
    tol = registry.replay_tolerance(a, b)
    eps = registry.KERNELS["circ_conv"].by_name("interpret").epsilon
    assert tol == pytest.approx(eps)
    assert registry.replay_tolerance(b, a) == pytest.approx(eps)


# -- registry invariants -----------------------------------------------------


def test_every_kernel_has_exact_ref_lowering():
    for spec in registry.KERNELS.values():
        refs = [low for low in spec.lowerings if low.is_ref]
        assert len(refs) == 1
        assert refs[0].equivalence == "exact"
        assert refs[0].platforms == registry.PLATFORMS


def test_plan_tags_and_tag_rendering():
    plan = registry.negotiate(platform="cpu", override="")
    assert set(plan.tags()) == set(registry.KERNELS)
    assert plan.tag() == "cpu/interpret"   # uniform plans render compactly
    mixed = registry.negotiate(platform="cpu", override="circ_conv=xla")
    assert "circ_conv:xla" in mixed.tag()


# -- deploy() integration (cheap: report shape only) -------------------------


def test_deployment_report_records_backend(tmp_path):
    from repro.serve import Budget, Traffic, deploy

    dep = deploy(["nvsa"], Traffic(), Budget(max_batch=2), seed=0,
                 options={"nvsa": {"d": 16}})
    rec = dep.report()["nvsa"]["backend"]
    assert rec is not None
    assert set(rec["lowerings"]) == set(registry.KERNELS)
    assert rec["platform"] == dep.backend.platform
    assert "backend=" in dep.summary()
    # explicit override is honored and recorded
    dep2 = deploy(["nvsa"], Traffic(), Budget(max_batch=2), seed=0,
                  options={"nvsa": {"d": 16}}, backend="xla")
    rec2 = dep2.report()["nvsa"]["backend"]
    assert all(v == "xla" for v in rec2["lowerings"].values())
    assert rec2["source"] == "override:xla"


def test_nested_recorders_survive_each_other():
    """An inner scope whose log equals the outer's (both empty here) must
    remove itself, not the enclosing recorder."""
    plan = registry.negotiate(platform="cpu", override="")
    with registry.record_selections() as outer:
        with registry.record_selections() as inner:
            pass
        plan.select("qmatmul")
    assert inner == [] and outer == [("qmatmul", "interpret")]
