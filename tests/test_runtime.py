"""Unified serving-runtime tests: the traffic-class registry, the
request/result work-unit envelope, DSE-driven ``deploy()`` (serving knobs
selected from ``core.dse.explore`` output, not hand-set fields), and the
acceptance regression: one FrontDoor serving interleaved LM + NSAI
arrivals with answers bit-identical to the per-stack offline paths."""

import numpy as np
import pytest
from _numerics import assert_logprobs_close

from repro.serve import runtime as rt


class VirtualClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        assert dt >= 0
        self.t += dt


# -- registry + envelope -----------------------------------------------------


def test_traffic_class_registry_and_resolve():
    assert set(rt.TRAFFIC_CLASSES) == {"lm", "reason", "frontdoor"}
    lm = rt.TRAFFIC_CLASSES["lm"].models()
    reason = rt.TRAFFIC_CLASSES["reason"].models()
    assert "llama3.2-3b" in lm and "stablelm-3b" in lm
    assert "internvl2-26b" not in lm          # vlm kinds are not servable
    assert set(reason) == {"nvsa", "prae", "mimonet", "lvrf"}
    # the mixed class serves the union
    both = rt.TRAFFIC_CLASSES["frontdoor"].models()
    assert set(both) == set(lm) | set(reason)
    assert rt.resolve_models("frontdoor", ["stablelm-3b", "nvsa"]) == \
        ("stablelm-3b", "nvsa")
    with pytest.raises(KeyError, match="unknown workload"):
        rt.resolve_models("warp", ["nvsa"])
    with pytest.raises(ValueError, match="unknown models"):
        rt.resolve_models("reason", ["stablelm-3b"])   # LM id, NSAI class
    with pytest.raises(ValueError, match="unknown models"):
        rt.resolve_models("frontdoor", ["mystery"])


def test_work_units_envelope():
    from repro.serve.engine import Result
    from repro.serve.reason import ReasonResult

    lm = Result(uid=0, tokens=np.arange(5, dtype=np.int32), prompt_len=3,
                finished_by_eos=False, slot=0)
    ns = ReasonResult(uid=1, answer=2, answer_logprobs=np.zeros(8), batch=0)
    assert rt.work_units(lm) == 5          # generated tokens
    assert rt.work_units(ns) == 1          # one problem
    assert rt.work_unit_name([lm]) == "tok"
    assert rt.work_unit_name([ns]) == "prob"
    assert rt.work_unit_name([]) == "prob"


def test_measured_rate_fallback():
    stats = rt.fresh_split_stats()
    assert rt.measured_rate(stats) == 0.0
    stats["warmup"].update(work=10, wall_time_s=2.0)
    assert rt.measured_rate(stats) == 5.0      # warmup-only fallback
    stats["measured"].update(work=30, wall_time_s=2.0)
    assert rt.measured_rate(stats) == 15.0     # measured wins when present


# -- serving_plan: DSE point -> runtime knobs --------------------------------


def test_serving_plan_maps_design_to_knobs():
    from repro.core.dse import DesignConfig, serving_plan

    para = DesignConfig(H=8, W=8, N=16, mode="parallel", n_l=[8], n_v=[8],
                        nl_bar=8, nv_bar=8, t_para=100, t_seq=250,
                        t_phase1=100)
    plan = serving_plan(para, max_batch=8, inflight_cap=4)
    assert plan.batch_size == 8            # pow2 floor of N=16, capped at 8
    assert plan.buckets == (2, 4, 8)
    assert plan.max_inflight == 2          # round(250/100), capped
    assert plan.design is para
    seq = DesignConfig(H=8, W=8, N=3, mode="sequential", n_l=[3], n_v=[3],
                       nl_bar=3, nv_bar=3, t_para=100, t_seq=90,
                       t_phase1=90)
    plan = serving_plan(seq, max_batch=8)
    # an unfolded (sequential) design serves a one-group window
    assert plan.max_inflight == 1
    assert plan.batch_size == 2 and plan.buckets == (2,)  # pow2 floor of 3
    # the inflight cap binds
    deep = serving_plan(para, max_batch=4, inflight_cap=1)
    assert deep.max_inflight == 1 and deep.batch_size == 4


def test_deploy_selects_serving_config_from_dse(monkeypatch):
    """deploy() must configure the NSAI engine from core.dse.explore
    output — not hand-set ReasonConfig fields.  Asserted two ways: the
    engine's compiled knobs equal serving_plan(explored design), and a
    monkeypatched explore() visibly steers the engine's buckets."""
    from repro.core import dse
    from repro.serve import Budget, deploy

    d = deploy(["nvsa"], budget=Budget(max_pes=1024, max_batch=4),
               options={"nvsa": {"variant": "oracle", "d": 64}})
    design, plan = d.designs["nvsa"], d.plans["nvsa"]
    assert design.searched_points > 0          # explore actually ran
    expect = dse.serving_plan(design, max_batch=4, inflight_cap=4)
    assert (plan.batch_size, plan.buckets, plan.max_inflight) == \
        (expect.batch_size, expect.buckets, expect.max_inflight)
    eng = d.engines["nvsa"]
    assert eng.cfg.batch_size == plan.batch_size
    assert eng.cfg.buckets == plan.buckets
    assert eng.cfg.max_inflight == plan.max_inflight
    # deploy() serves the one-dispatch fused schedule when the fused
    # negotiation came out exact, the staged overlap one otherwise
    assert eng.cfg.schedule == ("fused" if eng.schedules["oracle"].fused_ok
                                else "overlap")
    assert eng.schedules["oracle"].batch_buckets == plan.buckets
    # the report records which DSE point serves (bench provenance)
    rec = d.report()["nvsa"]
    assert rec["design"] == design.summary()
    assert rec["serving"]["buckets"] == plan.buckets

    forced = dse.DesignConfig(H=4, W=4, N=2, mode="parallel", n_l=[1],
                              n_v=[1], nl_bar=1, nv_bar=1, t_para=50,
                              t_seq=100, t_phase1=50, searched_points=7)
    monkeypatch.setattr(dse, "explore", lambda *a, **k: forced)
    d2 = deploy(["nvsa"], budget=Budget(max_pes=1024, max_batch=4),
                options={"nvsa": {"variant": "oracle", "d": 64}})
    assert d2.engines["nvsa"].cfg.buckets == (2,)      # pow2 floor of N=2
    # nvsa's fused trace negotiates exact, so the deployment serves the
    # one-dispatch fused schedule
    assert d2.engines["nvsa"].cfg.schedule == "fused"
    assert d2.engines["nvsa"].cfg.max_inflight == 2    # t_seq/t_para


# -- the acceptance regression: mixed LM + NSAI through one front-door -------


def test_mixed_lm_nsai_frontdoor_bit_identical():
    """One FrontDoor instance serves interleaved LM + NSAI arrivals in a
    single run; the served LM tokens and NSAI answers are bit-identical
    to the respective pre-redesign single-stack offline paths."""
    from repro.serve import Budget, Traffic, deploy
    from repro.serve import frontdoor as fd

    clock = VirtualClock()
    d = deploy(["stablelm-3b", "nvsa"],
               traffic=Traffic(rate_rps=50.0, deadline_s=0.01),
               budget=Budget(max_pes=1024, max_batch=4, max_slots=2,
                             max_len=64, max_new_tokens=6),
               options={"nvsa": {"variant": "oracle", "d": 64}},
               clock=clock, sleep=clock.sleep)
    n = 5
    streams, truths = d._streams(n, seed=42)
    lm_reqs = list(streams["stablelm-3b"])
    ns_reqs = list(streams["nvsa"])
    arrivals = fd.merge_arrivals(
        fd.poisson_arrivals("stablelm-3b", lm_reqs, 50.0, seed=1),
        fd.poisson_arrivals("nvsa", ns_reqs, 50.0, seed=2))
    rep = d.serve(arrivals)
    # interleaved service through ONE front-door, both classes in ONE report
    assert sorted(rep.results) == ["nvsa", "stablelm-3b"]
    assert len(rep.results["stablelm-3b"]) == n
    assert len(rep.results["nvsa"]) == n
    assert {g.model for g in rep.groups} == {"nvsa", "stablelm-3b"}
    assert rep.work_unit("stablelm-3b") == "tok"
    assert rep.work_unit("nvsa") == "prob"
    for field in ("queue_s", "service_s"):
        for m in ("stablelm-3b", "nvsa"):
            p = rep.percentiles(field, m)
            assert np.isfinite(p["p50"]) and np.isfinite(p["p95"])
    # single-stack offline regressions (sampling is (seed, uid, token)-
    # keyed and NSAI answers admission-group independent, so the same
    # engines replay the same uids bit-identically)
    lm_offline = d.engines["stablelm-3b"].run(lm_reqs)
    for uid, res in rep.results["stablelm-3b"].items():
        np.testing.assert_array_equal(res.tokens, lm_offline[uid].tokens)
    ns_offline = d.engines["nvsa"].run(ns_reqs)
    for uid, res in rep.results["nvsa"].items():
        assert_logprobs_close(res.answer_logprobs,
                              ns_offline[uid].answer_logprobs)
        assert res.answer == ns_offline[uid].answer
    # NSAI accuracy is intact through the mixed path
    from repro.configs import base as cbase

    acc = cbase.REASON_WORKLOADS["nvsa"].score(rep.results["nvsa"],
                                               truths["nvsa"]())
    assert acc == 1.0              # oracle variant is exact


def test_deployment_warmup_and_synthetic_traffic():
    from repro.serve import Budget, deploy

    d = deploy(["nvsa"], budget=Budget(max_pes=256, max_batch=2),
               options={"nvsa": {"variant": "oracle", "d": 64}})
    d.warmup()
    # warmup compiled every bucket: serving now is measured, not warmup
    eng = d.engines["nvsa"]
    assert eng.stats["warmup"]["requests"] > 0
    arrivals, truths = d.synthetic_traffic(4)
    rep = d.serve(arrivals)
    assert len(rep.results["nvsa"]) == 4
    assert set(truths) == {"nvsa"}
