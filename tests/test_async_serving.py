"""``deploy()`` serves every NSAI workload asynchronously: one ``fused``
dispatch per group where the fused negotiation is exact, the staged
``overlap`` pipeline otherwise, and the answers are bit-identical to the
staged pipeline's on the same admission groups."""

import dataclasses

import numpy as np
import pytest


@pytest.mark.parametrize("model, opts", [
    ("nvsa", {"variant": "oracle", "d": 64}),   # fused negotiates exact
    ("mimonet", {}),                            # epsilon: staged overlap
    ("lvrf", {"variant": "oracle", "d": 64}),
    ("prae", {"variant": "oracle", "d": 64}),
])
def test_deploy_serves_groups_asynchronously(model, opts):
    from repro.serve import deploy
    from repro.serve.reason import ReasonEngine

    dep = deploy([model], options={model: dict(opts)})
    eng = dep.engines[model]
    sched = eng.schedules[dep.variants[model]]
    assert eng.cfg.schedule == ("fused" if sched.fused_ok else "overlap")
    dep.warmup()
    arrivals, _ = dep.synthetic_traffic(40)
    arrivals = list(arrivals)
    before = dict(eng.stats)
    rep = dep.serve(arrivals)
    served = rep.results[model]
    assert sorted(served) == sorted(a.request.uid for a in arrivals)
    assert all(g.dispatch_s <= g.done_s for g in rep.groups)
    groups = eng.stats["batches"] - before["batches"]
    assert groups == len(rep.groups)
    per_group = 1 if sched.fused_ok else len(sched.stages)
    assert eng.stats["dispatches"] - before["dispatches"] == \
        per_group * groups

    # the same admission groups through the staged pipeline, on a second
    # engine over the same compiled schedules
    staged = ReasonEngine(eng.schedules,
                          dataclasses.replace(eng.cfg, schedule="overlap"),
                          consts=eng.consts, device=eng.device)
    requests = {a.request.uid: a.request for a in arrivals}
    for g in rep.groups:
        staged.submit([requests[u] for u in g.uids])
    ref = staged.drain_all()
    assert staged.stats["dispatches"] == len(sched.stages) * groups
    for uid, res in served.items():
        np.testing.assert_array_equal(res.answer_logprobs,
                                      ref[uid].answer_logprobs)
        np.testing.assert_array_equal(res.answer, ref[uid].answer)


def test_explicit_schedule_option_is_honoured():
    """``options={model: {"schedule": ...}}`` replaces the negotiated
    schedule: nvsa's fused pipeline is exact, yet an explicit ``overlap``
    is not upgraded to ``fused``."""
    from repro.serve import Budget, deploy

    dep = deploy(["nvsa"], budget=Budget(max_batch=2),
                 options={"nvsa": {"variant": "oracle", "d": 64,
                                   "schedule": "overlap"}})
    eng = dep.engines["nvsa"]
    assert eng.schedules["oracle"].fused_ok
    assert eng.cfg.schedule == "overlap"
    assert dep.report()["nvsa"]["serving"]["schedule"] == "overlap"
