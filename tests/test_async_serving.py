"""``deploy()`` serves every NSAI workload asynchronously: the DSE-derived
plan is the pipelined ``overlap`` schedule, upgraded to one ``fused``
dispatch per group where the fused negotiation is exact, and the answers
are bit-identical to the host-synchronous ``sequential`` schedule's on
the same admission groups."""

import numpy as np
import pytest


@pytest.mark.parametrize("model, opts", [
    ("nvsa", {"variant": "oracle", "d": 64}),   # fused negotiates exact
    ("mimonet", {}),                            # epsilon: staged overlap
])
def test_deploy_serves_groups_asynchronously(model, opts):
    from repro.serve import deploy

    dep = deploy([model], options={model: dict(opts)})
    eng = dep.engines[model]
    sched = eng.schedules[dep.variants[model]]
    assert dep.plans[model].schedule == "overlap"
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
    # only the sequential schedule blocks on (and times) single stages
    assert not any(eng.stats["stage_time_s"].values())

    # the same admission groups, stage by stage with a block after each
    requests = {a.request.uid: a.request for a in arrivals}
    for g in rep.groups:
        eng.submit([requests[u] for u in g.uids], schedule="sequential")
    ref = eng.drain_all()
    assert any(eng.stats["stage_time_s"].values())
    for uid, res in served.items():
        np.testing.assert_array_equal(res.answer_logprobs,
                                      ref[uid].answer_logprobs)
        np.testing.assert_array_equal(res.answer, ref[uid].answer)


def test_explicit_schedule_option_is_honoured():
    """``options={model: {"schedule": ...}}`` replaces the derived
    schedule and is not upgraded to ``fused``."""
    from repro.serve import Budget, deploy

    dep = deploy(["nvsa"], budget=Budget(max_batch=2),
                 options={"nvsa": {"variant": "oracle", "d": 64,
                                   "schedule": "sequential"}})
    assert dep.plans["nvsa"].schedule == "overlap"
    assert dep.engines["nvsa"].cfg.schedule == "sequential"
    assert dep.report()["nvsa"]["serving"]["schedule"] == "sequential"
