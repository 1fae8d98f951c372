"""ReplicaPool tests: protocol mechanics (least-inflight routing, merged
stats and drains, admission-cap validation), replica-count invariance of
answers — 4 replicas behind the front-door must serve bit-identical
results to 1 — and work conservation of the merged accounting."""

import jax
import numpy as np
import pytest

from repro.configs import base as cbase
from repro.models import nvsa
from repro.serve import frontdoor as fd
from repro.serve import work_units
from repro.serve.reason import ReasonConfig
from repro.serve.replica import ReplicaPool, _merge_stats
from tests.test_frontdoor import (VirtualClock, _oracle_engine,
                                  _oracle_requests)


def _oracle_pool(replicas, batch_size=4, buckets=(2, 4), max_inflight=2,
                 d=64):
    """An oracle-variant nvsa pool (always a ReplicaPool, even at 1)."""
    cfg = cbase.REASON_WORKLOADS["nvsa"].make_config(d=d)
    consts = {"params": None,
              "books": nvsa.nvsa_codebooks(cfg, jax.random.PRNGKey(1))}
    eng = cbase.reason_engine_pool(
        "nvsa", cfg,
        ReasonConfig(batch_size=batch_size, buckets=buckets,
                     max_inflight=max_inflight, schedule="overlap"),
        consts=consts, variants=("oracle",), replicas=replicas,
        trace_graph=False)
    if not isinstance(eng, ReplicaPool):
        eng = ReplicaPool([eng])
    return cfg, eng


# -- construction + validation ----------------------------------------------


def test_pool_rejects_empty_and_mismatched_caps():
    with pytest.raises(ValueError, match="at least one"):
        ReplicaPool([])
    _, _, e2 = _oracle_engine(batch_size=2, buckets=(2,))
    _, _, e4 = _oracle_engine(batch_size=4, buckets=(2, 4))
    with pytest.raises(ValueError, match="admission_cap"):
        ReplicaPool([e2, e4])


def test_reason_engine_pool_unwraps_single_replica():
    cfg = cbase.REASON_WORKLOADS["nvsa"].make_config(d=64)
    consts = {"params": None,
              "books": nvsa.nvsa_codebooks(cfg, jax.random.PRNGKey(1))}
    rcfg = ReasonConfig(batch_size=4, schedule="overlap")
    one = cbase.reason_engine_pool("nvsa", cfg, rcfg, consts=consts,
                                   variants=("oracle",), replicas=1,
                                   trace_graph=False)
    assert not isinstance(one, ReplicaPool)
    three = cbase.reason_engine_pool("nvsa", cfg, rcfg, consts=consts,
                                     variants=("oracle",), replicas=3,
                                     trace_graph=False)
    assert isinstance(three, ReplicaPool) and len(three) == 3
    # replicas share the compiled StagedSchedules (jit caches are shared)
    assert all(r.schedules["oracle"] is three.replicas[0].schedules["oracle"]
               for r in three.replicas)
    with pytest.raises(ValueError, match="replicas"):
        cbase.reason_engine_pool("nvsa", cfg, rcfg, consts=consts,
                                 replicas=0)


def test_merge_stats_sums_trees():
    a = {"n": 1, "nested": {"x": 2.0}, "lst": [1, 2], "flag": True,
         "name": "a"}
    b = {"n": 3, "nested": {"x": 0.5, "y": 7}, "lst": [10, 20],
         "flag": True, "name": "b"}
    m = _merge_stats([a, b])
    assert m["n"] == 4 and m["nested"]["x"] == 2.5 and m["nested"]["y"] == 7
    assert m["lst"] == [11, 22]
    assert m["flag"] is True and m["name"] == "a"


# -- routing + protocol surface ---------------------------------------------


def test_least_inflight_routing_spreads_groups():
    cfg, pool = _oracle_pool(replicas=3, max_inflight=2)
    reqs = _oracle_requests(cfg, 12)
    recs = [pool.submit(reqs[i:i + 4]) for i in (0, 4, 8)]
    # back-to-back submits with nothing drained round-robin across idle
    # replicas (ties break round-robin)
    assert [r.replica for r in recs] == [0, 1, 2]
    assert pool.inflight == 3
    results = pool.drain_all()
    assert pool.inflight == 0 and len(results) == 12
    assert pool.dispatched_groups == [1, 1, 1]
    assert pool.dispatched_requests == [4, 4, 4]
    split = pool.per_replica()
    assert [r["groups"] for r in split] == [1, 1, 1]
    assert sum(r["work"] for r in split) == 12


def test_pool_run_merges_results_and_conserves_work():
    cfg, p1 = _oracle_pool(replicas=1)
    cfg4, p4 = _oracle_pool(replicas=4)
    reqs = _oracle_requests(cfg, 12)
    r1 = p1.run(list(reqs))
    r4 = p4.run(list(reqs))
    assert set(r1) == set(r4) == {r.uid for r in reqs}
    # answers are bit-identical whichever replica served them
    for u in r1:
        assert np.array_equal(np.asarray(r1[u].answer),
                              np.asarray(r4[u].answer))
    # merged accounting conserves work: same totals whatever the count
    for p in (p1, p4):
        s = p.stats
        assert s["measured"]["work"] + s["warmup"]["work"] == 12
    assert sum(work_units(r) for r in r4.values()) == \
        sum(work_units(r) for r in r1.values()) == 12
    # and the routing counters account for every dispatched request
    assert sum(p4.dispatched_requests) == 12
    p4.reset_stats()
    assert p4.stats["measured"]["work"] == 0
    assert p4.dispatched_groups == [0] * 4


# -- front-door: replica-count determinism ----------------------------------


def _serve(pool, cfg, n=12, deadline_s=0.05):
    clock = VirtualClock()
    door = fd.FrontDoor({"nvsa": pool},
                        fd.FrontDoorConfig(deadline_s=deadline_s),
                        clock=clock, sleep=clock.sleep)
    reqs = _oracle_requests(cfg, n)
    arrivals = fd.poisson_arrivals("nvsa", reqs, rate_rps=200.0, seed=11)
    return door.serve(arrivals)


def test_frontdoor_answers_invariant_under_replica_count():
    cfg, p1 = _oracle_pool(replicas=1)
    _, p4 = _oracle_pool(replicas=4)
    rep1 = _serve(p1, cfg)
    rep4 = _serve(p4, cfg)
    assert set(rep1.results["nvsa"]) == set(rep4.results["nvsa"])
    for u, res in rep1.results["nvsa"].items():
        assert np.array_equal(np.asarray(res.answer),
                              np.asarray(rep4.results["nvsa"][u].answer))
    # same merged arrival trace => same admission groups, so total
    # dispatched work matches too (conservation across the pool boundary)
    w1 = sum(work_units(r) for r in rep1.results["nvsa"].values())
    w4 = sum(work_units(r) for r in rep4.results["nvsa"].values())
    assert w1 == w4 == 12


def test_frontdoor_report_carries_replica_breakdown():
    cfg, p4 = _oracle_pool(replicas=4)
    rep = _serve(p4, cfg)
    bd = rep.replica_breakdown("nvsa")
    assert bd is not None and set(bd) <= {0, 1, 2, 3}
    assert sum(r["requests"] for r in bd.values()) == 12
    assert abs(sum(r["share"] for r in bd.values()) - 1.0) < 1e-9
    assert all(r["busy_s"] >= 0 for r in bd.values())
    assert "replicas r" in rep.summary()
    # a bare (unpooled) engine reports no breakdown
    cfg1, _, bare = _oracle_engine(max_inflight=2)
    clock = VirtualClock()
    door = fd.FrontDoor({"nvsa": bare}, fd.FrontDoorConfig(deadline_s=0.05),
                        clock=clock, sleep=clock.sleep)
    rep1 = door.serve(fd.poisson_arrivals(
        "nvsa", _oracle_requests(cfg1, 4), rate_rps=200.0, seed=11))
    assert rep1.replica_breakdown("nvsa") is None


def test_pool_clock_fans_out_to_replicas():
    cfg, pool = _oracle_pool(replicas=2)
    clock = VirtualClock()
    pool.clock = clock
    assert all(r.clock is clock for r in pool.replicas)
    assert pool.clock is clock


# -- launcher validation -----------------------------------------------------


def test_launcher_mesh_flags_name_the_escape_hatch():
    from repro.launch.serve import _require_devices

    _require_devices(jax.device_count(), "--replicas")  # fits: no raise
    n = jax.device_count() + 1
    with pytest.raises(SystemExit,
                       match="xla_force_host_platform_device_count"):
        _require_devices(n, "--replicas")
    with pytest.raises(SystemExit, match="--tp"):
        _require_devices(n, "--tp")


# -- placement: each replica's arrays on its own device ----------------------

PLACEMENT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import numpy as np
from repro.configs import ARCHS
from repro.configs import base as cbase
from repro.models import nvsa
from repro.serve.engine import Request, ServeConfig
from repro.serve.reason import ReasonConfig, ReasonRequest

devs = jax.devices()
assert len(devs) == 4


def on(tree):
    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


cfg = cbase.REASON_WORKLOADS["nvsa"].make_config(d=64)
consts = {"params": None,
          "books": nvsa.nvsa_codebooks(cfg, jax.random.PRNGKey(1))}
pool = cbase.reason_engine_pool(
    "nvsa", cfg, ReasonConfig(batch_size=2, schedule="overlap"),
    consts=consts, variants=("oracle",), replicas=4)
attrs = np.zeros((8, cfg.raven.n_attrs), np.int32)
group = [ReasonRequest(uid=i, context_attrs=attrs, candidate_attrs=attrs)
         for i in range(2)]
for i, eng in enumerate(pool.replicas):
    sched = eng.schedules["oracle"]
    staged, _ = eng._stage(group, sched)
    assert on(eng.consts) == on(staged) == {devs[i]}, i
    out = staged
    for fn in sched.jit_stages:
        out = fn(eng.consts, out)
    assert on(out) == {devs[i]}, i

lm, _ = cbase.lm_engine_pool(
    "stablelm-3b", ARCHS["stablelm-3b"].make_smoke(),
    ServeConfig(max_new_tokens=4, max_slots=2, max_len=32), replicas=4)
lm.run([Request(uid=i, prompt=np.arange(5, dtype=np.int32) + i)
        for i in range(8)])
for i, eng in enumerate(lm.replicas):
    assert on(eng.params) == on(eng._caches) == {devs[i]}, i
print("PLACEMENT_OK")
"""


def test_replica_arrays_live_on_their_own_device_subprocess():
    """Four replicas on four (virtual) devices: consts, staged inputs,
    stage outputs, LM params and KV caches all sit on the replica's own
    device — no group routes through device 0."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", PLACEMENT_SCRIPT],
                       capture_output=True, text=True, timeout=600, env=env)
    assert "PLACEMENT_OK" in r.stdout, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
