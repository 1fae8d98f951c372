"""Preflight static-analysis suite.

Three layers, mirroring the analyzer's contract:

* **golden known-bad fixtures** — one seeded offender per rule family
  (NSF001–NSF007 artifact/registry rules, NSF101–NSF104 lint rules),
  each asserting *exactly* its rule fires, so a rule that silently stops
  matching shows up as a failed golden rather than a quiet pass;
* **clean passes** — the real serving sources lint clean (the raw
  ``time.perf_counter()`` regression), the real registry is consistent,
  and every NSAI workload's compiled schedule clears the full artifact +
  retrace pass across its buckets;
* **integration** — the CLI entry point, ``deploy()``'s preflight gate
  (error raises, warn records), and the injectable ``wall`` clock the
  lint forced into the engines.
"""

import dataclasses
import json
import textwrap
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analyze import (AnalysisReport, PreflightError, RULES, finding,
                           lint_file, lint_tree, preflight)
from repro.analyze import artifacts, registry_check, retrace
from repro.backend import registry
from repro.configs import base as cbase

# -- fixture scaffolding ------------------------------------------------------

_SPECS = {"x": jax.ShapeDtypeStruct((4, 8), jnp.float32)}


def _cpu_plan():
    return registry.negotiate(platform="cpu", override="")


@dataclasses.dataclass
class _Stage:
    name: str
    stream: str
    fn: object


class _FakeSched:
    """Just enough StagedSchedule surface for the artifact/retrace checks."""

    def __init__(self, stages, input_specs=None, plan=None, buckets=(),
                 jit_fused=None):
        self.stages = list(stages)
        self.input_specs = _SPECS if input_specs is None else input_specs
        self.consts_spec = {}
        self.plan = plan or _cpu_plan()
        self.batch_buckets = tuple(buckets)
        self.jit_fused = jit_fused
        self.workload = "fixture"
        self.variant = "bad"

    def covering_bucket(self, n):
        for b in self.batch_buckets:
            if b >= n:
                return b
        raise ValueError(f"no bucket covers {n}")


def _rules_of(report):
    return sorted({f.rule for f in report.findings})


# -- golden fixtures: artifact rules (NSF001-NSF004) --------------------------


def test_nsf001_downcast_below_declared_int_precision():
    """f32 -> bf16 inside a vsa stage declared int8 is a precision error."""
    def fn(consts, bufs):
        return {"x": bufs["x"].astype(jnp.bfloat16).astype(jnp.float32)}

    cfg = types.SimpleNamespace(nn_precision="fp32", symb_precision="int8")
    sched = _FakeSched([_Stage("symbolic", "vsa", fn)])
    rep = artifacts.check_schedule(sched, cfg=cfg)
    assert _rules_of(rep) == ["NSF001"]
    assert not rep.ok


def test_nsf001_ignores_downcast_under_float_precision():
    """The same cast under declared fp32 symbolic precision is legal."""
    def fn(consts, bufs):
        return {"x": bufs["x"].astype(jnp.bfloat16).astype(jnp.float32)}

    cfg = types.SimpleNamespace(nn_precision="fp32", symb_precision="fp32")
    sched = _FakeSched([_Stage("symbolic", "vsa", fn)])
    assert artifacts.check_schedule(sched, cfg=cfg).ok


def test_nsf001_f64_upcast():
    def fn(consts, bufs):
        wide = jax.lax.convert_element_type(bufs["x"], jnp.float64)
        return {"x": wide.astype(jnp.float32)}

    sched = _FakeSched([_Stage("drift", "nn", fn)])
    with jax.enable_x64():
        rep = artifacts.check_schedule(sched)
    assert "NSF001" in _rules_of(rep)
    assert any("float64" in f.message for f in rep.findings)


def test_nsf002_mixed_amax_axes():
    """Global + per-problem amax scales in one stage = admission-group
    dependent numerics (warning, not error)."""
    def fn(consts, bufs):
        x = bufs["x"]
        global_scale = jnp.max(jnp.abs(x))
        per_problem = jnp.max(jnp.abs(x), axis=1, keepdims=True)
        return {"x": x / global_scale + x / per_problem}

    rep = artifacts.check_schedule(_FakeSched([_Stage("quant", "vsa", fn)]))
    assert _rules_of(rep) == ["NSF002"]
    assert rep.ok  # warning severity: reported, never fails preflight


def test_nsf003_host_callback_in_stage():
    def fn(consts, bufs):
        x = bufs["x"]
        y = jax.pure_callback(lambda a: a,
                              jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return {"x": y}

    rep = artifacts.check_schedule(_FakeSched([_Stage("leak", "nn", fn)]))
    assert _rules_of(rep) == ["NSF003"]
    assert not rep.ok


def test_nsf004_off_cpu_fused_without_donation():
    def fused(consts, bufs):
        return {"x": bufs["x"] * 2.0}

    sched = _FakeSched([], plan=registry.negotiate(platform="tpu",
                                                   override=""),
                       jit_fused=jax.jit(fused))
    rep = artifacts.check_schedule(sched)
    assert _rules_of(rep) == ["NSF004"]
    assert not rep.ok


def test_nsf004_off_cpu_fused_no_reusable_buffer_is_clean():
    """An input no output can reuse (RAVEN panels in, logprobs out) has
    nothing to donate: JAX would drop the annotation anyway."""
    def fused(consts, bufs):
        return {"y": bufs["x"].sum(axis=1)}

    sched = _FakeSched([], plan=registry.negotiate(platform="tpu",
                                                   override=""),
                       jit_fused=jax.jit(fused))
    rep = artifacts.check_schedule(sched)
    assert rep.findings == []


def test_nsf004_cpu_fused_with_donation_warns():
    def fused(consts, bufs):
        return {"x": bufs["x"] * 2.0}

    sched = _FakeSched([], jit_fused=jax.jit(fused, donate_argnums=(1,)))
    rep = artifacts.check_schedule(sched)
    assert _rules_of(rep) == ["NSF004"]
    assert rep.ok  # CPU-side donation is a warning (XLA:CPU just ignores it)


def test_nsf004_clean_cpu_fused():
    def fused(consts, bufs):
        return {"x": bufs["x"] * 2.0}

    rep = artifacts.check_schedule(_FakeSched([], jit_fused=jax.jit(fused)))
    assert rep.findings == []
    assert rep.coverage["fused_donation"] == 1


# -- golden fixtures: retrace hazards (NSF005) --------------------------------


def test_nsf005_bucket_closure_hole():
    class _Leaky(_FakeSched):
        def covering_bucket(self, n):
            return n  # 1 and 3 are not declared buckets

    rep = retrace.check_retrace(_Leaky([], buckets=(2, 4)))
    assert _rules_of(rep) == ["NSF005"]
    assert len(rep.findings) == 2  # n=1 and n=3 both escape the bucket set


def test_nsf005_group_size_leaks_into_nonbatch_axis():
    entry = types.SimpleNamespace(input_specs=lambda cfg, b, v: {
        "x": jax.ShapeDtypeStruct((b, b + 7), jnp.float32)})
    out = retrace.check_bucket_specs(entry, None, None, (2, 4), "fixture")
    assert sorted({f.rule for f in out}) == ["NSF005"]
    assert any("non-batch" in f.message for f in out)


def test_nsf005_nondeterministic_stage_trace():
    counter = iter(range(100))

    def fn(consts, bufs):
        return {"x": bufs["x"] + float(next(counter))}

    sched = _FakeSched([_Stage("drift", "nn", fn)], buckets=(4,))
    rep = retrace.check_retrace(sched, double_trace=True)
    assert "NSF005" in _rules_of(rep)
    assert any("traces differently" in f.message for f in rep.findings)


def test_nsf005_clean_on_deterministic_stage():
    def fn(consts, bufs):
        return {"x": bufs["x"] * 2.0}

    sched = _FakeSched([_Stage("ok", "nn", fn)], buckets=(2, 4))
    rep = retrace.check_retrace(sched, double_trace=True)
    assert rep.findings == []
    assert rep.coverage == {"bucket_closure": 1, "double_trace": 1}


# -- golden fixtures: registry rules (NSF006/NSF007) --------------------------


def test_nsf006_registry_entry_without_kernel_package(monkeypatch):
    monkeypatch.setitem(registry.KERNELS, "ghost_kernel",
                        registry.KERNELS["qmatmul"])
    rep = registry_check.check_static()
    assert [f.rule for f in rep.findings] == ["NSF006"]
    assert "ghost_kernel" in rep.findings[0].where


def test_nsf006_twin_predicate_drift(monkeypatch):
    """A shape-predicate fix applied to circ_conv but not its circulant
    twin unbind_classify must fire the twin check."""
    spec = registry.KERNELS["unbind_classify"]
    pallas = spec.by_name("pallas")
    lows = tuple(dataclasses.replace(low, min_size=16)
                 if low is pallas else low for low in spec.lowerings)
    monkeypatch.setitem(registry.KERNELS, "unbind_classify",
                        dataclasses.replace(spec, lowerings=lows))
    rep = registry_check.check_static()
    assert [f.rule for f in rep.findings] == ["NSF006"]
    assert "circ_conv+unbind_classify" in rep.findings[0].where


def test_nsf007_floor_without_dispatch_site(monkeypatch):
    spec = registry.KERNELS["qmatmul"]
    assert spec.dispatch_min_size == 0  # precondition: floorless today
    monkeypatch.setitem(registry.KERNELS, "qmatmul",
                        dataclasses.replace(spec, dispatch_min_size=64))
    rep = registry_check.check_dispatch_floors()
    assert [f.rule for f in rep.findings] == ["NSF007"]
    assert "dead policy" in rep.findings[0].message


def test_nsf007_dispatch_site_without_floor(monkeypatch):
    spec = registry.KERNELS["circ_conv"]
    assert spec.dispatch_min_size > 0  # precondition: floored today
    monkeypatch.setitem(registry.KERNELS, "circ_conv",
                        dataclasses.replace(spec, dispatch_min_size=0))
    rep = registry_check.check_dispatch_floors()
    assert [f.rule for f in rep.findings] == ["NSF007"]
    assert "no-op" in rep.findings[0].message


# -- golden fixtures: serving lint (NSF101-NSF104) ----------------------------


def _lint(tmp_path, src, name="fixture.py"):
    """Write a fixture under a serve/ dir so path routing applies.

    ``name`` matters to NSF105's clock half, which keys on control-plane
    basenames (control.py / slo.py / sim.py).
    """
    p = tmp_path / "serve" / name
    p.parent.mkdir(exist_ok=True)
    p.write_text(textwrap.dedent(src))
    return AnalysisReport(list(lint_file(str(p))))


def test_nsf101_raw_clock_call(tmp_path):
    rep = _lint(tmp_path, """
        import time

        def measure():
            t0 = time.perf_counter()
            return time.perf_counter() - t0
        """)
    assert _rules_of(rep) == ["NSF101"]
    assert len(rep.findings) == 2


def test_nsf101_injectable_clock_default_is_clean(tmp_path):
    rep = _lint(tmp_path, """
        import time

        def measure(clock=time.perf_counter, wall=time.perf_counter):
            return wall() - clock()
        """)
    assert rep.findings == []


def test_nsf102_host_materialization_in_jit(tmp_path):
    rep = _lint(tmp_path, """
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return np.asarray(x) + 1
        """)
    assert _rules_of(rep) == ["NSF102"]


def test_nsf102_host_materialization_outside_jit_is_clean(tmp_path):
    rep = _lint(tmp_path, """
        import jax
        import numpy as np

        def collect(x):
            return np.asarray(x) + 1
        """)
    assert rep.findings == []


def test_nsf103_prngkey_without_fold_in(tmp_path):
    rep = _lint(tmp_path, """
        import jax

        def make_stream(seed):
            return jax.random.PRNGKey(seed)
        """)
    assert _rules_of(rep) == ["NSF103"]


def test_nsf103_fold_in_derivation_is_clean(tmp_path):
    rep = _lint(tmp_path, """
        import jax

        def make_stream(seed, i):
            root = jax.random.PRNGKey(seed)
            return jax.random.fold_in(root, i)
        """)
    assert rep.findings == []


def test_nsf104_blocks_before_stamping(tmp_path):
    rep = _lint(tmp_path, """
        import jax

        class BadEngine:
            def submit(self, group):
                out = jax.block_until_ready(self.fn(group))
                rec = self.record(group)
                rec.dispatch_t = self.clock()
                return rec
        """)
    assert _rules_of(rep) == ["NSF104"]


def test_nsf104_never_stamps(tmp_path):
    rep = _lint(tmp_path, """
        class WorseEngine:
            def submit(self, group):
                return list(group)
        """)
    assert _rules_of(rep) == ["NSF104"]


def test_nsf104_stamp_then_block_is_clean(tmp_path):
    rep = _lint(tmp_path, """
        import jax

        class GoodEngine:
            def submit(self, group):
                rec = self.record(group)
                rec.dispatch_t = self.clock()
                jax.block_until_ready(self.fn(group))
                return rec
        """)
    assert rep.findings == []


def test_nsf105_unbounded_queue_append(tmp_path):
    # method named enqueue (not submit) so NSF104 doesn't co-fire
    rep = _lint(tmp_path, """
        class Router:
            def __init__(self):
                self.pending = []

            def enqueue(self, item):
                self.pending.append(item)
        """)
    assert _rules_of(rep) == ["NSF105"]
    assert "bound check" in rep.findings[0].message


def test_nsf105_bounded_queue_append_is_clean(tmp_path):
    rep = _lint(tmp_path, """
        class Router:
            def __init__(self, depth):
                self.pending = []
                self.depth = depth

            def enqueue(self, item):
                if len(self.pending) >= self.depth:
                    return False
                self.pending.append(item)
                return True
        """)
    assert rep.findings == []


def test_nsf105_closure_bound_check_does_not_dominate(tmp_path):
    # the check lives in a nested function — the outer append is still
    # unbounded, so the closure must not satisfy the rule
    rep = _lint(tmp_path, """
        class Router:
            def enqueue(self, item):
                def bounded():
                    return len(self.pending) < self.depth
                self.pending.append(item)
                return bounded
        """)
    assert _rules_of(rep) == ["NSF105"]


def test_nsf105_non_queue_append_is_clean(tmp_path):
    rep = _lint(tmp_path, """
        def collect(rows):
            out = []
            for r in rows:
                out.append(r)
            return out
        """)
    assert rep.findings == []


def test_nsf105_time_reference_in_control_plane_module(tmp_path):
    # attribute *reference* (no call) — NSF101 only flags calls, so this
    # would slip through without the control-plane clause
    rep = _lint(tmp_path, """
        import dataclasses
        import time


        @dataclasses.dataclass
        class ControlConfig:
            clock: object = time.monotonic
        """, name="control.py")
    assert _rules_of(rep) == ["NSF105"]
    assert len(rep.findings) == 2  # the import and the reference
    assert "control-plane" in rep.findings[0].message


def test_nsf105_time_reference_outside_control_plane_is_clean(tmp_path):
    rep = _lint(tmp_path, """
        import dataclasses
        import time


        @dataclasses.dataclass
        class Cfg:
            clock: object = time.monotonic
        """, name="helpers.py")
    assert rep.findings == []


# -- clean passes over the real stack -----------------------------------------


def test_serving_sources_lint_clean():
    """Regression for the raw time.perf_counter() offenders the lint
    originally flagged in serve/ — the tree must stay clean."""
    import repro.serve as serve_pkg

    rep = lint_tree(serve_pkg.__path__[0])
    assert rep.findings == [], rep.render()
    assert rep.coverage["lint_files"] >= 8


def test_whole_package_lint_clean():
    import repro

    rep = lint_tree(repro.__path__[0])
    assert rep.findings == [], rep.render()


def test_registry_static_consistency_clean():
    rep = registry_check.check_registry(probe=False)
    assert rep.findings == [], rep.render()
    assert rep.coverage["registry_static"] == len(registry.KERNELS)
    assert rep.coverage["dispatch_floors"] == len(registry.KERNELS)


@pytest.mark.slow
def test_registry_probes_clean():
    """Empirical interpret-vs-reference probes (the check that demoted the
    registry's over-strict non-pow2 claim) find nothing today."""
    rep = registry_check.check_probes()
    assert rep.findings == [], rep.render()
    assert rep.coverage["kernel_probes"] >= 10


@pytest.mark.parametrize("model", sorted(cbase.REASON_WORKLOADS))
def test_clean_pass_real_workload(model):
    """Every NSAI workload's compiled schedule clears the full artifact +
    retrace pass across its buckets (abstract consts — no params)."""
    entry = cbase.REASON_WORKLOADS[model]
    cfg = entry.make_config(d=32)
    variant = entry.variants[0]
    sched = cbase.compile_reason_schedule(model, cfg, variant,
                                          batch_size=(1, 2, 4),
                                          trace_graph=False,
                                          plan=_cpu_plan())
    rep = preflight([(sched, cfg, entry, variant)], double_trace=True)
    assert rep.ok, rep.render()
    assert rep.coverage["schedules"] == 1
    assert rep.coverage["stage_jaxprs"] >= 1
    assert rep.coverage["bucket_specs"] == 1
    assert rep.coverage["double_trace"] == 1


# -- findings / report datatypes ----------------------------------------------


def test_finding_validates_rule_and_severity():
    with pytest.raises(ValueError):
        finding("NSF999", "x", "no such rule")
    with pytest.raises(ValueError):
        finding("NSF001", "x", "bad severity", severity="fatal")
    f = finding("NSF002", "here", "msg")
    assert f.severity == RULES["NSF002"][0] == "warning"


def test_report_merge_and_verdict():
    a = AnalysisReport([finding("NSF003", "a", "err")], {"c": 1})
    b = AnalysisReport([finding("NSF002", "b", "warn")], {"c": 2, "d": 1})
    a.merge(b)
    assert not a.ok and len(a.errors) == 1 and len(a.warnings) == 1
    assert a.coverage == {"c": 3, "d": 1}
    assert set(a.by_rule()) == {"NSF002", "NSF003"}
    assert "preflight FAIL: 1 error(s), 1 warning(s)" in a.render()
    round_trip = json.loads(a.to_json())
    assert round_trip["ok"] is False and len(round_trip["findings"]) == 2


# -- CLI ----------------------------------------------------------------------


def test_cli_lint_and_registry_only(tmp_path, capsys):
    from repro.analyze.__main__ import main

    out = tmp_path / "results" / "ANALYZE.json"
    rc = main(["--workload", "none", "--format", "json", "--out", str(out),
               "--no-probe", "--no-double-trace"])
    assert rc == 0
    data = json.loads(out.read_text())
    assert data["ok"] is True
    assert data["coverage"]["lint_files"] >= 1
    assert data["coverage"]["registry_static"] == len(registry.KERNELS)
    assert json.loads(capsys.readouterr().out) == data


def test_cli_rejects_unknown_workload():
    from repro.analyze.__main__ import main

    with pytest.raises(SystemExit):
        main(["--workload", "not_a_workload"])


# -- deploy() preflight gate --------------------------------------------------


def _seeded_failure(subjects, **kw):
    rep = AnalysisReport()
    rep.findings.append(finding("NSF003", "fixture/stage", "seeded error"))
    return rep


def test_deploy_preflight_gate(monkeypatch):
    import importlib

    # the package re-exports the preflight *function*, which shadows the
    # submodule on attribute access — resolve the module explicitly
    pf = importlib.import_module("repro.analyze.preflight")
    from repro.serve.deploy import Budget, deploy

    opts = {"nvsa": {"d": 32}}
    monkeypatch.setattr(pf, "preflight", _seeded_failure)
    # warn: the failing report is recorded, deploy still succeeds
    dep = deploy(["nvsa"], options=opts, budget=Budget(max_batch=2),
                 preflight="warn")
    rec = dep.report()["analysis"]
    assert rec["ok"] is False and rec["errors"] == 1
    assert "preflight FAIL: 1 error(s)" in dep.summary()
    # error (the default): same findings abort the deploy
    with pytest.raises(PreflightError) as ei:
        deploy(["nvsa"], options=opts, budget=Budget(max_batch=2))
    assert [f.rule for f in ei.value.report.findings] == ["NSF003"]
    # off: nothing runs, nothing recorded
    monkeypatch.setattr(pf, "preflight", _boom)
    dep = deploy(["nvsa"], options=opts, budget=Budget(max_batch=2),
                 preflight="off")
    assert dep.report()["analysis"] is None
    with pytest.raises(ValueError, match="preflight"):
        deploy(["nvsa"], options=opts, preflight="bogus")


def _boom(*a, **kw):  # preflight="off" must never reach the analyzer
    raise AssertionError("preflight ran despite preflight='off'")


# -- injectable wall clock (the NSF101 fix) -----------------------------------


class _Ticker:
    """Deterministic fake wall: each read advances a huge step, so any
    accounting it feeds is unmistakably not real time."""

    def __init__(self, step=1000.0):
        self.t, self.step = 0.0, step

    def __call__(self):
        self.t += self.step
        return self.t


def test_reason_engine_wall_is_injectable():
    from repro.models import nvsa
    from repro.serve.reason import ReasonConfig, requests_from_batch

    cfg = cbase.REASON_WORKLOADS["nvsa"].make_config(d=32)
    consts = {"params": None,
              "books": nvsa.nvsa_codebooks(cfg, jax.random.PRNGKey(1))}
    eng = cbase.reason_engine(
        "nvsa", cfg, ReasonConfig(batch_size=2, schedule="sequential"),
        consts=consts, variants=("oracle",), trace_graph=False)
    eng.wall = _Ticker()

    from repro.data import raven

    def reqs(seed):
        return requests_from_batch(raven.generate_batch(cfg.raven,
                                                        seed=seed, n=2))

    eng.run(reqs(3), variant="oracle")          # cold run -> warmup bucket
    assert eng.stats["warmup"]["wall_time_s"] >= 1000.0
    eng.run(reqs(4), variant="oracle")          # steady state -> measured
    assert eng.stats["measured"]["wall_time_s"] >= 1000.0
    # the measured rate reads the fake wall, not the real clock
    assert 0 < eng.problems_per_s() < 1.0


def test_lm_engine_wall_is_injectable():
    from repro.configs import ARCHS
    from repro.serve.engine import Engine, ServeConfig

    arch = ARCHS["llama3.2-3b"]
    mcfg = arch.make_smoke()
    from repro.nn import init as nninit

    params = nninit.materialize(cbase.model_spec(arch, mcfg),
                                jax.random.PRNGKey(0))
    step, init_caches = cbase.serve_fns(arch, mcfg, max_len=32)
    eng = Engine(step, init_caches,
                 ServeConfig(max_new_tokens=4, max_slots=2, max_len=32,
                             decode_block=2),
                 params=params, wall=_Ticker())
    prompts = np.random.default_rng(0).integers(
        0, mcfg.vocab, (2, 6)).astype(np.int32)
    eng.generate(prompts)
    assert eng.stats["decode_time_s"] >= 1000.0


def test_replica_pool_wall_delegates_and_falls_back():
    import time

    from repro.serve.replica import ReplicaPool

    ticker = _Ticker()
    with_wall = types.SimpleNamespace(admission_cap=4, wall=ticker)
    pool = ReplicaPool([with_wall])
    assert pool.wall is ticker
    legacy = types.SimpleNamespace(admission_cap=4)  # pre-`wall` engine
    assert ReplicaPool([legacy]).wall is time.perf_counter
