"""``deploy()``: the paper's generator -> serving-architecture loop.

NSFlow's headline claim (paper Sec III, V) is *end-to-end*: a design
architecture generator reads the workload's dataflow dependencies and
emits the serving architecture.  This module closes that loop in the
actual serving path:

1. **trace** — each NSAI workload's staged pipeline is compiled and its
   :class:`~repro.core.dataflow.DataflowGraph` traced from the composed
   stages (``serve.schedule.ensure_graph`` — the same jaxpr-derived graph
   the analytic side consumes).
2. **explore** — ``core.dse.explore`` runs Algorithm 1 over the graph
   under the deployment :class:`Budget` (PE count), picking the AdArray
   shape, mode, and static nn/vsa partition.
3. **derive** — ``core.dse.serving_plan`` maps the winning design point
   onto the serving runtime's knobs (batch buckets, ``max_inflight``),
   and the engines are compiled from the *plan* instead of hand-set
   ``ReasonConfig`` fields.  Every plan serves asynchronously: the
   ``overlap`` schedule, upgraded to one ``fused`` dispatch per group
   where the fused negotiation is exact.

LM workloads (token-in/token-out archs) have a single homogeneous nn
stream — the dual-stream AdArray DSE has nothing to partition — so their
slot-pool engines are sized from the :class:`Budget` directly (``designs``
records ``None`` for them).

The result is a :class:`Deployment`: one :class:`~repro.serve.frontdoor.
FrontDoor` over every engine, so mixed LM + NSAI arrival streams serve
through a single admission layer.  ``Deployment.report()`` surfaces the
chosen ``DesignConfig.summary()`` per workload, so benchmark records can
say which DSE point served each measurement.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Iterable, Mapping

from repro.backend import registry as backend_registry
from repro.serve.control import (ControlConfig, OverloadController,
                                 validate_shed_policy)
from repro.serve.frontdoor import (ArrivalRequest, FrontDoor,
                                   FrontDoorConfig, FrontDoorReport,
                                   merge_arrivals, poisson_arrivals,
                                   with_priorities)
from repro.serve.slo import slo_targets


@dataclasses.dataclass(frozen=True)
class Traffic:
    """What the deployment is sized to serve (the ``traffic`` argument)."""

    rate_rps: float = 20.0        # per-model Poisson offered load
    deadline_s: float = 0.02      # admission-group deadline
    poll_s: float = 0.002         # front-door drain poll while in flight


@dataclasses.dataclass(frozen=True)
class Budget:
    """Resource envelope the generator explores under.

    ``devices`` / ``replicas`` / ``tp`` size the *mesh* side of the
    search: ``devices`` is the device pool (None = ``jax.device_count()``
    — fake host devices via ``XLA_FLAGS=--xla_force_host_platform_
    device_count=N``), ``replicas`` the data-parallel engine replica
    count per model (None = 1, ``"auto"`` = the data axis of the
    mesh-DSE winner under ``devices``), ``tp`` the tensor-parallel degree
    of each LM replica (NSAI pipelines serve whole-pipeline-per-device,
    so ``tp`` does not apply to them).  ``replicas`` may exceed
    ``devices`` (placement wraps round-robin) — useful on 1-device hosts
    where N replicas still shard load across N in-flight windows.

    ``slo_ms`` / ``queue_depth`` / ``shed_policy`` size the *overload
    control plane*: setting either of the first two attaches an
    :class:`~repro.serve.control.OverloadController` to the front-door,
    with the DSE-derived serving plan as its initial operating point.
    ``slo_ms`` is a scalar (interactive p99 target; see
    :func:`~repro.serve.slo.slo_targets`) or a per-class mapping;
    ``queue_depth`` bounds each model's pending queue (arrivals beyond
    it shed per ``shed_policy`` instead of growing the queue without
    bound).
    """

    max_pes: int = 4096           # AdArray PE budget handed to the DSE
    max_batch: int = 8            # admission-group ceiling (NSAI buckets)
    inflight_cap: int = 4         # ceiling on the DSE-derived window depth
    max_slots: int = 4            # LM slot-pool size
    max_len: int = 128            # LM per-slot KV capacity
    decode_block: int = 8         # LM tokens per fused decode dispatch
    max_new_tokens: int = 24      # LM default generation budget
    devices: int | None = None    # device pool (None = jax.device_count())
    replicas: int | str | None = None  # DP engine replicas (None=1, "auto")
    tp: int | None = None         # LM tensor-parallel degree (None = 1)
    # overload control plane (both None = legacy static front-door)
    slo_ms: float | Mapping[str, float] | None = None
    queue_depth: int | None = None
    shed_policy: str = "lowest-priority"


@dataclasses.dataclass
class Deployment:
    """One deployed serving runtime: protocol engines + one front-door.

    ``classes[model]`` is the runtime traffic class ("reason" | "lm");
    ``designs`` / ``plans`` carry the DSE point and derived serving plan
    for NSAI models (None for LM models); ``configs`` the per-model model
    config (an ``NVSAConfig``-style workload config or an arch smoke
    config — whatever the model class builds traffic from).
    """

    engines: dict[str, Any]
    door: FrontDoor
    classes: dict[str, str]
    designs: dict[str, Any]
    plans: dict[str, Any]
    configs: dict[str, Any]
    variants: dict[str, str | None]
    traffic: Traffic
    budget: Budget
    seed: int = 0
    # the LoweringPlan negotiated once at deploy() time; every NSAI
    # schedule compiled under it (None only for hand-built Deployments)
    backend: backend_registry.LoweringPlan | None = None
    # the per-model option kwargs deploy() was called with — kept so a
    # recorded golden trace can re-deploy the same models for replay
    options: dict = dataclasses.field(default_factory=dict)
    # mesh-DSE outcome per model: the deployed MeshPoint (data = replica
    # count, model = TP degree; empty for hand-built Deployments) and the
    # resolved replica count (defaults to 1 when absent)
    mesh: dict = dataclasses.field(default_factory=dict)
    replicas: dict = dataclasses.field(default_factory=dict)
    # the preflight AnalysisReport deploy() ran over the compiled
    # schedules + serving sources (None when preflight="off" or for
    # hand-built Deployments)
    analysis: Any = None
    # the overload controller attached to the front-door (None when the
    # Budget requested no SLO targets and no queue bound)
    controller: OverloadController | None = None

    def _pool(self, m: str):
        """The model's ReplicaPool, or None when served by a bare engine."""
        from repro.serve.replica import ReplicaPool

        eng = self.engines[m]
        return eng if isinstance(eng, ReplicaPool) else None

    def _base(self, m: str):
        """The model's representative engine (replica 0 of a pool) — the
        one to read compile-time structure (cfg / schedules) from; stats
        should come from the pool (merged) instead."""
        pool = self._pool(m)
        return pool.replicas[0] if pool is not None else self.engines[m]

    def serve(self, arrivals: Iterable[ArrivalRequest]) -> FrontDoorReport:
        """Serve one merged arrival stream through the front-door."""
        return self.door.serve(arrivals)

    def backend_record(self) -> dict | None:
        """The negotiated LoweringPlan as a plain record: platform, how it
        was chosen (negotiated vs env/explicit override), and the headline
        lowering per registered kernel."""
        if self.backend is None:
            return None
        return {
            "platform": self.backend.platform,
            "source": self.backend.source,
            "lowerings": self.backend.tags(),
        }

    def report(self) -> dict:
        """Per-model deployment record, incl. the chosen DSE point and the
        negotiated per-kernel backend lowerings."""
        out = {}
        backend = self.backend_record()
        for m, eng in self.engines.items():
            design, plan = self.designs[m], self.plans[m]
            pool, base = self._pool(m), self._base(m)
            if self.classes[m] == "reason":
                sched = base.schedules[self.variants[m]]
                # stats off ``eng``: for a pool that's the recursive sum
                # over replicas, so dispatch counts / rates stay whole-
                # deployment truths whatever the replica count
                serving = {
                    "batch_size": base.cfg.batch_size,
                    "buckets": tuple(base.cfg.buckets or ()),
                    "max_inflight": base.cfg.max_inflight,
                    "schedule": base.cfg.schedule,
                    "variant": self.variants[m],
                    # the fused-pipeline negotiation outcome for the served
                    # variant, plus the measured (non-warmup) steady-state
                    # rate — real even for engines only ever driven through
                    # the submit/drain protocol (per-group accounting)
                    "fused": {
                        "ok": sched.fused_ok,
                        "equivalence": sched.fused_equivalence,
                        "epsilon": sched.fused_epsilon,
                        "lowering_diff": sched.fused_lowering_diff,
                        "groups": eng.stats["fused_groups"],
                        "fallback_groups":
                            eng.stats["fused_fallback_groups"],
                    },
                    "dispatches": eng.stats["dispatches"],
                    "measured_requests": eng.stats["measured"]["requests"],
                    "problems_per_s": eng.problems_per_s(),
                }
            else:
                serving = {
                    "max_slots": base.cfg.max_slots,
                    "max_len": base.cfg.max_len,
                    "decode_block": base.cfg.decode_block,
                }
            point = self.mesh.get(m)
            out[m] = {
                "class": self.classes[m],
                "design": design.summary() if design is not None else None,
                "searched_points": getattr(design, "searched_points", None),
                "serving": serving,
                "backend": backend,
                # the deployed mesh factorization (data = engine replicas,
                # model = TP degree) with its predicted roofline bound,
                # and the routing/utilization split across replicas
                "mesh": point.record() if point is not None else None,
                "replicas": self.replicas.get(m, 1),
                "per_replica": pool.per_replica() if pool else None,
            }
        # the preflight verdict rides alongside the per-model records so
        # benchmark JSON carries the analysis that cleared the deployment
        out["analysis"] = (self.analysis.to_dict()
                           if self.analysis is not None else None)
        # the overload control plane in force (None = legacy static door)
        ctl = self.controller
        out["control"] = None if ctl is None else {
            "slo_ms": {p: t.total_p99_ms for p, t in ctl.targets.items()},
            "queue_depth": ctl.cfg.queue_depth,
            "shed_policy": ctl.cfg.shed_policy,
            "tick_s": ctl.cfg.tick_s,
            "operating": {m: {"deadline_s": ctl.deadline_s(m),
                              "cap": ctl.cap(m)}
                          for m in sorted(ctl.bound())},
            "ticks": ctl.ticks,
            "decisions": len(ctl.decisions),
        }
        return out

    def summary(self) -> str:
        """One line per model: class, serving knobs, DSE + backend tags."""
        lines = []
        backend = f"backend={self.backend.tag()}" if self.backend else \
            "backend=n/a"
        for m, rec in self.report().items():
            if m in ("analysis", "control"):  # deployment-wide records
                continue
            design = self.designs[m]
            if design is not None:
                dse = (f"dse={design.tag()} "
                       f"({design.searched_points} points)")
            else:
                dse = "dse=n/a (single nn stream)"
            point = self.mesh.get(m)
            mesh = (f"{point.tag()} replicas={rec['replicas']}"
                    if point is not None else "mesh=n/a")
            knobs = " ".join(f"{k}={v}" for k, v in rec["serving"].items())
            lines.append(f"{m} [{rec['class']}]: {knobs} | {dse} | {mesh} "
                         f"| {backend}")
            if rec["per_replica"]:
                split = " ".join(
                    f"r{r['replica']}:{r['groups']}g/{r['requests']}req"
                    f"/{r['share']:.0%}" for r in rec["per_replica"])
                lines.append(f"  {m} replicas: {split}")
        if self.analysis is not None:
            verdict = "PASS" if self.analysis.ok else "FAIL"
            lines.append(f"preflight {verdict}: "
                         f"{len(self.analysis.errors)} error(s), "
                         f"{len(self.analysis.warnings)} warning(s)")
        if self.controller is not None:
            ctl = self.controller
            slos = " ".join(f"{p}<= {t.total_p99_ms:.0f}ms"
                            for p, t in ctl.targets.items()) or "none"
            lines.append(f"control: slo [{slos}] "
                         f"queue_depth={ctl.cfg.queue_depth} "
                         f"shed={ctl.cfg.shed_policy} "
                         f"tick={ctl.cfg.tick_s * 1e3:.0f}ms")
        return "\n".join(lines)

    # -- synthetic traffic + warmup (launcher / benchmark helpers) ----------

    def _streams(self, n: int, seed: int):
        """Per-model lazy request streams + NSAI ground-truth thunks."""
        import numpy as np

        from repro.configs import base as cbase
        from repro.serve.engine import Request

        streams, truths = {}, {}
        for i, m in enumerate(self.engines):
            if self.classes[m] == "reason":
                factory, truth = cbase.REASON_WORKLOADS[m].make_requests(
                    self.configs[m], n, seed=seed + i)
                streams[m], truths[m] = factory(), truth
            else:
                cfg, scfg = self.configs[m], self._base(m).cfg
                plen = max(1, min(16, scfg.max_len - scfg.max_new_tokens))
                rng = np.random.default_rng(seed + i)

                def lm_stream(rng=rng, vocab=cfg.vocab, plen=plen):
                    for uid in range(n):
                        yield Request(uid=uid, prompt=rng.integers(
                            0, vocab, (plen,)).astype(np.int32))

                streams[m] = lm_stream()
        return streams, truths

    def synthetic_traffic(self, n: int, seed: int = 100,
                          priorities: str | Mapping[str, float] | None
                          = None):
        """A merged Poisson arrival feed of ``n`` requests per model at
        the deployment's offered rate.  Returns ``(arrivals, truths)``
        where ``truths[model]()`` lazily materializes ground truth for
        NSAI models (absent for LM models).  ``priorities`` stamps a
        traffic-class mix onto the stream (one class name, or a
        ``{class: weight}`` mapping sampled deterministically — see
        :func:`~repro.serve.frontdoor.with_priorities`)."""
        streams, truths = self._streams(n, seed)
        arrivals = merge_arrivals(*(
            poisson_arrivals(m, s, self.traffic.rate_rps, seed=seed + j)
            for j, (m, s) in enumerate(streams.items())))
        if priorities is not None:
            arrivals = with_priorities(arrivals, priorities, seed=seed)
        return arrivals, truths

    def warmup(self):
        """Compile every serving shape before traffic arrives: each NSAI
        bucket's jit entry and the LM prefill + decode block — so online
        latency percentiles never include jit compile.  Pooled engines
        warm every replica: the jit caches are shared across replicas but
        keyed by device placement, so each replica's device needs its own
        first touch."""
        from repro.configs import base as cbase

        for m, eng in self.engines.items():
            pool = self._pool(m)
            subs = pool.replicas if pool is not None else [eng]
            base = subs[0]
            if self.classes[m] == "reason":
                for sub in subs:
                    for b in base.cfg.buckets or (base.cfg.batch_size,):
                        factory, _ = cbase.REASON_WORKLOADS[m].make_requests(
                            self.configs[m], b, seed=5000 + b)
                        sub.run(factory())
            else:
                for sub in subs:
                    streams, _ = self._streams(base.cfg.max_slots, seed=5000)
                    sub.run(list(streams[m]))
        return self


def _mesh_plan(n_params: float, d_model: int, n_layers: int, seq: int,
               batch: int, ndev: int, replicas, tp: int, hw: dict,
               kv_bytes_per_tok: float = 0.0, bytes_per_param: float = 4.0):
    """Resolve (replica count, deployed MeshPoint) for one model.

    ``replicas="auto"`` lets the serving-mode mesh DSE pick: search the
    whole ``ndev`` pool with the model axis pinned to ``tp`` and take the
    winner's data axis.  An explicit/None replica count is honored as-is
    — the search then runs at ``chips = replicas × tp`` so the recorded
    point describes the factorization actually deployed (its ``bound_s``
    is the per-step roofline prediction for that mesh).  ``hw`` is the
    device's peaks table (``launch.mesh.device_peaks``).
    """
    from repro.core import meshdse

    def pts_at(chips, b):
        pts = meshdse.serving_search(
            n_params, n_params, d_model, n_layers, seq, b,
            devices=chips, kv_bytes_per_tok=kv_bytes_per_tok,
            bytes_per_param=bytes_per_param, max_model=tp, hw=hw)
        return [p for p in pts if p.model == tp] or pts

    if replicas == "auto":
        point = pts_at(max(1, ndev), batch)[0]
        return point.data, point
    r = int(replicas or 1)
    # the search drops data axes that don't divide the batch; an explicit
    # replica count is honored regardless, so round the modeled batch up
    b = batch if (batch % r == 0 or batch < r) else -(-batch // r) * r
    pts = pts_at(r * tp, b)
    point = next((p for p in pts if p.data == r and p.model == tp), pts[0])
    return r, point


def deploy(workloads: Iterable[str], traffic: Traffic | None = None,
           budget: Budget | None = None, *, seed: int = 0,
           options: Mapping[str, Mapping[str, Any]] | None = None,
           backend: str | backend_registry.LoweringPlan | None = None,
           preflight: str = "error",
           clock: Callable[[], float] = time.perf_counter,
           sleep: Callable[[float], None] = time.sleep) -> Deployment:
    """Deploy a mixed set of workloads behind one front-door.

    ``workloads``: model names from the runtime registry — NSAI workload
    ids (``configs.base.REASON_WORKLOADS``: nvsa, prae, mimonet, lvrf)
    and/or servable LM arch ids (llama3.2-3b, stablelm-3b, ...), freely
    mixed.  ``options[model]`` passes per-model config kwargs (NSAI:
    ``make_config`` knobs like ``d`` / ``nn_precision`` plus an optional
    ``variant`` and ``schedule``; LM: ``ServeConfig`` field overrides plus
    ``size``, ``"smoke"`` (default) or ``"full"`` for the arch's published
    widths).  An NSAI ``schedule`` replaces the DSE-derived one;
    ``"fused"`` serves one dispatch per group even where the fused
    pipeline is only epsilon-equivalent to the staged one.

    For each NSAI workload the serving configuration is *derived*, not
    hand-set: the staged pipeline's dataflow graph is traced, explored by
    ``core.dse.explore`` under ``budget.max_pes``, and the winning design
    point mapped to batch buckets / ``max_inflight`` / schedule by
    ``core.dse.serving_plan`` (see the module docstring).

    ``backend``: the kernel-lowering choice for the whole deployment —
    None negotiates against the runtime (honoring ``REPRO_BACKEND``), a
    string is an explicit override spec (``"xla"`` or
    ``"circ_conv=xla,qmatmul=pallas"``), or pass a pre-built
    :class:`~repro.backend.registry.LoweringPlan`.  Negotiation happens
    exactly once here; every NSAI schedule compiles under the resulting
    plan and ``Deployment.report()`` records the per-kernel choices.

    ``preflight``: the static-analysis gate over what was just compiled —
    ``"error"`` (default) runs the cheap preflight tier (per-stage jaxpr
    checks, retrace hazards, registry consistency, the memoized serving
    lint) and raises :class:`~repro.analyze.findings.PreflightError` when
    error-severity findings survive; ``"warn"`` runs it but only records
    the report; ``"off"`` skips it.  Either way the report lands in
    ``Deployment.report()["analysis"]``.
    """
    import jax
    import jax.numpy as jnp

    from repro.configs import base as cbase
    from repro.core import dse
    from repro.launch.mesh import device_peaks
    from repro.serve import runtime as rt
    from repro.serve import schedule as sch
    from repro.serve.engine import ServeConfig
    from repro.serve.reason import ReasonConfig

    traffic = traffic or Traffic()
    budget = budget or Budget()
    options = dict(options or {})
    models = rt.resolve_models("frontdoor", workloads)
    if not models:
        raise ValueError("deploy needs at least one workload")
    if preflight not in ("error", "warn", "off"):
        raise ValueError(f"preflight must be 'error', 'warn' or 'off', "
                         f"got {preflight!r}")
    if isinstance(backend, backend_registry.LoweringPlan):
        lowering_plan = backend
    else:
        lowering_plan = backend_registry.negotiate(override=backend)

    engines: dict[str, Any] = {}
    classes: dict[str, str] = {}
    designs: dict[str, Any] = {}
    plans: dict[str, Any] = {}
    configs: dict[str, Any] = {}
    variants: dict[str, str | None] = {}
    mesh: dict[str, Any] = {}
    replicas: dict[str, int] = {}
    ndev = budget.devices or jax.device_count()
    hw = device_peaks()
    tp_eff = budget.tp or 1
    root = jax.random.PRNGKey(seed)
    for i, m in enumerate(models):
        key = jax.random.fold_in(root, i)
        opts = dict(options.get(m, {}))
        if m in cbase.REASON_WORKLOADS:
            entry = cbase.REASON_WORKLOADS[m]
            variant = opts.pop("variant", None) or entry.variants[0]
            schedule = opts.pop("schedule", None)
            cfg = entry.make_config(**opts)
            # generator step: trace the exact pipeline the schedule will
            # execute (abstract consts — nothing materialized yet) and
            # explore the design space over its dataflow graph
            probe = cbase.compile_reason_schedule(
                m, cfg, variant=variant, batch_size=budget.max_batch,
                trace_graph=False, plan=lowering_plan)
            design = dse.explore(sch.ensure_graph(probe),
                                 max_pes=budget.max_pes)
            plan = dse.serving_plan(design, max_batch=budget.max_batch,
                                    inflight_cap=budget.inflight_cap)
            consts = entry.make_consts(cfg, key)
            # mesh co-search (serving mode): staged pipelines serve one
            # whole pipeline per device, so the model axis is pinned to 1
            # and the winner's data axis is the engine replica count
            n_params = sum(getattr(x, "size", 0)
                           for x in jax.tree.leaves(consts))
            r, point = _mesh_plan(
                float(n_params), getattr(cfg, "d", 128),
                max(1, len(entry.stage_specs(cfg, variant))), seq=1,
                batch=budget.max_batch, ndev=ndev,
                replicas=budget.replicas, tp=1, hw=hw)
            eng = cbase.reason_engine_pool(
                m, cfg,
                ReasonConfig(batch_size=plan.batch_size,
                             schedule=schedule or plan.schedule,
                             variant=variant,
                             max_inflight=plan.max_inflight,
                             buckets=plan.buckets),
                consts=consts, variants=(variant,), replicas=r,
                trace_graph=False, plan=lowering_plan,
                fused=True if schedule == "fused" else "auto")
            # fused-pipeline negotiation: when the compiled schedule's
            # fused variant is provably bit-identical under the deployment
            # plan, serve one dispatch per admission group instead of K;
            # where it is only epsilon-equivalent the plan's ``overlap``
            # stands and dispatches stage by stage (answers never change).
            # Replicas share one compiled schedule but carry their own cfg
            # copy, so the upgrade applies per replica.
            subs = eng.replicas if hasattr(eng, "replicas") else [eng]
            if schedule is None and subs[0].schedules[variant].fused_ok:
                for sub in subs:
                    sub.cfg.schedule = "fused"
            classes[m], designs[m], plans[m] = "reason", design, plan
            variants[m] = variant
        else:
            # resolve_models already validated every name against the
            # frontdoor registry, so non-NSAI names are servable LM archs
            from repro.configs import ARCHS

            size = opts.pop("size", "smoke")
            if size not in ("smoke", "full"):
                raise ValueError(f"{m}: size must be 'smoke' or 'full', "
                                 f"got {size!r}")
            if size == "full":
                # published widths, weights stored at the compute dtype:
                # the decode scan hoists the per-step f32->bf16 weight
                # casts, so f32 storage needs 1.5x the params in HBM
                # (stablelm-3b: 16.3 GB against the v5e's 15.75 GB)
                full = ARCHS[m].make_full()
                mcfg = dataclasses.replace(full,
                                           param_dtype=full.compute_dtype)
            else:
                mcfg = ARCHS[m].make_smoke()
            scfg = dataclasses.replace(
                ServeConfig(max_slots=budget.max_slots,
                            max_len=budget.max_len,
                            decode_block=budget.decode_block,
                            max_new_tokens=budget.max_new_tokens), **opts)
            # mesh co-search: LM decode may take a real TP axis through
            # distributed.sharding_rules, so the model axis is budget.tp;
            # the KV term comes from the arch config (bytes per resident
            # token across every layer's K+V, 4 bytes an element)
            kv_bytes = (getattr(mcfg, "n_layers", 1) * 2
                        * getattr(mcfg, "n_kv_heads",
                                  getattr(mcfg, "n_heads", 1))
                        * getattr(mcfg, "head_dim", 64) * 4.0)
            r, point = _mesh_plan(
                float(cbase.param_count(ARCHS[m], mcfg)),
                getattr(mcfg, "d_model", 128),
                getattr(mcfg, "n_layers", 1), seq=budget.max_len,
                batch=budget.max_slots, ndev=ndev,
                replicas=budget.replicas, tp=tp_eff, hw=hw,
                kv_bytes_per_tok=kv_bytes,
                bytes_per_param=float(jnp.dtype(mcfg.param_dtype).itemsize))
            eng, cfg = cbase.lm_engine_pool(m, mcfg, scfg, key=key,
                                            replicas=r, tp=tp_eff)
            classes[m], designs[m], plans[m] = "lm", None, None
            variants[m] = None
        engines[m], configs[m] = eng, cfg
        mesh[m], replicas[m] = point, r

    # preflight gate: the cheap analysis tier over exactly what was just
    # compiled — the schedules the engines will serve, under the one
    # negotiated plan — plus the serving-source lint (mtime-memoized, so
    # repeat deploys pay ~nothing) and the static registry checks.  No
    # kernel probes, no double-trace: those are the CLI/CI tier.
    analysis = None
    if preflight != "off":
        from repro.analyze.preflight import preflight as run_preflight
        from repro.serve.replica import ReplicaPool

        subjects = []
        for m in models:
            if classes[m] != "reason":
                continue
            eng = engines[m]
            base = eng.replicas[0] if isinstance(eng, ReplicaPool) else eng
            subjects.append((base.schedules[variants[m]], configs[m],
                             cbase.REASON_WORKLOADS[m], variants[m]))
        analysis = run_preflight(subjects)
        if preflight == "error" and not analysis.ok:
            from repro.analyze.findings import PreflightError

            raise PreflightError(analysis)

    # overload control plane: requested via the Budget's SLO/queue knobs.
    # The DSE-derived serving plan is the controller's *initial* operating
    # point — the feedback loop adapts deadline/cap from there, and the
    # plan's buckets are the cap steps it may move across.
    controller = None
    if budget.slo_ms is not None or budget.queue_depth is not None:
        validate_shed_policy(budget.shed_policy)
        controller = OverloadController(
            targets=slo_targets(budget.slo_ms),
            cfg=ControlConfig(queue_depth=budget.queue_depth,
                              shed_policy=budget.shed_policy))
        for m in models:
            if classes[m] == "reason":
                cap = plans[m].batch_size
                buckets = tuple(plans[m].buckets or (cap,))
            else:
                cap = budget.max_slots
                buckets = None
            controller.bind(m, deadline_s=traffic.deadline_s, cap=cap,
                            buckets=buckets)

    door = FrontDoor(engines,
                     FrontDoorConfig(deadline_s=traffic.deadline_s,
                                     poll_s=traffic.poll_s),
                     clock=clock, sleep=sleep, controller=controller)
    return Deployment(engines=engines, door=door, classes=classes,
                      designs=designs, plans=plans, configs=configs,
                      variants=variants, traffic=traffic, budget=budget,
                      seed=seed, backend=lowering_plan,
                      options={m: dict(options.get(m, {})) for m in models
                               if options.get(m)},
                      mesh=mesh, replicas=replicas, analysis=analysis,
                      controller=controller)
