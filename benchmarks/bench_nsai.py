"""NSAI reasoning-traffic benchmark: the serving analogue of paper Fig. 9.

Serves synthetic problems for any registered workload (``--model nvsa |
prae | mimonet | lvrf`` — the list derives from
``configs.base.REASON_WORKLOADS``) through the generic staged-pipeline
engine and reports reasoning-problems/s for:

  - each compiled pipeline stage in isolation (the per-stage timing
    breakdown, paper Fig. 9's per-unit bars — stream tags included)
  - the naive sequential schedule (sync after every stage)
  - the overlapped double-buffered schedule (steady-state pipeline)
  - (nvsa) the symbolic-stream-only oracle variant and Tab. IV mixed
    precision (nn int8 through the Pallas qmatmul kernel, symbolic int4)
  - an **online latency-vs-offered-load sweep**: Poisson arrivals at
    fractions of the measured offline throughput through the
    deadline-batched, shape-bucketed front-door (``serve.frontdoor``),
    reporting achieved problems/s plus p50/p95 queueing and service
    latency (and total p99) per schedule at each load point.
  - ``--scaling``: a paper-style **symbolic-scaling sweep** — runtime as
    the VSA dimension grows (NSFlow's headline: 150x symbolic scale ->
    only 4x runtime), with the fused whole-pipeline schedule (one jit
    dispatch per admission group) tracked as a ratio against the staged
    schedule (K dispatches) at every scale point.  ``--check`` gates the
    largest scale point: one dispatch per fused group, zero fallbacks,
    and the fused wall clock not behind staged beyond a 10% noise floor
    (dispatch savings are O(100us)/group, so strict wall-clock ordering
    is unmeasurable over scheduler noise on shared runners).

The request stream is a lazy generator — per-request rendering runs inside
the pipeline, exactly the preprocessing a serving frontend would do — so
the overlapped schedule's host/device overlap is measured, not idealized.

Run:  PYTHONPATH=src python benchmarks/bench_nsai.py [--model nvsa]
          [--json out.json] [--check-overlap] [--problems N]
          [--batch-size B] [--d D] [--loads 0.5,0.8,1.2]
          [--deadline-ms 10] [--no-sweep]

``--check-overlap`` exits non-zero if the overlapped schedule does not beat
the sequential one, or if the load sweep emitted no p50/p95 latency rows
(the CI regression gates for the pipeline and the front-door).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax


def _best_of(fn, iters: int = 3) -> float:
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _dse_point(schedule, max_pes: int = 4096):
    """Explore the schedule's traced dataflow graph; returns (DesignConfig,
    comma-free provenance tag) so every BENCH row can record which DSE
    point served its measurement."""
    from repro.core import dse
    from repro.serve import schedule as sch

    design = dse.explore(sch.ensure_graph(schedule), max_pes=max_pes)
    return design, f"dse={design.tag()}"


def _stamp_backend(rows):
    """Append the active lowering-plan tag to every row's provenance so
    measurements are attributable to the backend that produced them."""
    from repro.backend import registry

    btag = f"backend={registry.get_plan().tag()}"
    return [(name, val, f"{derived} {btag}") for name, val, derived in rows]


def bench_nsai(model: str = "nvsa", problems: int = 32, batch_size: int = 4,
               d: int = 64, iters: int = 3):
    from repro.configs import base as cbase
    from repro.serve.reason import ReasonConfig

    entry = cbase.REASON_WORKLOADS[model]
    cfg = entry.make_config(d=d)
    consts = entry.make_consts(cfg, jax.random.PRNGKey(0))
    eng = cbase.reason_engine(model, cfg, ReasonConfig(batch_size=batch_size),
                              consts=consts)
    default = entry.variants[0]
    sched = eng.schedules[default]
    design, dse_tag = _dse_point(sched)

    rows = [(f"nsai/{model}/dse/t_best_cycles", design.t_best,
             f"{dse_tag} points={design.searched_points}")]
    n = problems

    def stream(count, start=0):
        factory, _ = entry.make_requests(cfg, count, seed=9000 + start)
        return factory()

    # warm both schedules' jit caches (shared engine instance)
    eng.run(stream(batch_size), schedule="overlap")
    eng.run(stream(batch_size), schedule="sequential")

    # -- per-stage breakdown (paper Fig. 9's per-unit bars) -----------------
    # time each compiled stage in isolation on pre-staged buffers
    staged = [eng._stage(b, sched)[0] for b in eng._batches(list(stream(n)))]
    for si, (spec, fn) in enumerate(zip(sched.stages, sched.jit_stages)):
        dt = _best_of(lambda: [jax.block_until_ready(fn(consts, b))
                               for b in staged], iters)
        rows.append((f"nsai/{model}/stage/{spec.name}/problems_s", n / dt,
                     f"stream={spec.stream}"))
        staged = [fn(consts, b) for b in staged]
        jax.block_until_ready(staged)

    # -- schedules, end to end (ingest -> answer) ---------------------------
    dt_seq = _best_of(lambda: eng.run(stream(n),
                                      schedule="sequential"), iters)
    rows.append((f"nsai/{model}/sequential/problems_s", n / dt_seq,
                 "sync after every stage"))
    dt_ovl = _best_of(lambda: eng.run(stream(n),
                                      schedule="overlap"), iters)
    rows.append((f"nsai/{model}/overlap/problems_s", n / dt_ovl,
                 "double-buffered"))
    rows.append((f"nsai/{model}/overlap_vs_sequential/speedup",
                 dt_seq / dt_ovl,
                 f"problems={n} batch={batch_size} "
                 f"pipeline={'->'.join(sched.stage_names)} {dse_tag}"))

    if model == "nvsa":
        rows.extend(_bench_nvsa_extras(cbase, entry, cfg, consts, eng,
                                       stream, n, batch_size, d, iters))
    return _stamp_backend(rows)


def _bench_nvsa_extras(cbase, entry, cfg, consts, eng, stream, n,
                       batch_size, d, iters):
    """NVSA-only rows: oracle variant + Tab. IV mixed precision."""
    from repro.serve.reason import ReasonConfig
    from repro.vsa import ops as vsa_ops

    rows = []
    # symbolic-stream-only serving (oracle variant)
    factory, truth = entry.make_requests(cfg, n, seed=9000)
    res = eng.run(factory(), schedule="overlap", variant="oracle")
    acc = entry.score(res, truth())
    dt = _best_of(lambda: eng.run(stream(n), schedule="overlap",
                                  variant="oracle"), iters)
    rows.append(("nsai/nvsa/oracle_overlap/problems_s", n / dt,
                 f"accuracy={acc:.3f} circ path={vsa_ops.dispatch_path(d)}"))

    # Tab. IV mixed precision through the qmatmul kernel
    mp_cfg = entry.make_config(d=d, nn_precision="int8",
                               symb_precision="int4")
    mp_eng = cbase.reason_engine("nvsa", mp_cfg,
                                 ReasonConfig(batch_size=batch_size),
                                 consts=consts, variants=("cnn",))
    mp_eng.run(stream(batch_size), schedule="overlap")
    dt = _best_of(lambda: mp_eng.run(stream(n),
                                     schedule="overlap"), iters)
    rows.append(("nsai/nvsa/mixed_int8_int4_overlap/problems_s", n / dt,
                 "nn=int8 via qmatmul / symb=int4"))
    return rows


def bench_scaling(model: str, problems: int = 32, batch_size: int = 4,
                  dims=(64, 128), iters: int = 3):
    """Symbolic-scaling sweep: fused vs staged runtime as the VSA dim grows.

    The paper's scalability claim is that symbolic scale-up must not scale
    runtime proportionally (150x scale -> 4x runtime, Fig. 10); the serving
    analogue measured here is the problems/s curve over the VSA block dim
    for both pipeline schedules, with ``fused_vs_staged`` (staged time /
    fused time; >= 1.0 means the single-dispatch pipeline wins) a tracked
    ratio per scale point.  RAVEN reasoners sweep their symbolic-only
    ``oracle`` variant so the curve is the symbolic stream's, not the CNN
    frontend's.  Rows record the schedule's fused-negotiation outcome and
    the measured per-group dispatch counts (K staged vs 1 fused).
    """
    from repro.configs import base as cbase
    from repro.serve.reason import ReasonConfig

    entry = cbase.REASON_WORKLOADS[model]
    variant = "oracle" if "oracle" in entry.variants else entry.variants[0]
    rows = []
    fused_dts = {}
    for d in dims:
        cfg = entry.make_config(d=d)
        consts = entry.make_consts(cfg, jax.random.PRNGKey(0))
        eng = cbase.reason_engine(
            model, cfg, ReasonConfig(batch_size=batch_size, variant=variant),
            consts=consts, variants=(variant,), trace_graph=False)
        sched = eng.schedules[variant]

        def stream(count, start=0):
            factory, _ = entry.make_requests(cfg, count, seed=9500 + start)
            return factory()

        # warm both paths' jit caches before timing
        eng.run(stream(batch_size), schedule="overlap")
        eng.run(stream(batch_size), schedule="fused")

        # measured per-group dispatch counts (the K -> 1 claim)
        d0, b0 = eng.stats["dispatches"], eng.stats["batches"]
        eng.run(stream(problems), schedule="overlap")
        disp_staged = (eng.stats["dispatches"] - d0) / \
            max(1, eng.stats["batches"] - b0)
        d0, b0 = eng.stats["dispatches"], eng.stats["batches"]
        f0 = eng.stats["fused_fallback_groups"]
        eng.run(stream(problems), schedule="fused")
        disp_fused = (eng.stats["dispatches"] - d0) / \
            max(1, eng.stats["batches"] - b0)
        fallbacks = eng.stats["fused_fallback_groups"] - f0

        n = problems
        dt_staged = _best_of(lambda: eng.run(stream(n), schedule="overlap"),
                             iters)
        dt_fused = _best_of(lambda: eng.run(stream(n), schedule="fused"),
                            iters)
        fused_dts[d] = dt_fused
        pre = f"nsai/{model}/scaling/d{d}"
        neg = (f"variant={variant} fused_eq={sched.fused_equivalence} "
               f"diff={'/'.join(sched.fused_lowering_diff) or 'none'}")
        rows += [
            (f"{pre}/staged_problems_s", n / dt_staged,
             f"{neg} dispatches_per_group={disp_staged:g}"),
            (f"{pre}/fused_problems_s", n / dt_fused,
             f"{neg} dispatches_per_group={disp_fused:g} "
             f"fallback_groups={fallbacks}"),
            (f"{pre}/fused_vs_staged/ratio", dt_staged / dt_fused,
             f"{neg} staged_K={disp_staged:g} fused_K={disp_fused:g}"),
        ]
    if len(dims) > 1:
        lo, hi = dims[0], dims[-1]
        rows.append((f"nsai/{model}/scaling/runtime_growth",
                     fused_dts[hi] / fused_dts[lo],
                     f"fused runtime d{lo}->d{hi} (scale x{hi / lo:g})"))
    return _stamp_backend(rows)


def bench_replicas(model: str, problems: int = 48, batch_size: int = 4,
                   d: int = 64, repl=(1, 2, 4), iters: int = 3):
    """Data-parallel replica sweep: problems/s at R engine replicas.

    Each point builds a ``ReplicaPool`` of R engines over the same
    constants — consts ``device_put`` round-robin over the (possibly
    faked) device pool, one depth-k in-flight window per replica — and
    serves the same pre-rendered request list offline through the pool
    protocol.  Rows record problems/s per R, the scaling ratio of the
    largest R against R=1, and a bitwise answer-equality flag (the
    pool's answers must be replica-count invariant).  Run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to give the
    replicas distinct devices; with fewer devices placement wraps and the
    sweep degenerates to measuring pool overhead.
    """
    import numpy as np

    from repro.configs import base as cbase
    from repro.serve.reason import ReasonConfig
    from repro.serve.replica import ReplicaPool

    entry = cbase.REASON_WORKLOADS[model]
    variant = "oracle" if "oracle" in entry.variants else entry.variants[0]
    cfg = entry.make_config(d=d)
    consts = entry.make_consts(cfg, jax.random.PRNGKey(0))
    ndev = jax.device_count()

    def requests(seed):
        factory, _ = entry.make_requests(cfg, problems, seed=seed)
        return list(factory())

    rows, answers, rates = [], {}, {}
    for r in repl:
        pool = cbase.reason_engine_pool(
            model, cfg,
            ReasonConfig(batch_size=batch_size, schedule="overlap",
                         variant=variant, max_inflight=2),
            consts=consts, variants=(variant,), replicas=r,
            trace_graph=False)
        if not isinstance(pool, ReplicaPool):
            pool = ReplicaPool([pool])
        # first pass compiles every replica's device cache (and is the
        # answer-invariance sample); timed passes reuse it
        res = pool.run(requests(seed=9900))
        answers[r] = {u: np.asarray(res[u].answer) for u in res}
        dt = _best_of(lambda: pool.run(requests(seed=9900)), iters)
        rates[r] = problems / dt
        split = " ".join(f"r{x['replica']}:{x['groups']}g"
                         for x in pool.per_replica())
        rows.append((f"nsai/{model}/replicas/r{r}/problems_s", rates[r],
                     f"devices={ndev} inflight=2x{r} groups={split}"))
    lo, hi = repl[0], repl[-1]
    same = all(
        np.array_equal(answers[lo][u], answers[r][u])
        for r in repl for u in answers[lo])
    rows.append((f"nsai/{model}/replicas/scaling_r{hi}_vs_r{lo}/ratio",
                 rates[hi] / rates[lo],
                 f"devices={ndev} answers_bitwise_equal={same}"))
    return _stamp_backend(rows)


def bench_load_sweep(model: str, problems: int = 24, batch_size: int = 4,
                     d: int = 64, loads=(0.5, 0.8, 1.2),
                     deadline_ms: float = 10.0):
    """Latency vs offered load through the online front-door.

    The engine's serving configuration (batch buckets, in-flight window
    depth) is DSE-derived from the workload's traced dataflow graph via
    ``core.dse.serving_plan`` — every row's ``derived`` field records the
    DSE point that served it.  Offered rates are fractions of the
    engine's *measured* offline overlapped throughput on this host, so
    the sweep spans under- and over-load on any machine.  Each point
    serves ``problems`` Poisson arrivals per schedule (the schedule knob
    is swept explicitly to keep the overlap-vs-sequential online
    comparison); every bucket's jit entry is compiled before timing, so
    warmup never lands in a latency percentile.
    """
    import dataclasses

    from repro.configs import base as cbase
    from repro.core import dse
    from repro.serve import frontdoor as fd
    from repro.serve.reason import ReasonConfig

    entry = cbase.REASON_WORKLOADS[model]
    cfg = entry.make_config(d=d)
    consts = entry.make_consts(cfg, jax.random.PRNGKey(0))
    # DSE-derived serving plan (generator -> architecture, as deploy() does)
    probe = cbase.compile_reason_schedule(
        model, cfg, variant=entry.variants[0], batch_size=batch_size,
        trace_graph=False)
    design, dse_tag = _dse_point(probe)
    plan = dse.serving_plan(design, max_batch=batch_size)
    buckets = plan.buckets
    eng = cbase.reason_engine(
        model, cfg,
        ReasonConfig(batch_size=plan.batch_size, buckets=buckets,
                     max_inflight=plan.max_inflight, schedule=plan.schedule),
        consts=consts, variants=(entry.variants[0],), trace_graph=False)
    # warm every bucket's jit entry (schedules share the same jit_stages,
    # so one pass covers overlap and sequential alike)
    for b in buckets:
        warm, _ = entry.make_requests(cfg, b, seed=7000 + b)
        eng.run(warm())

    factory, _ = entry.make_requests(cfg, problems, seed=8000)
    eng.run(factory())
    base_pps = eng.last_run["problems_per_s"]

    rows = []
    for frac in loads:
        rate = max(2.0, frac * base_pps)
        for sched in ("overlap", "sequential"):
            stream, _ = entry.make_requests(cfg, problems,
                                            seed=8100 + int(frac * 100))
            # sweep the schedule knob on the shared engine (jit caches live
            # on the StagedSchedules, so no recompilation)
            eng.cfg = dataclasses.replace(eng.cfg, schedule=sched)
            door = fd.FrontDoor(
                {model: eng},
                fd.FrontDoorConfig(deadline_s=deadline_ms / 1e3))
            rep = door.serve(fd.poisson_arrivals(model, stream(), rate,
                                                 seed=int(frac * 100)))
            q = rep.percentiles("queue_s", model)
            s = rep.percentiles("service_s", model)
            t = rep.percentiles("total_s", model)
            pre = f"nsai/{model}/frontdoor/{sched}/load_{frac:g}"
            # keep the derived column comma-free: rows print as 3-field CSV
            derived = (f"poisson {rate:.1f} req/s deadline={deadline_ms:g}ms "
                       f"buckets={'/'.join(map(str, buckets))} "
                       f"inflight={plan.max_inflight} {dse_tag}")
            hist = " ".join(f"{b}x{c}" for b, c in
                            rep.bucket_histogram(model).items())
            rows += [
                (f"{pre}/offered_rps", rate, derived),
                (f"{pre}/problems_s", rep.throughput_rps(model),
                 f"served={len(rep.latencies)} groups={hist} {dse_tag}"),
                (f"{pre}/queue_p50_ms", q["p50"] * 1e3, "arrival->dispatch"),
                (f"{pre}/queue_p95_ms", q["p95"] * 1e3, "arrival->dispatch"),
                (f"{pre}/queue_p99_ms", q["p99"] * 1e3, "arrival->dispatch"),
                (f"{pre}/service_p50_ms", s["p50"] * 1e3, "dispatch->done"),
                (f"{pre}/service_p95_ms", s["p95"] * 1e3, "dispatch->done"),
                (f"{pre}/service_p99_ms", s["p99"] * 1e3, "dispatch->done"),
                (f"{pre}/total_p99_ms", t["p99"] * 1e3, "arrival->done"),
            ]
    return _stamp_backend(rows)


def _emit(rows, json_path):
    print("name,value,derived")
    for name, val, derived in rows:
        print(f"{name},{val:.2f},{derived}")
    if json_path:
        json_path.parent.mkdir(parents=True, exist_ok=True)
        json_path.write_text(json.dumps(
            [{"name": n, "value": v, "derived": str(x)}
             for n, v, x in rows], indent=1))


def _scaling_main(args):
    dims = tuple(int(x) for x in args.dims.split(",") if x.strip())
    rows = bench_scaling(model=args.model, problems=args.problems,
                         batch_size=args.batch_size, dims=dims,
                         iters=args.iters)
    _emit(rows, args.json)
    if not args.check:
        return 0
    hi = dims[-1]
    key = f"nsai/{args.model}/scaling/d{hi}/fused_vs_staged/ratio"
    ratio = {n: v for n, v, _ in rows}[key]
    # Wall-clock gate: per-group dispatch savings are O(100us) while group
    # compute is O(ms), so run-to-run scheduler noise on shared CI runners
    # swamps a strict >= 1.0 comparison.  Remeasure once with a larger
    # sample, then fail only below a noise floor — a real fused-path
    # regression (per-group recompilation, fallback engaging) lands far
    # below it.  The deterministic claims (one dispatch per group, zero
    # fallbacks) are gated strictly below.
    noise_floor = 0.9
    if ratio < 1.0:
        print(f"scaling gate: {ratio:.3f}x < 1.0x at d={hi}, remeasuring "
              f"with {2 * args.problems} problems / best-of-{2 * args.iters}",
              file=sys.stderr)
        rows2 = bench_scaling(model=args.model, problems=2 * args.problems,
                              batch_size=args.batch_size, dims=dims,
                              iters=2 * args.iters)
        ratio = {n: v for n, v, _ in rows2}[key]
        rows = rows2
    if ratio < noise_floor:
        print(f"FAIL: {args.model} fused schedule slower than staged at "
              f"d={hi} beyond the {noise_floor:.0%} noise floor "
              f"({ratio:.3f}x)", file=sys.stderr)
        return 1
    fused_row = next(x for n, _, x in rows
                     if n == f"nsai/{args.model}/scaling/d{hi}"
                     f"/fused_problems_s")
    m = re.search(r"dispatches_per_group=([0-9.]+)", fused_row)
    if m is None or float(m.group(1)) != 1.0:
        print(f"FAIL: fused schedule at d={hi} did not serve one dispatch "
              f"per group ({fused_row})", file=sys.stderr)
        return 1
    m = re.search(r"fallback_groups=([0-9]+)", fused_row)
    if m is None or int(m.group(1)) != 0:
        print(f"FAIL: fused schedule at d={hi} fell back to staged "
              f"dispatch ({fused_row})", file=sys.stderr)
        return 1
    print(f"scaling gate OK ({args.model}): fused {ratio:.3f}x over staged "
          f"at d={hi}, one dispatch per group, no fallbacks")
    return 0


def _replicas_main(args):
    repl = tuple(int(x) for x in args.repl.split(",") if x.strip())
    rows = bench_replicas(model=args.model, problems=args.problems,
                          batch_size=args.batch_size, d=args.d, repl=repl,
                          iters=args.iters)
    _emit(rows, args.json)
    if not args.check:
        return 0
    lo, hi = repl[0], repl[-1]
    key = f"nsai/{args.model}/replicas/scaling_r{hi}_vs_r{lo}/ratio"

    def gate(rows):
        ratio = {n: v for n, v, _ in rows}[key]
        derived = next(x for n, _, x in rows if n == key)
        return ratio, "answers_bitwise_equal=True" in derived

    ratio, same = gate(rows)
    if not same:
        print(f"FAIL: {args.model} answers differ across replica counts "
              "(pooling must not change results)", file=sys.stderr)
        return 1
    # Throughput gate: R replicas on >= R devices must scale. Wall-clock
    # ratios on shared CI runners are noisy, so remeasure once with a
    # larger sample before failing — a real regression (replicas
    # serialized on one device, pool dispatch blocking) lands far below.
    target = 2.0
    if ratio < target:
        print(f"replica gate: {ratio:.2f}x < {target:g}x at r{hi}, "
              f"remeasuring with {2 * args.problems} problems / "
              f"best-of-{2 * args.iters}", file=sys.stderr)
        rows = bench_replicas(model=args.model, problems=2 * args.problems,
                              batch_size=args.batch_size, d=args.d,
                              repl=repl, iters=2 * args.iters)
        ratio, same = gate(rows)
    if not same:
        print(f"FAIL: {args.model} answers differ across replica counts "
              "(pooling must not change results)", file=sys.stderr)
        return 1
    if ratio < target:
        print(f"FAIL: {args.model} r{hi} throughput only {ratio:.2f}x of "
              f"r{lo} (gate {target:g}x on {jax.device_count()} devices)",
              file=sys.stderr)
        return 1
    print(f"replica gate OK ({args.model}): r{hi} {ratio:.2f}x over r{lo}, "
          "answers bit-identical")
    return 0


def main():
    from repro.configs import base as cbase

    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="nvsa",
                    choices=sorted(cbase.REASON_WORKLOADS))
    ap.add_argument("--problems", type=int, default=32)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--d", type=int, default=64,
                    help="VSA block dim; >=128 (pow2) engages the Pallas "
                         "circ_conv kernel (interpret mode off-TPU)")
    ap.add_argument("--iters", type=int, default=3, help="best-of timing")
    ap.add_argument("--json", type=pathlib.Path, default=None,
                    help="also write rows as JSON")
    ap.add_argument("--check-overlap", action="store_true",
                    help="exit 1 unless overlap beats sequential AND the "
                         "load sweep emitted p50/p95 latency rows")
    ap.add_argument("--loads", default="0.5,0.8,1.2",
                    help="offered-load sweep points as fractions of the "
                         "measured offline throughput")
    ap.add_argument("--deadline-ms", type=float, default=10.0,
                    help="front-door admission deadline")
    ap.add_argument("--no-sweep", action="store_true",
                    help="skip the latency-vs-offered-load sweep")
    ap.add_argument("--scaling", action="store_true",
                    help="run ONLY the symbolic-scaling sweep (fused vs "
                         "staged over --dims)")
    ap.add_argument("--dims", default="64,128",
                    help="VSA block dims for --scaling, ascending")
    ap.add_argument("--replicas", action="store_true",
                    help="run ONLY the data-parallel replica sweep "
                         "(problems/s at --repl engine replicas; fake "
                         "devices via XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=8)")
    ap.add_argument("--repl", default="1,2,4",
                    help="replica counts for --replicas, ascending")
    ap.add_argument("--check", action="store_true",
                    help="with --scaling: exit 1 unless at the largest dim "
                         "the fused schedule serves one dispatch per group "
                         "with zero fallbacks and stays within noise of "
                         "staged (ratio >= 0.9 after remeasure); with "
                         "--replicas: exit 1 unless answers are bit-equal "
                         "across replica counts and the largest R reaches "
                         "2x the R=1 rate (after remeasure)")
    args = ap.parse_args()
    from repro.common.util import enable_compile_cache

    enable_compile_cache()

    if args.scaling:
        return _scaling_main(args)
    if args.replicas:
        return _replicas_main(args)
    rows = bench_nsai(model=args.model, problems=args.problems,
                      batch_size=args.batch_size, d=args.d, iters=args.iters)
    if not args.no_sweep:
        loads = tuple(float(x) for x in args.loads.split(",") if x.strip())
        rows += bench_load_sweep(
            model=args.model, problems=min(args.problems, 24),
            batch_size=args.batch_size, d=args.d, loads=loads,
            deadline_ms=args.deadline_ms)
    _emit(rows, args.json)
    if args.check_overlap:
        key = f"nsai/{args.model}/overlap_vs_sequential/speedup"
        speedup = {n: v for n, v, _ in rows}[key]
        if speedup < 1.0:
            # wall-clock races on shared CI runners are noisy — re-measure
            # once with a larger sample before calling it a regression
            print(f"overlap gate: {speedup:.3f}x < 1.0x, remeasuring with "
                  f"{2 * args.problems} problems / best-of-{2 * args.iters}",
                  file=sys.stderr)
            rows2 = bench_nsai(model=args.model, problems=2 * args.problems,
                               batch_size=args.batch_size, d=args.d,
                               iters=2 * args.iters)
            speedup = {n: v for n, v, _ in rows2}[key]
        if speedup < 1.0:
            print(f"FAIL: {args.model} overlapped schedule slower than "
                  f"sequential ({speedup:.3f}x)", file=sys.stderr)
            return 1
        print(f"overlap gate OK ({args.model}): {speedup:.3f}x over "
              f"sequential")
        if not args.no_sweep:
            import math

            for p in ("queue_p50_ms", "queue_p95_ms", "queue_p99_ms",
                      "service_p50_ms", "service_p95_ms",
                      "service_p99_ms", "total_p99_ms"):
                vals = [v for n, v, _ in rows if n.endswith(p)]
                # NaN percentiles mean the front-door served nothing —
                # row names alone would pass vacuously
                if not vals or not all(math.isfinite(v) for v in vals):
                    print(f"FAIL: load sweep has no finite {p} rows "
                          f"(got {vals})", file=sys.stderr)
                    return 1
            print(f"latency sweep gate OK ({args.model}): finite "
                  f"p50/p95/p99 queue+service rows present")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
