"""Mixed LM + NSAI front-door benchmark: one admission layer, two classes.

Deploys an arbitrary mix of LM archs and NSAI workloads (``--models
stablelm-3b,nvsa``) through ``repro.serve.deploy`` — the NSAI engines'
serving knobs (batch buckets, in-flight depth, schedule) DSE-derived from
each workload's traced dataflow graph — and serves interleaved Poisson
arrival streams through ONE ``FrontDoor``.  Rows report, per model, the
class's own throughput unit (tokens/s for LM, problems/s for NSAI) plus
p50/p95 queueing and service latency out of the single shared
``FrontDoorReport``.

Run:  PYTHONPATH=src python benchmarks/bench_mixed.py
          [--models stablelm-3b,nvsa] [--requests 12] [--rate 4]
          [--json out.json] [--check]

``--check`` exits non-zero unless BOTH request classes are present in the
one report and every model's queue/service p50/p95 latencies are finite
(the CI gate for mixed serving).
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))


def bench_mixed(models, requests: int = 12, rate_rps: float = 4.0,
                deadline_ms: float = 20.0, max_pes: int = 4096,
                max_batch: int = 4, seed: int = 0,
                replicas: int | None = None):
    import jax

    from repro.serve import Budget, Traffic, deploy

    options = {m: {"d": 64} for m in models
               if deployment_class(m) == "reason"}
    deployment = deploy(
        models,
        traffic=Traffic(rate_rps=rate_rps, deadline_s=deadline_ms / 1e3),
        budget=Budget(max_pes=max_pes, max_batch=max_batch, max_slots=2,
                      max_len=64, max_new_tokens=8, replicas=replicas),
        options=options, seed=seed)
    for line in deployment.summary().splitlines():
        print(f"# deploy: {line}", file=sys.stderr)
    deployment.warmup()  # compile every serving shape before latencies
    arrivals, _ = deployment.synthetic_traffic(requests, seed=100 + seed)
    report = deployment.serve(arrivals)

    rows = []
    # every row records the device pool and the model's replica count, so
    # a BENCH measurement is attributable to the mesh it ran on
    ndev = jax.device_count()
    for m in models:
        design = deployment.designs[m]
        dse_tag = f"dse={design.tag()}" if design is not None else "dse=n/a"
        mesh_tag = (f"devices={ndev} "
                    f"replicas={deployment.replicas.get(m, 1)}")
        unit = report.work_unit(m)
        q = report.percentiles("queue_s", m)
        s = report.percentiles("service_s", m)
        t = report.percentiles("total_s", m)
        pre = f"serve/mixed/{m}"
        rows += [
            (f"{pre}/served", len(report.results[m]),
             f"class={deployment.classes[m]} {mesh_tag} {dse_tag}"),
            (f"{pre}/{'tok' if unit == 'tok' else 'problems'}_s",
             report.work_per_s(m), f"unit={unit} {mesh_tag} {dse_tag}"),
            (f"{pre}/queue_p50_ms", q["p50"] * 1e3,
             f"arrival->dispatch {mesh_tag}"),
            (f"{pre}/queue_p95_ms", q["p95"] * 1e3,
             f"arrival->dispatch {mesh_tag}"),
            (f"{pre}/queue_p99_ms", q["p99"] * 1e3,
             f"arrival->dispatch {mesh_tag}"),
            (f"{pre}/service_p50_ms", s["p50"] * 1e3,
             f"dispatch->done {mesh_tag}"),
            (f"{pre}/service_p95_ms", s["p95"] * 1e3,
             f"dispatch->done {mesh_tag}"),
            (f"{pre}/service_p99_ms", s["p99"] * 1e3,
             f"dispatch->done {mesh_tag}"),
            (f"{pre}/total_p99_ms", t["p99"] * 1e3,
             f"arrival->done {mesh_tag}"),
        ]
    return rows, report, deployment


def deployment_class(model: str) -> str:
    # same membership test deploy() itself uses (Deployment.classes is the
    # authoritative answer post-deploy; this is needed pre-deploy to build
    # the per-model options)
    from repro.configs.base import REASON_WORKLOADS

    return "reason" if model in REASON_WORKLOADS else "lm"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="stablelm-3b,nvsa",
                    help="comma list mixing LM archs and NSAI workloads")
    ap.add_argument("--requests", type=int, default=12,
                    help="Poisson arrivals per model")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="per-model offered load, req/s")
    ap.add_argument("--deadline-ms", type=float, default=20.0)
    ap.add_argument("--max-pes", type=int, default=4096)
    ap.add_argument("--replicas", type=int, default=None,
                    help="data-parallel engine replicas per model (default "
                         "1; fake devices via XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=N)")
    ap.add_argument("--json", type=pathlib.Path, default=None,
                    help="also write rows as JSON")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless both request classes report finite "
                         "p50/p95 latencies in the one FrontDoorReport")
    args = ap.parse_args()
    from repro.common.util import enable_compile_cache

    enable_compile_cache()

    models = [m.strip() for m in args.models.split(",") if m.strip()]
    rows, report, deployment = bench_mixed(
        models, requests=args.requests, rate_rps=args.rate,
        deadline_ms=args.deadline_ms, max_pes=args.max_pes,
        replicas=args.replicas)
    print("name,value,derived")
    for name, val, derived in rows:
        print(f"{name},{val:.2f},{derived}")
    if args.json:
        args.json.parent.mkdir(parents=True, exist_ok=True)
        args.json.write_text(json.dumps(
            [{"name": n, "value": v, "derived": str(x)}
             for n, v, x in rows], indent=1))
    if args.check:
        classes = {deployment.classes[m] for m in models}
        if classes != {"lm", "reason"}:
            print(f"FAIL: mixed gate needs both classes in one report, "
                  f"got {sorted(classes)}", file=sys.stderr)
            return 1
        vals = {n: v for n, v, _ in rows}
        for m in models:
            if not vals[f"serve/mixed/{m}/served"] == args.requests:
                print(f"FAIL: {m} served "
                      f"{vals[f'serve/mixed/{m}/served']:.0f} of "
                      f"{args.requests} requests", file=sys.stderr)
                return 1
            for p in ("queue_p50_ms", "queue_p95_ms", "queue_p99_ms",
                      "service_p50_ms", "service_p95_ms", "service_p99_ms",
                      "total_p99_ms"):
                v = vals[f"serve/mixed/{m}/{p}"]
                if not math.isfinite(v):
                    print(f"FAIL: {m} {p} is not finite ({v})",
                          file=sys.stderr)
                    return 1
        missing = [n for n, _, x in rows
                   if "devices=" not in x or "replicas=" not in x]
        if missing:
            print(f"FAIL: rows missing devices=/replicas= provenance: "
                  f"{missing}", file=sys.stderr)
            return 1
        print("mixed front-door gate OK: both request classes finite "
              f"p50/p95 ({','.join(models)}), devices/replicas recorded")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
