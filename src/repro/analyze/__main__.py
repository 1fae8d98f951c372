"""Preflight CLI: ``python -m repro.analyze [--workload all] [--format json]``.

Runs the *full* static-analysis matrix: every requested NSAI workload ×
variant × backend plan is compiled (abstract — no params materialize)
across the declared batch buckets, then checked for precision flow,
host round-trips, donation, retrace hazards (including double-trace
determinism), registry consistency (including empirical kernel probes),
dispatch floors, and the serving-source AST lint.  Exit code 0 iff no
error-severity finding survives; warnings never fail the run.

The CI ``static-analysis`` leg runs ``--workload all --format json`` and
uploads the findings JSON as an artifact.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPRO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build_subjects(models, d, buckets, plan_names, log):
    from repro.backend import registry
    from repro.configs.base import (REASON_WORKLOADS,
                                    compile_reason_schedule)

    subjects = []
    for model in models:
        entry = REASON_WORKLOADS[model]
        cfg = entry.make_config(d=d)
        for variant in entry.variants:
            for plan_name in plan_names:
                override = "" if plan_name == "negotiated" else plan_name
                plan = registry.negotiate(override=override)
                log(f"compiling {model}/{variant} under "
                    f"{plan.tag()} (buckets {buckets})")
                sched = compile_reason_schedule(
                    model, cfg, variant, batch_size=buckets,
                    trace_graph=False, plan=plan)
                subjects.append((sched, cfg, entry, variant))
    return subjects


def main(argv=None) -> int:
    from repro.analyze.preflight import preflight

    p = argparse.ArgumentParser(
        prog="python -m repro.analyze",
        description="Preflight static analysis over the serving stack")
    p.add_argument("--workload", default="all",
                   help="comma list of NSAI workloads, or 'all' "
                        "(default), or 'none' for lint+registry only")
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.add_argument("--out", default=None,
                   help="also write the JSON findings to this path")
    p.add_argument("--d", type=int, default=32,
                   help="block dim for the compiled configs (default 32)")
    p.add_argument("--buckets", default="1,2,4",
                   help="batch-size buckets to compile (default 1,2,4)")
    p.add_argument("--plans", default="negotiated,xla,interpret",
                   help="backend plans to compile each schedule under")
    p.add_argument("--lint-root", default=_REPRO_ROOT,
                   help="source tree for the AST lint (default: the "
                        "repro package)")
    p.add_argument("--no-probe", action="store_true",
                   help="skip the empirical kernel probes")
    p.add_argument("--no-double-trace", action="store_true",
                   help="skip the double-trace determinism proof")
    args = p.parse_args(argv)

    def log(msg):
        if args.format == "text":
            print(f"[analyze] {msg}", file=sys.stderr)

    from repro.configs.base import REASON_WORKLOADS

    if args.workload == "all":
        models = list(REASON_WORKLOADS)
    elif args.workload == "none":
        models = []
    else:
        models = [m.strip() for m in args.workload.split(",") if m.strip()]
        unknown = [m for m in models if m not in REASON_WORKLOADS]
        if unknown:
            p.error(f"unknown workload(s) {unknown}; "
                    f"available: {tuple(REASON_WORKLOADS)}")
    buckets = tuple(int(b) for b in args.buckets.split(","))
    plan_names = [s.strip() for s in args.plans.split(",") if s.strip()]

    subjects = _build_subjects(models, args.d, buckets, plan_names, log)
    report = preflight(subjects, lint_root=args.lint_root,
                       probe=not args.no_probe,
                       double_trace=not args.no_double_trace)

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(report.to_json(indent=2))
    if args.format == "json":
        print(report.to_json(indent=2))
    else:
        print(report.render())
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
