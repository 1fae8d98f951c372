"""Find a cell's knee once: set up the cell's system, then serve its
traffic mix at a list of offered rates, one window each, and print what was
served.  The knee is the highest rate served without a growing backlog;
the cells' traffic files fix their rates from it.

    python3 -m bench.sweep --workload <cell> --seconds <s> --rates 500 1000 2000

Prints one JSON line per rate.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from bench import harness

    cell = harness.load(args.workload)
    dev = harness.device(cell["chips"])
    harness.program_path()
    harness.compile_cache()
    import importlib

    system = importlib.import_module(
        f"bench.systems.{cell['config']['system']}").System(
            cell, args.seed, args.seconds)
    t0 = time.perf_counter()
    system.deploy()
    system.warmup()
    print(json.dumps({"device": dev, "setup_s": time.perf_counter() - t0}),
          flush=True)
    for rate in args.rates:
        system.mix = {**cell["traffic"], "rate_per_s": rate}
        system.arrivals = system._arrivals()
        w0 = time.perf_counter()
        report = system.serve()
        wall = time.perf_counter() - w0
        total = np.array([l.total_s for l in report.latencies]) * 1e3
        done = system.units_done(args.seconds)
        late = sum(1 for l in report.latencies if l.done_s > args.seconds)
        print(json.dumps({
            "offered_per_s": rate, "requests": len(system.arrivals),
            f"{system.unit}s_per_s": done / args.seconds,
            "requests_done_per_s": (len(report.latencies) - late)
            / args.seconds,
            "unfinished_at_close": late,
            "latency_p50_ms": float(np.percentile(total, 50)),
            "latency_p95_ms": float(np.percentile(total, 95)),
            "serve_wall_s": wall, "stats": system.stats}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
