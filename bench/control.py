"""The control of a cell's check, at the cell's own size and load: for each
seed, the program's readings and the control's on the same requests (the
reference in the precision below the one the configuration states, put in
the program's place).  The limits in the configuration files are set from
these readings.

    python3 -m bench.control --workload <cell> --seconds <s> --seeds 1 2 3

Prints one JSON line per seed; the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import harness

    cell = harness.load(args.workload)
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = harness.run(cell, seed, args.seconds, trace=False, t0=t0,
                          control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "program": {k: v["value"] for k, v in
                                      out["checks"].items()},
                          "control": out["control"],
                          "metrics": out["metrics"],
                          "s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
