"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (one row per reported quantity) and
writes results/bench_output.json.  A section that raises is reported as an
ERROR row, the remaining sections still run, and the run exits non-zero.
"""

from __future__ import annotations

import json
import pathlib
import sys
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from benchmarks.bench_tables import (bench_fig1_characterization,
                                     bench_fig5_runtime, bench_fig6_ablation,
                                     bench_tab2_searchspace,
                                     bench_tab3_configs, bench_tab4_precision)
from benchmarks.bench_kernels import bench_kernels
from benchmarks.bench_nsai import bench_nsai
from benchmarks.bench_roofline import bench_roofline
from benchmarks.bench_serve import bench_serve

SECTIONS = [
    ("tab2_searchspace", bench_tab2_searchspace),
    ("tab3_design_configs", bench_tab3_configs),
    ("tab4_mixed_precision", bench_tab4_precision),
    ("fig1_characterization", bench_fig1_characterization),
    ("fig5_runtime_vs_baselines", bench_fig5_runtime),
    ("fig6_scalability_ablation", bench_fig6_ablation),
    ("kernels_microbench", bench_kernels),
    ("roofline_from_dryrun", bench_roofline),
    ("serve_continuous_batching", bench_serve),
    ("serve_nsai_reasoning", bench_nsai),
]


def main() -> int:
    from repro.common.util import enable_compile_cache

    enable_compile_cache()
    all_rows = []
    failed = []
    print("name,us_per_call,derived")
    for section, fn in SECTIONS:
        t0 = time.perf_counter()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001 — report, run the rest, fail
            traceback.print_exc()
            failed.append(section)
            rows = [(f"{section}/ERROR", 0.0, f"{type(e).__name__}: {e}")]
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
            all_rows.append({"name": name, "us_per_call": us,
                             "derived": str(derived)})
        dt = time.perf_counter() - t0
        print(f"# section {section} done in {dt:.1f}s", flush=True)
    out = pathlib.Path(__file__).resolve().parents[1] / "results" / "bench_output.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(all_rows, indent=1))
    if failed:
        print(f"# FAILED sections: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
