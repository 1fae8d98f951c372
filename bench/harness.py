"""One run of one cell: set up, serve the window, check, report.

Everything a cell is made of is found by name: its entry in
``BENCHMARK.json``, its configuration file, its traffic mix
``bench/traffic/<traffic>.json``, the system that drives it
``bench/systems/<config["system"]>.py`` and one reader per metric
``bench/metrics/<metric>.py``.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import os
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
# seconds of the window a traced run traces, centred in the window
TRACE_S = 4.0


class NoChip(RuntimeError):
    """The accelerator the cell asks for is not there."""


def load(workload: str, root: pathlib.Path = ROOT) -> dict:
    """The cell named ``workload``: its configuration, traffic mix and the
    metrics it reports."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no cell {workload!r}; cells: {sorted(cells)}")
    cell = cells[workload]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    return {"name": workload, "chips": cell["chips"],
            "config": json.loads((root / conf["file"]).read_text()),
            "traffic": json.loads(
                (root / "bench" / "traffic" / f"{cell['traffic']}.json")
                .read_text()),
            "end_to_end": e2e, "per_layer": layer}


def program_path(root: pathlib.Path = ROOT) -> None:
    """Put the program (``src/``) on the import path."""
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def device(chips: int, require_tpu: bool = True) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devs)}")
    if require_tpu:
        from bench import peaks

        peaks.peaks(devs[0].device_kind)   # an unknown chip is an error
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def compile_cache(root: pathlib.Path = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program in it, so only a checkout's first run compiles."""
    import jax

    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts compilations (and persistent-cache loads) while active."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, *_args, **_kw):
        if self.active and event in self.EVENTS:
            self.count += 1


def reader(name: str):
    """The ``read(ctx)`` function of metric ``name``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics(specs: list[dict], ctx: dict) -> dict:
    """Each metric's reader over ``ctx``; a reader that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in specs:
        value = reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(cell: dict, seed: int, seconds: float, trace: bool,
        t0: float | None = None, require_tpu: bool = True,
        after_setup=None, trace_keep: str | None = None,
        control: bool = False) -> dict:
    """One run; returns the result object (``correct`` ... ``checks``).

    ``after_setup(system)`` is called between set-up and the window (the
    fault tests plant their faults there); ``trace_keep`` names a file to
    keep the traced run's ``.xplane.pb`` in; ``control`` also puts the
    control's answers to the same requests through the same check
    (``bench.control``; never in a benchmark run)."""
    t0 = time.perf_counter() if t0 is None else t0
    dev = device(cell["chips"], require_tpu)
    program_path()
    if require_tpu:
        compile_cache()
    import jax

    system = importlib.import_module(
        f"bench.systems.{cell['config']['system']}").System(cell, seed,
                                                           seconds)
    spans = {}
    s0 = time.perf_counter()
    system.deploy()
    spans["deploy"] = time.perf_counter() - s0
    s0 = time.perf_counter()
    system.warmup()
    spans["warmup"] = time.perf_counter() - s0
    setup_s = time.perf_counter() - t0
    if after_setup is not None:
        after_setup(system)
    # what set-up made (the arrival schedule above all) is kept for the
    # whole run: out of the collector's way, so its pauses in the window
    # scan only what the window makes
    gc.collect()
    gc.freeze()

    counter = CompileCounter()
    tracer = None
    if trace:
        from bench import trace as tr

        length = min(TRACE_S, seconds)
        tracer = tr.Tracer(tempfile.mkdtemp(prefix="bench-trace-"),
                           start_s=(seconds - length) / 2, length_s=length)
    counter.active = True
    w0 = time.perf_counter()
    if tracer is not None:
        system.clock = tracer.clock(w0)
    report = system.serve()
    if tracer is not None:
        tracer.stop()
        system.clock = time.perf_counter
    spans["serve"] = time.perf_counter() - w0
    counter.active = False

    stats = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    from bench import peaks

    ctx = {"seconds": seconds, "setup_s": setup_s, "report": report,
           "model": system.model, "system": system, "trace": None,
           "peaks": peaks.PEAKS.get(dev["kind"]), **system.context()}
    result_metrics = metrics(cell["end_to_end"], ctx) if not trace else {}
    breakdown = None
    if trace:
        summary = tracer.reduce(w0, keep=trace_keep)
        ctx["trace"] = summary
        dev["busy_s"] = summary["busy_s"]
        dev["window_s"] = summary["window_s"]
        result_metrics = metrics(cell["per_layer"], ctx)
        breakdown = {"device_ops": summary["top_ops"][:10],
                     "idle_gaps": summary["idle_gaps"][:10]}

    gc.unfreeze()
    system.release()
    attempted, failed, checks = system.check()
    correct = all(v <= lim for v, lim in checks.values())
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": result_metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control:
        _, c_failed, c_checks = system.check(system.control())
        out["control"] = {
            "correct": all(v <= lim for v, lim in c_checks.values()),
            "failed": c_failed,
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in c_checks.items()}}
    out["compiles_in_window"] = counter.count
    out["spans_s"] = spans
    out["served_plan"] = ctx.get("served_plan")
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def main(args, t0: float) -> int:
    cell = load(args.workload)
    try:
        out = run(cell, args.seed, float(args.seconds), bool(args.trace),
                  t0=t0)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(f"compiles in the window: {out['compiles_in_window']}",
          file=sys.stderr)
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out))
    return 0
