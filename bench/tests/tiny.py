"""A checkout-shaped directory with a tiny cell, for running the harness on
the CPU: the same system, reference and readers at a size a test holds."""

from __future__ import annotations

import json
import pathlib

NVSA = {
    "model": "nvsa", "system": "reason", "variant": "oracle",
    "blocks": 4, "d": 32, "attr_sizes": [5, 6, 8],
    "rule_temp": 0.1, "answer_temp": 0.05,
    "deploy_options": {"d": 32, "variant": "oracle"},
    "limits": {"logprob_gap": 1.5e-5},
}

RAVEN = {"kind": "raven", "payload": "attrs", "pool": 16, "rate_per_s": 40.0}


def root(tmp: pathlib.Path) -> pathlib.Path:
    """Write the tiny checkout under ``tmp`` and return it."""
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir(parents=True)
    (tmp / "bench" / "configs" / "nvsa.json").write_text(json.dumps(NVSA))
    (tmp / "bench" / "traffic" / "raven.json").write_text(json.dumps(RAVEN))
    spec = {
        "configs": [{"name": "nvsa", "file": "bench/configs/nvsa.json"}],
        "workloads": [{"name": "nvsa.raven", "config": "nvsa",
                       "traffic": "raven", "chips": 1}],
        "end_to_end": [
            {"name": "problems_per_s", "unit": "problems/s"},
            {"name": "latency_p50_ms", "unit": "ms"},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "dispatches_per_group", "unit": "dispatches/group",
             "moves": "latency_p50_ms"}],
    }
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
