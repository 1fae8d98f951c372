"""Serving benchmark: fused-scan continuous batching vs the seed lockstep
loop (one XLA dispatch per token), on a 4-request llama-smoke batch.

Reports tokens/s for both engines plus slot utilization for a ragged
8-request / 4-slot run that exercises admission-on-retirement.
"""

from __future__ import annotations

import time

import jax
import numpy as np


def _serve_setup(max_len: int = 64):
    from repro.configs import ARCHS
    from repro.configs import base as cbase
    from repro.nn import init as nninit

    arch = ARCHS["llama3.2-3b"]
    cfg = arch.make_smoke()
    params = nninit.materialize(cbase.model_spec(arch, cfg),
                                jax.random.PRNGKey(0))
    step, init_caches = cbase.serve_fns(arch, cfg, max_len=max_len)
    return cfg, params, step, init_caches


def bench_serve():
    from repro.serve.engine import Engine, LockstepEngine, Request, ServeConfig

    cfg, params, step, init_caches = _serve_setup()
    rows = []
    new, n_req, plen = 32, 4, 12
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, (n_req, plen)).astype(np.int32)
    scfg = ServeConfig(max_new_tokens=new, max_slots=n_req, max_len=64,
                       decode_block=8)

    def _best_of(fn, iters=5):
        dts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            out = fn()
            dts.append(time.perf_counter() - t0)
        return out, min(dts)

    # seed-style lockstep: one dispatch per token
    lockstep = LockstepEngine(step, init_caches, scfg)
    lockstep.generate(params, prompts)  # warm up compile
    ref, dt_lock = _best_of(lambda: lockstep.generate(params, prompts))
    rows.append(("serve/lockstep_4x32/tok_s", n_req * new / dt_lock,
                 f"dispatches={new}"))

    # fused scan blocks (params bound: the engine implements the runtime
    # protocol; generate/run no longer take params)
    engine = Engine(step, init_caches, scfg, params=params)
    engine.generate(prompts)  # warm up compile
    out, dt_fused = _best_of(lambda: engine.generate(prompts))
    assert np.array_equal(out, ref), "fused decode diverged from lockstep"
    rows.append(("serve/fused_scan_4x32/tok_s", n_req * new / dt_fused,
                 f"dispatches={-(-new // scfg.decode_block)}"))
    rows.append(("serve/fused_vs_lockstep/speedup", dt_lock / dt_fused,
                 f"block={scfg.decode_block}"))

    # continuous batching: ragged 8-request queue through the 4-slot pool
    rng = np.random.default_rng(1)
    cb = Engine(step, init_caches, scfg, params=params)
    reqs = [Request(uid=i, prompt=rng.integers(
        0, cfg.vocab, (int(rng.integers(4, 16)),)).astype(np.int32),
        max_new_tokens=int(rng.integers(8, new))) for i in range(8)]
    cb.run([Request(uid=99, prompt=reqs[0].prompt, max_new_tokens=4)])
    cb.stats.update(slot_steps=0, active_slot_steps=0)  # warm-up off the books
    t0 = time.perf_counter()
    results = cb.run(reqs)
    dt = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in results.values())
    rows.append(("serve/continuous_8req_4slot/tok_s", toks / dt,
                 f"utilization={cb.utilization():.2f}"))
    return rows


if __name__ == "__main__":
    import argparse
    import json
    import pathlib

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", type=pathlib.Path, default=None,
                    help="also write rows as JSON")
    args = ap.parse_args()
    from repro.common.util import enable_compile_cache

    enable_compile_cache()
    rows = bench_serve()
    print("name,value,derived")
    for name, val, derived in rows:
        print(f"{name},{val:.2f},{derived}")
    if args.json:
        args.json.parent.mkdir(parents=True, exist_ok=True)
        args.json.write_text(json.dumps(
            [{"name": n, "value": v, "derived": str(d)}
             for n, v, d in rows], indent=1))
