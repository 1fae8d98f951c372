"""Serving neuro-symbolic reasoning traffic — the NSFlow pipeline demo.

Every workload in ``configs.base.REASON_WORKLOADS`` serves through the SAME
generic engine: its pipeline is compiled from declared stage functions into
a ``StagedSchedule`` (``serve.schedule``), and ``ReasonEngine`` executes
the schedule asynchronously so host ingest/staging of batch i+1 overlaps
batch i on the device.

Part 1: NVSA RAVEN serving — a lazy request stream flows through the
        compiled two-stage pipeline (ResNet frontend -> attribute PMFs;
        FPE codes -> VSA rule abduction -> circ-conv rule execution),
        staged (``overlap``, one dispatch per stage) vs ``fused`` (the
        whole pipeline as one dispatch) on the same compiled schedule.
Part 2: symbolic-stream-only serving (oracle variant) — accuracy 1.0 on
        unambiguous RAVEN grids by construction.
Part 3: every registered workload through the same path — PrAE (PMF-table
        symbolic stream), MIMONet (bind -> shared NN trunk -> unbind/
        classify, K inputs per request), LVRF (learned-rule posterior ->
        posterior-weighted execution) — the model list derives from the
        registry, so a new workload shows up here by registration alone.
Part 4: Tab. IV mixed precision on NVSA (nn int8 through the Pallas
        qmatmul kernel, symbolic int4) behind the same engine.
Part 5: ONLINE mixed serving via ``repro.serve.deploy`` — an LM arch
        (stablelm-3b) and two NSAI workloads (nvsa + mimonet) deployed
        behind ONE deadline-batched, shape-bucketed front-door under
        Poisson arrivals.  The NSAI engines' serving knobs (batch
        buckets, in-flight depth) are DSE-derived from each workload's traced dataflow graph — the
        paper's generator -> architecture loop — and the report covers
        both request classes (tokens/s for LM rows, problems/s for NSAI
        rows) with per-request queue/service latency percentiles.

Run:  PYTHONPATH=src python examples/serve_reason.py
"""

import dataclasses
import time

import jax

from repro.configs import base as cbase
from repro.models import nvsa
from repro.serve.reason import ReasonConfig, ReasonEngine

D = 64          # VSA block dim; >= 128 (pow2) would engage the Pallas kernel
N_PROBLEMS = 16
BATCH = 4


def main():
    entry = cbase.REASON_WORKLOADS["nvsa"]
    cfg = entry.make_config(d=D)
    consts = entry.make_consts(cfg, jax.random.PRNGKey(0))
    engine = cbase.reason_engine("nvsa", cfg, ReasonConfig(batch_size=BATCH),
                                 consts=consts)

    # Part 1 — compiled NVSA pipeline, staged vs fused: a second engine
    # over the same compiled schedules (jit caches live on them)
    print(f"[serve_reason] nvsa pipeline: "
          f"{engine.schedules['cnn'].describe()}")
    fused = ReasonEngine(engine.schedules,
                         dataclasses.replace(engine.cfg, schedule="fused"),
                         consts=consts)
    stream, truth = entry.make_requests(cfg, N_PROBLEMS, seed=100)
    warm, _ = entry.make_requests(cfg, BATCH, seed=0)
    for eng in (engine, fused):
        eng.run(warm())  # warm up compile
        t0 = time.time()
        res = eng.run(stream())
        dt = time.time() - t0
        print(f"[serve_reason] nvsa/{eng.cfg.schedule}: {N_PROBLEMS} "
              f"problems in {dt:.2f}s, {eng.stats['dispatches']} "
              f"dispatches for {eng.stats['batches']} groups")
    first = res[0]
    print(f"[serve_reason]   e.g. uid 0 (batch {first.batch}): answer "
          f"panel {first.answer}, logp {first.answer_logprobs.round(2)}")

    # Part 2 — symbolic stream only: oracle variant, accuracy 1.0
    res = engine.run(stream(), variant="oracle")
    print(f"[serve_reason] oracle variant (symbolic stream only): "
          f"accuracy {entry.score(res, truth()):.3f}")

    # Part 3 — every registered workload through the same generic engine
    for model, e in cbase.REASON_WORKLOADS.items():
        if model == "nvsa":
            continue
        mcfg = e.make_config(d=D)
        mconsts = e.make_consts(mcfg, jax.random.PRNGKey(0))
        variant = "oracle" if "oracle" in e.variants else e.variants[0]
        eng = cbase.reason_engine(model, mcfg, ReasonConfig(batch_size=BATCH),
                                  consts=mconsts, variants=(variant,))
        mstream, mtruth = e.make_requests(mcfg, N_PROBLEMS, seed=100)
        t0 = time.time()
        res = eng.run(mstream())
        dt = time.time() - t0
        print(f"[serve_reason] {model}/{variant}: "
              f"{eng.schedules[variant].describe()}")
        print(f"[serve_reason]   {N_PROBLEMS} problems in {dt:.2f}s "
              f"({N_PROBLEMS / dt:.1f} problems/s), accuracy "
              f"{e.score(res, mtruth()):.3f}")

    # Part 4 — Tab. IV mixed precision on the same engine API
    mp_cfg = entry.make_config(d=D, nn_precision="int8",
                               symb_precision="int4")
    mp_eng = cbase.reason_engine("nvsa", mp_cfg,
                                 ReasonConfig(batch_size=BATCH),
                                 consts=consts, variants=("cnn",))
    t0 = time.time()
    mp_eng.run(stream())
    print(f"[serve_reason] mixed precision nn=int8(qmatmul)/symb=int4: "
          f"{N_PROBLEMS} problems in {time.time() - t0:.2f}s (memory "
          f"{nvsa.nvsa_memory_bytes(cfg, consts['params']) / nvsa.nvsa_memory_bytes(mp_cfg, consts['params']):.1f}x smaller)")

    # Part 5 — online mixed LM + NSAI serving through deploy(): the DSE
    # reads each NSAI workload's traced dataflow graph and emits the
    # serving configuration; one front-door admits both request classes
    from repro.serve import Budget, Traffic, deploy

    deployment = deploy(
        ["stablelm-3b", "nvsa", "mimonet"],
        traffic=Traffic(rate_rps=40.0, deadline_s=0.02),
        budget=Budget(max_pes=4096, max_batch=BATCH, max_slots=2,
                      max_len=64, max_new_tokens=8),
        options={"nvsa": {"d": D}, "mimonet": {"d": D}})
    for line in deployment.summary().splitlines():
        print(f"[serve_reason] deploy: {line}")
    deployment.warmup()  # compile every serving shape before latencies
    arrivals, _ = deployment.synthetic_traffic(N_PROBLEMS)
    report = deployment.serve(arrivals)
    print(f"[serve_reason] front-door: poisson 40 req/s per model, "
          f"deadline 20ms — one report, both request classes:")
    for line in report.summary().splitlines():
        print(f"[serve_reason]   {line}")


if __name__ == "__main__":
    main()
