"""Chip smoke test: the serving main path, end to end, on a TPU.

    python chip_smoke.py             # one chip: nvsa, mimonet, stablelm-3b
    python chip_smoke.py --chips 4   # four one-chip nvsa replicas vs one

Every phase goes through the calls a user makes — ``deploy()``, then
``Deployment.warmup()``, then Poisson arrivals through ``Deployment.serve``
(the ``FrontDoor``) into the engines and their Pallas kernels — and checks
what comes out against the model's plain reference:

- ``nvsa`` (cnn variant, 4 x 256 block codes) against the offline
  ``nvsa.solve``, run under the all-``xla`` plan at highest matmul
  precision;
- ``mimonet`` (fused schedule, which runs the ``unbind_classify`` kernel)
  against its offline ``forward``, likewise;
- ``stablelm-3b`` at its published widths against ``LockstepEngine``, the
  one-dispatch-per-token decode loop, on the same chip with the same
  params;
- with ``--chips 4``, only the replica path: four one-chip ``nvsa``
  replicas against one, on the same arrivals.

Every kernel selection the lowering registry makes inside a phase must be
the compiled ``pallas`` lowering.  Times printed here are smoke timings,
not benchmark numbers.  The last line of standard output is one JSON
object, printed only when every phase passed on a TPU; otherwise the
script exits non-zero.  The phase functions take their sizes as
arguments, so a CPU test can run them small under the interpret plan;
only :func:`main` insists on the chip.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent


# Greedy picks of two differently compiled bf16 decode loops may differ
# where the top two logits are within 4 bf16 ulps (2**-7) of the top one.
TIE_TOL_REL = 4 * 2.0 ** -7


def _log(phase: str, msg: str):
    print(f"[{phase}] {msg}", flush=True)


def _reference_scope():
    """The plain reference: every kernel on its ``xla`` lowering, matmuls
    at highest precision, as served stages trace."""
    import jax

    from repro.backend import registry

    stack = contextlib.ExitStack()
    stack.enter_context(registry.use_plan(registry.negotiate(override="xla")))
    stack.enter_context(jax.default_matmul_precision("highest"))
    return stack


def _fresh(fn):
    """A jit of ``fn`` that shares no trace with any other.  JAX caches
    traces by function, argument shapes and config, not by the lowering
    plan, so the reference gets a new function object to trace instead of
    one traced under the served plan."""
    import jax

    return jax.jit(lambda *args: fn(*args))


def _epsilon(selections) -> float:
    """The largest declared epsilon among the lowerings a phase selected."""
    from repro.backend import registry

    return max((registry.KERNELS[k].by_name(low).epsilon
                for k, low in selections), default=0.0)


def compare_logprobs(served, ref, eps: float) -> dict:
    """Served vs reference log-probabilities over the last axis.

    An answer (the argmax) may differ from the reference's only where the
    reference's top-2 margin is within ``eps``; the log-probabilities must
    agree within ``eps`` absolute plus ``eps`` relative, the registry's
    conformance rule.
    """
    import numpy as np

    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    top2 = np.sort(ref, axis=-1)[..., -2:]
    margin = top2[..., 1] - top2[..., 0]
    differ = served.argmax(-1) != ref.argmax(-1)
    return {
        "max_dev": float(np.max(np.abs(served - ref))),
        "eps": eps,
        "within_eps": bool(np.all(np.abs(served - ref)
                                  <= eps + eps * np.abs(ref))),
        "answers": int(differ.size),
        "answers_differ": int(differ.sum()),
        "answers_differ_outside_ties": int((differ & (margin > eps)).sum()),
    }


def _phase_check(out: dict) -> list[str]:
    """Failures of a comparison record."""
    fails = []
    if out["answers_differ_outside_ties"]:
        fails.append(f"{out['answers_differ_outside_ties']} answers differ "
                     "from the reference outside a top-2 tie")
    if not out["within_eps"]:
        fails.append(f"logprob deviation {out['max_dev']:.3g} exceeds "
                     f"eps {out['eps']:.3g}")
    return fails


def _serve_nsai(model: str, options: dict, requests: int, rate_rps: float,
                seed: int, max_batch: int, replicas: int | None = None):
    """deploy -> warmup -> Poisson arrivals through the front door.

    Returns (deployment, arrivals, report, setup_s, serve_s, selections).
    """
    from repro.backend import registry
    from repro.serve import Budget, Traffic, deploy

    with registry.record_selections() as sel:
        t0 = time.perf_counter()
        dep = deploy([model], traffic=Traffic(rate_rps=rate_rps),
                     budget=Budget(max_batch=max_batch, replicas=replicas),
                     options={model: options}, seed=seed)
        dep.warmup()
        setup_s = time.perf_counter() - t0
        arrivals, _ = dep.synthetic_traffic(requests, seed=seed + 100)
        arrivals = list(arrivals)
        t0 = time.perf_counter()
        report = dep.serve(arrivals)
        serve_s = time.perf_counter() - t0
    return dep, arrivals, report, setup_s, serve_s, sorted(set(sel))


def nvsa_phase(d: int = 256, requests: int = 32, rate_rps: float = 20.0,
               max_batch: int = 8, seed: int = 0) -> dict:
    """NVSA, cnn variant, at block dim ``d`` through the front door,
    checked against the offline ``nvsa.solve`` reference."""
    import jax.numpy as jnp
    import numpy as np

    from repro.models import nvsa as nv

    dep, arrivals, report, setup_s, serve_s, sel = _serve_nsai(
        "nvsa", {"d": d}, requests, rate_rps, seed, max_batch)
    cfg, consts = dep.configs["nvsa"], dep._base("nvsa").consts
    served = report.results["nvsa"]
    reqs = [a.request for a in arrivals]
    ctx = jnp.asarray(np.stack([r.context for r in reqs]))
    cand = jnp.asarray(np.stack([r.candidates for r in reqs]))

    def solve(p, b, x, y):
        return nv.solve.__wrapped__(p, b, cfg, x, y)

    with _reference_scope():
        ref, _ = _fresh(solve)(consts["params"], consts["books"], ctx, cand)
    logp = np.stack([served[r.uid].answer_logprobs for r in reqs])
    out = compare_logprobs(logp, np.asarray(ref), _epsilon(sel))
    out.update(selections=sel, setup_s=setup_s, serve_s=serve_s,
               served=len(served), plan=dep.backend.tag(),
               schedule=dep._base("nvsa").cfg.schedule,
               groups=len(report.groups))
    out["failures"] = _phase_check(out)
    if len(served) != requests:
        out["failures"].append(f"served {len(served)} of {requests}")
    return out


def mimonet_phase(d: int | None = None, requests: int = 16,
                  rate_rps: float = 20.0, max_batch: int = 8,
                  seed: int = 0) -> dict:
    """MIMONet on the fused schedule (the ``unbind_classify`` kernel) at
    its registry ``d`` unless given, checked against its offline
    ``forward``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import mimonet as mm

    opts = {"schedule": "fused", **({"d": d} if d is not None else {})}
    dep, arrivals, report, setup_s, serve_s, sel = _serve_nsai(
        "mimonet", opts, requests, rate_rps, seed, max_batch)
    cfg, consts = dep.configs["mimonet"], dep._base("mimonet").consts
    served = report.results["mimonet"]
    reqs = [a.request for a in arrivals]
    images = jnp.asarray(np.stack([r.images for r in reqs]))
    with _reference_scope():
        logits = _fresh(lambda p, k, x: mm.forward.__wrapped__(
            p, k, cfg, x))(consts["params"], consts["keys"], images)
        ref = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    out = compare_logprobs(
        np.stack([served[r.uid].answer_logprobs for r in reqs]), ref,
        _epsilon(sel))
    stats = dep.engines["mimonet"].stats
    out.update(selections=sel, setup_s=setup_s, serve_s=serve_s,
               served=len(served), plan=dep.backend.tag(), d=cfg.d,
               fused_groups=stats["fused_groups"],
               fused_fallback_groups=stats["fused_fallback_groups"])
    out["failures"] = _phase_check(out)
    if stats["fused_fallback_groups"] or not stats["fused_groups"]:
        out["failures"].append(
            f"fused schedule served {stats['fused_groups']} groups fused, "
            f"{stats['fused_fallback_groups']} fell back to staged")
    if len(served) != requests:
        out["failures"].append(f"served {len(served)} of {requests}")
    return out


def lm_phase(arch: str = "stablelm-3b", size: str = "full",
             requests: int = 4, rate_rps: float = 20.0,
             seed: int = 0) -> dict:
    """Greedy decode of ``arch`` at ``size`` widths through the front
    door, token-for-token against ``LockstepEngine`` with the same params
    on the same device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.backend import registry
    from repro.configs import ARCHS
    from repro.configs import base as cbase
    from repro.serve import Traffic, deploy
    from repro.serve.engine import LockstepEngine, ServeConfig

    with registry.record_selections() as sel:
        t0 = time.perf_counter()
        dep = deploy([arch], traffic=Traffic(rate_rps=rate_rps),
                     options={arch: {"size": size}}, seed=seed)
        dep.warmup()
        setup_s = time.perf_counter() - t0
        arrivals = list(dep.synthetic_traffic(requests, seed=seed + 100)[0])
        t0 = time.perf_counter()
        report = dep.serve(arrivals)
        serve_s = time.perf_counter() - t0
    eng, cfg = dep._base(arch), dep.configs[arch]
    reqs = [a.request for a in arrivals]
    step, init = cbase.serve_fns(ARCHS[arch], cfg, max_len=eng.cfg.max_len)
    lock = LockstepEngine(step, init, ServeConfig(
        max_new_tokens=eng.cfg.max_new_tokens))
    ref = lock.generate(eng.params, np.stack([r.prompt for r in reqs]))
    served = report.results[arch]
    # the two loops compile differently, so bf16 rounding may flip a
    # greedy pick where the top two logits tie: at the first divergence
    # the served token must be the reference's runner-up within the tie
    # tolerance, and the streams are not compared past it
    diverged = []
    for i, r in enumerate(reqs):
        got = served[r.uid].tokens
        k = next((j for j in range(len(ref[i])) if got[j] != ref[i][j]),
                 None)
        if k is None:
            continue
        prefix = np.concatenate([r.prompt, ref[i][:k]])[None]
        _, z = lock._prefill(eng.params, init(1), jnp.asarray(prefix))
        z = np.asarray(z[0], np.float32)
        second, first = np.argsort(z)[-2:]
        diverged.append({
            "uid": r.uid, "step": k, "served": int(got[k]),
            "ref": int(ref[i][k]), "margin": float(z[first] - z[second]),
            "tol": float(TIE_TOL_REL * abs(z[first])),
            "served_is_runner_up": int(got[k]) == int(second)})
    untied = [dv["uid"] for dv in diverged
              if not dv["served_is_runner_up"] or dv["margin"] > dv["tol"]]
    n_params = cbase.param_count(ARCHS[arch], cfg)
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.params))
    kv_bytes = sum(x.nbytes for x in jax.tree.leaves(eng._caches))
    out = {"selections": sorted(set(sel)), "setup_s": setup_s,
           "serve_s": serve_s, "served": len(served),
           "tokens": int(sum(len(r.tokens) for r in served.values())),
           "n_params": n_params, "param_bytes": param_bytes,
           "kv_bytes": kv_bytes, "diverged_at_tie": diverged,
           "failures": []}
    if untied:
        out["failures"].append(f"tokens differ from LockstepEngine outside "
                               f"a top-2 tie for uids {untied}")
    if len(served) != requests:
        out["failures"].append(f"served {len(served)} of {requests}")
    return out


def _devices_of(tree) -> set:
    import jax

    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


def replica_phase(d: int = 256, requests: int = 32, replicas: int = 4,
                  rate_rps: float = 20.0, max_batch: int = 4,
                  seed: int = 0) -> dict:
    """``replicas`` one-device nvsa replicas against one replica on the
    same arrivals: equal answers, every replica's arrays on its own
    device, groups over every replica.  Each replica compiles for its
    own device, so the batch ceiling is kept at 4 (buckets 1, 2, 4)."""
    import jax
    import numpy as np

    *_, rep1, _, _, sel1 = _serve_nsai("nvsa", {"d": d}, requests, rate_rps,
                                       seed, max_batch)
    dep, arrivals, repn, setup_s, serve_s, seln = _serve_nsai(
        "nvsa", {"d": d}, requests, rate_rps, seed, max_batch,
        replicas=replicas)
    res1, resn = rep1.results["nvsa"], repn.results["nvsa"]
    uids = sorted(set(res1) & set(resn))
    differ = [u for u in uids
              if not np.array_equal(res1[u].answer, resn[u].answer)]
    dev = max((float(np.max(np.abs(res1[u].answer_logprobs
                                   - resn[u].answer_logprobs)))
               for u in uids), default=0.0)
    pool = dep.engines["nvsa"]
    devs = jax.devices()
    placement, groups = [], [r["groups"] for r in pool.per_replica()]
    group = [a.request for a in arrivals[:2]]
    for i, sub in enumerate(pool.replicas):
        want = {devs[i % len(devs)]}
        # the staging and the compiled pipeline each submit() runs
        sched = sub.schedules[sub.default_variant]
        staged, _ = sub._stage(group, sched)
        out_bufs = staged
        for fn in ((sched.jit_fused,) if sub.cfg.schedule == "fused"
                   else sched.jit_stages):
            out_bufs = fn(sub.consts, out_bufs)
        placement.append({
            "replica": i, "device": str(devs[i % len(devs)]),
            "consts": _devices_of(sub.consts) == want,
            "inputs": _devices_of(staged) == want,
            "outputs": _devices_of(out_bufs) == want})
    out = {"selections": sorted(set(sel1) | set(seln)),
           "served": (len(res1), len(resn)), "answers_differ": differ,
           "max_logprob_dev": dev, "groups_per_replica": groups,
           "placement": placement, "setup_s": setup_s, "serve_s": serve_s,
           "failures": []}
    if differ or len(res1) != requests or set(res1) != set(resn):
        out["failures"].append(f"replica answers differ for uids {differ}")
    if not all(p["consts"] and p["inputs"] and p["outputs"]
               for p in placement):
        out["failures"].append(f"arrays off their replica's device: "
                               f"{placement}")
    if len(devs) >= replicas and not all(groups):
        out["failures"].append(f"groups did not reach every replica: "
                               f"{groups}")
    return out


def check_selections(name: str, out: dict, want: str,
                     require_kernels: bool) -> None:
    """Every registry selection of the phase must be ``want``."""
    lows = {low for _, low in out["selections"]}
    if lows - {want}:
        out["failures"].append(f"selections off the {want} lowering: "
                               f"{out['selections']}")
    if require_kernels and not out["selections"]:
        out["failures"].append("no kernel selection was recorded")


def _report(name: str, out: dict):
    for k, v in out.items():
        if k != "failures":
            _log(name, f"{k}: {v}")
    for f in out["failures"]:
        _log(name, f"FAIL {f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-replica nvsa path")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devs)}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.common.util import enable_compile_cache

    _log("setup", f"device {platform} {devs[0].device_kind} x{len(devs)}; "
                  f"compile cache {enable_compile_cache()}; every *_s time "
                  "below is smoke timing, not a benchmark")
    if args.chips == 4:
        phases = [("replicas", replica_phase, True)]
    else:
        phases = [("nvsa", nvsa_phase, True),
                  ("mimonet", mimonet_phase, True),
                  ("stablelm-3b", lm_phase, False)]
    failed = []
    for name, fn, kernels in phases:
        t0 = time.perf_counter()
        try:
            out = fn()
        except Exception as e:  # noqa: BLE001 — report the phase, go on
            import traceback

            traceback.print_exc()
            out = {"selections": [], "failures": [f"{type(e).__name__}: {e}"]}
        check_selections(name, out, "pallas", kernels)
        out["phase_s"] = time.perf_counter() - t0
        _report(name, out)
        if out["failures"]:
            failed.append(name)
    stats = devs[0].memory_stats() or {}
    _log("memory", f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
                   f"of bytes_limit {stats.get('bytes_limit')}")
    if failed:
        print(f"chip_smoke: FAILED phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
