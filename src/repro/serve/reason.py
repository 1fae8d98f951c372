"""Workload-generic NSAI serving: N-stage pipelines with host/device overlap.

NSFlow's workload characterization (paper Sec III) is that NSAI inference
is a *heterogeneous pipeline* of nn / vsa / simd streams.  ``ReasonEngine``
is the generic executor for that shape of traffic: it runs any
:class:`~repro.serve.schedule.StagedSchedule` — an ordered list of jitted
stage callables compiled from the workload's dataflow graph by
``serve.schedule.compile_schedule`` — and contains **no workload-specific
stage logic**.  NVSA, PrAE, MIMONet and LVRF all serve through schedules
contributed by the registry in ``configs.base.REASON_WORKLOADS``; adding a
workload means declaring stages + a graph builder there, not forking the
engine.

Admission groups flow through the compiled N-stage software pipeline with
a configurable in-flight window (``ReasonConfig.max_inflight`` dispatched-
but-undrained groups resident at once; 1 = double buffering), so group
*i*'s device work overlaps group *i+k*'s host work:

    device:  S₁⁰..S₁ᴺ S₂⁰..S₂ᴺ S₃⁰.. ...       (async queue, never idle)
    host:     stage₂     stage₃     ...         (a window ahead)
              collect₀   collect₁  ...

Every host-side step — ingesting the next group from the request stream
(which may be a lazy generator: rendering/preprocessing then runs inside
the pipeline), staging device arrays, and converting finished answers back
to numpy — runs while the device works through the in-flight window, so
none of it sits on the critical path.  A group is dispatched in one of two
ways, and both are asynchronous: its *entire* pipeline is enqueued on the
device before the engine blocks on anything, and only then is the window
trimmed back to ``max_inflight`` by draining the oldest group
(``jax.block_until_ready`` happens solely at drain).

- ``overlap``: one jit call per stage (K dispatches a group).
- ``fused``: the whole pipeline as **one** jit call
  (``StagedSchedule.jit_fused``) where the schedule's fused variant was
  negotiated bit-identical to the staged one (``fused_ok``); otherwise the
  group is dispatched stage by stage and counted under
  ``stats["fused_fallback_groups"]``.

The engine implements the unified :class:`~repro.serve.runtime.
EngineProtocol` natively — its workload constants (params / codebooks /
binding keys) are bound at construction, so callers schedule traffic, not
model state.  Two entry points:

- ``run(requests)`` — the offline loop: admit fixed-size groups from an
  iterable and serve them all (benchmarks, tests, batch jobs).  It is
  literally a loop over the group-level API below.
- ``submit(group)`` / ``drain_ready()`` / ``drain_all()`` — the
  group-level protocol the **online front-door** (``serve.frontdoor``)
  drives: it forms admission groups by its batch-full-or-deadline policy
  and dispatches each as it closes, with per-group dispatch/done
  timestamps returned as :class:`~repro.serve.runtime.GroupRecord`\\ s and
  finished answers collected from the drain calls (``{uid: result}``).

A partial group is padded to the smallest *covering bucket* of the
schedule's compiled batch sizes (``StagedSchedule.batch_buckets``), not to
the maximum — a 3-request group on a (1, 2, 4, 8)-bucket schedule runs at
batch 4, paying one row of padding instead of five.

Stats are split so jit warmup cannot pollute throughput numbers: a run
that compiles anything (first time a (variant, bucket) shape is executed)
is accounted under ``stats["warmup"]``, steady-state runs under
``stats["measured"]`` (which ``problems_per_s`` reports), and per-run
records append to ``engine.runs``.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import time
from typing import Iterable, Mapping

import jax
import numpy as np

from repro.serve import runtime as rt
from repro.serve.runtime import GroupRecord  # re-export (envelope lives there)
from repro.serve.schedule import StagedSchedule

SCHEDULES = ("overlap", "fused")


@dataclasses.dataclass
class ReasonConfig:
    batch_size: int = 4           # max problems per admission group
    schedule: str = "overlap"     # overlap | fused
    # Which compiled variant of the workload to run (e.g. "cnn" = neural
    # perception, "oracle" = ground-truth PMFs / symbolic-stream-only).
    # None = the first variant the engine was constructed with.
    variant: str | None = None
    # Depth of the in-flight window: dispatched-but-undrained groups
    # resident at once before the executor blocks on the oldest.
    # 1 = double buffering (one group on the device while the host stages
    # the next).
    max_inflight: int = 1
    # Compiled batch-size buckets, ascending (None = (batch_size,)): a
    # partial admission group pads to the smallest covering bucket.  Used
    # at schedule-compile time by ``configs.base.reason_engine``.
    buckets: tuple[int, ...] | None = None


@dataclasses.dataclass
class ReasonRequest:
    uid: int
    # RAVEN reasoning traffic (nvsa / prae / lvrf)
    context: np.ndarray | None = None          # (8, H, W, 1) float32
    candidates: np.ndarray | None = None       # (8, H, W, 1) float32
    context_attrs: np.ndarray | None = None    # (8, A) int32 — oracle variant
    candidate_attrs: np.ndarray | None = None  # (8, A) int32
    # superposed-classification traffic (mimonet)
    images: np.ndarray | None = None           # (K, H, W, 1) float32
    # traffic class for overload control (see serve.slo.PRIORITIES);
    # the engine ignores it — the front-door sheds and orders by it
    priority: str = "standard"


@dataclasses.dataclass
class ReasonResult:
    uid: int
    # argmax over candidates (int) or per-channel argmax (np.ndarray)
    answer: int | np.ndarray
    answer_logprobs: np.ndarray
    batch: int                    # pipeline group index that served it
    # workload extras (e.g. per-attribute rule posteriors); None if N/A
    rule_posteriors: np.ndarray | None = None


# GroupRecord note: ``dispatch_t`` is stamped right before the group's
# pipeline is enqueued on the device, and the *whole* pipeline is enqueued
# before the engine blocks on anything — so arrival→dispatch is pure
# queueing (the front-door's admission wait) and dispatch→done is service,
# matching the documented semantics in ``serve.runtime``/``serve.frontdoor``.
# Window backpressure (draining the oldest group once ``max_inflight`` is
# exceeded) happens strictly *after* the new dispatch, while the new group
# is already computing, so it can never inflate the new group's service
# latency.  (Earlier revisions drained mid-pipeline at the schedule's
# ``drain_stage``, which charged the window wait to service whenever
# ``drain_stage > 0``; ``drain_stage`` no longer gates dispatch.)


def _fresh_stats() -> dict:
    return {
        "requests": 0, "batches": 0,
        # device dispatches (jit calls): K per staged group, 1 per fused
        "dispatches": 0,
        # groups served by the single fused jit vs groups that asked for
        # "fused" but fell back per-stage (schedule not negotiated exact)
        "fused_groups": 0, "fused_fallback_groups": 0,
        # wall-time split: runs that compiled a new (variant, bucket)
        # shape land in "warmup", steady-state runs in "measured"
        # (``work`` == requests for reasoning traffic: one problem each)
        **rt.fresh_split_stats(),
    }


class ReasonEngine:
    """Generic N-stage pipelined executor over StagedSchedules.

    ``schedules`` maps variant name -> compiled :class:`StagedSchedule`
    (a single schedule is accepted too).  Stage jit caches live on the
    schedules, so sharing schedules across engines shares compilations.
    ``consts`` is the workload's constant pytree (params / codebooks /
    binding keys) handed to every stage — bound here so the engine
    implements the consts-free :class:`~repro.serve.runtime.
    EngineProtocol` (``configs.base.reason_engine`` binds it for you).
    ``run(requests)`` feeds every request batch through the schedule's
    stages, one call per stage (``overlap``) or one fused call
    (``fused``).  ``clock`` is the timestamp source for
    :class:`~repro.serve.runtime.GroupRecord`\\ s and the engine's layer
    spans (the front-door injects its own so queue/service latencies share
    one origin); ``wall`` is the real wall-clock the throughput accounting
    reads — separate so a virtual front-door clock never distorts measured
    rates, injectable so the accounting itself is testable.  ``device`` is
    where staged inputs land (None = the default device): a replica passes
    the device its ``consts`` live on, so its groups never route through
    device 0.
    """

    def __init__(self, schedules: StagedSchedule | Mapping[str, StagedSchedule],
                 cfg: ReasonConfig, consts=None, clock=time.perf_counter,
                 wall=time.perf_counter, device=None):
        if isinstance(schedules, StagedSchedule):
            schedules = {schedules.variant: schedules}
        if not schedules:
            raise ValueError("engine needs at least one compiled schedule")
        if cfg.schedule not in SCHEDULES:
            raise ValueError(f"unknown schedule {cfg.schedule!r}")
        if cfg.batch_size < 1:
            raise ValueError("batch_size must be >= 1")
        if cfg.max_inflight < 1:
            raise ValueError("max_inflight must be >= 1")
        for s in schedules.values():
            if s.batch_buckets and s.batch_buckets[-1] < cfg.batch_size:
                raise ValueError(
                    f"{s.workload}/{s.variant}: largest compiled bucket "
                    f"{s.batch_buckets[-1]} < batch_size {cfg.batch_size} — "
                    "admission groups would not fit any bucket")
        self.schedules = dict(schedules)
        self.default_variant = cfg.variant or next(iter(self.schedules))
        if self.default_variant not in self.schedules:
            raise ValueError(f"unknown variant {self.default_variant!r}; "
                             f"compiled: {sorted(self.schedules)}")
        self.cfg = cfg
        self.consts = consts
        self.device = device
        self.clock = clock
        self.wall = wall
        self.stats = _fresh_stats()
        self.runs: list[dict] = []    # per-run records from run()
        self._inflight: collections.deque = collections.deque()
        self._ready: dict[int, ReasonResult] = {}  # collected, undrained
        self._next_index = 0
        # (variant, bucket, mode) shapes already compiled (mode: the fused
        # jit and the staged jits have separate caches)
        self._warmed: set[tuple[str, int, str]] = set()
        self._cold_run = False
        self._in_run = False          # run() accounts at run level instead
        self._last_acct = float("-inf")  # busy-window edge for group stats

    @property
    def admission_cap(self) -> int:
        """Largest admission group ``submit`` accepts (protocol surface)."""
        return self.cfg.batch_size

    # -- host-side staging --------------------------------------------------

    def _resolve(self, variant: str | None):
        variant = variant or self.default_variant
        if variant not in self.schedules:
            raise ValueError(f"unknown variant {variant!r}; "
                             f"compiled: {sorted(self.schedules)}")
        return variant, self.schedules[variant]

    def _ingest(self, req: ReasonRequest, sched: StagedSchedule):
        try:
            return sched.ingest(req)
        except (ValueError, AttributeError, TypeError) as e:
            raise ValueError(
                f"request {req.uid}: cannot ingest for workload "
                f"{sched.workload!r} variant {sched.variant!r}: {e}") from e

    def _stage(self, batch: list[ReasonRequest], sched: StagedSchedule):
        """Stack one admission group and pad to its covering bucket.

        Padding replicates the last request so a group of any size hits a
        compiled jit cache entry; padded rows are computed and dropped at
        collect.  Bucketed schedules pad to the smallest compiled batch
        size that fits; bucket-less schedules keep the single
        ``batch_size`` shape.  Returns ``(device_bufs, bucket)``.
        """
        trees = [self._ingest(r, sched) for r in batch]
        bucket = sched.covering_bucket(len(batch)) if sched.batch_buckets \
            else self.cfg.batch_size
        pad = bucket - len(batch)

        def stack(*leaves):
            x = np.stack(leaves)
            if pad:
                x = np.concatenate([x, np.repeat(x[-1:], pad, axis=0)])
            return jax.device_put(x, self.device)

        return jax.tree.map(stack, *trees), bucket

    def _collect(self, batch: list[ReasonRequest], out,
                 rec: GroupRecord, sched: StagedSchedule,
                 cold: bool = False, t0: float | None = None):
        """Materialize one group's answers on the host (blocks if pending).

        Finished results land in the engine's ready buffer until a drain
        call hands them out.  Outside ``run()`` (the protocol path the
        front-door drives) the group is accounted into the warmup/measured
        split here, keyed off its own cold flag: wall time is the union of
        per-group busy windows ([dispatch, collect] on the real clock,
        clipped so overlapping windows are not double-counted), so
        ``problems_per_s()`` reports a real measured rate for engines that
        never see ``run()``.

        The block on the outputs is its own ``reason.wait`` span, ahead of
        the copy (``reason.collect``), so the wait is not hidden in
        ``np.asarray``."""
        with rt.Span("reason.wait", self.clock, rec, "wait_s",
                     group=rec.index):
            jax.block_until_ready(out)
        with rt.Span("reason.collect", self.clock, rec, "collect_s",
                     group=rec.index):
            host = jax.tree.map(np.asarray, out)
            for i, req in enumerate(batch):  # padded rows have no request
                fields = sched.collect(host, i)
                self._ready[req.uid] = ReasonResult(
                    uid=req.uid, batch=rec.index, **fields)
        rec.done_t = self.clock()
        self.stats["requests"] += len(batch)
        if not self._in_run and t0 is not None:
            now = self.wall()
            kind = "warmup" if cold else "measured"
            self.stats[kind]["requests"] += len(batch)
            self.stats[kind]["work"] += len(batch)
            self.stats[kind]["wall_time_s"] += max(
                0.0, now - max(t0, self._last_acct))
            self._last_acct = now

    def _batches(self, requests: Iterable[ReasonRequest]):
        """Pull admission groups lazily — a generator's per-request work
        (rendering, preprocessing) runs inside the pipeline."""
        it = iter(requests)
        seen: set = set()
        while True:
            batch = list(itertools.islice(it, self.cfg.batch_size))
            if not batch:
                return
            for req in batch:
                if req.uid in seen:
                    raise ValueError(f"duplicate request uid {req.uid} "
                                     "(results are keyed by uid)")
                seen.add(req.uid)
            yield batch

    # -- group-level API (the front-door drives these) ----------------------

    def submit(self, group: list[ReasonRequest],
               variant: str | None = None) -> GroupRecord:
        """Dispatch one admission group through the compiled pipeline.

        The group's whole pipeline is enqueued asynchronously and the
        returned :class:`GroupRecord` has ``done_t=None``: under
        ``overlap`` one jit call per stage, under ``fused`` one call of
        the composed pipeline when the schedule negotiated its fused
        variant substitutable (``fused_ok``), per-stage calls otherwise.
        Only after the dispatch is the in-flight window trimmed back to
        ``cfg.max_inflight`` by draining the oldest group — its record
        (already returned by the earlier ``submit``) gets ``done_t``
        stamped in place, and its answers wait in the ready buffer for the
        next ``drain_*`` call.
        """
        consts = self.consts
        if consts is None:
            raise ValueError(
                "engine has no consts bound — pass consts= to ReasonEngine "
                "(configs.base.reason_engine binds them for you)")
        variant, sched = self._resolve(variant)
        if not group:
            raise ValueError("empty admission group")
        if len(group) > self.cfg.batch_size:
            raise ValueError(f"admission group of {len(group)} exceeds "
                             f"batch_size {self.cfg.batch_size}")
        pending = {u for g, *_ in self._inflight for u in (r.uid for r in g)}
        seen: set = set()
        for req in group:
            if req.uid in self._ready or req.uid in pending \
                    or req.uid in seen:
                raise ValueError(f"duplicate request uid {req.uid} "
                                 "(results are keyed by uid)")
            seen.add(req.uid)
        index = self._next_index
        with rt.Span("reason.stage", self.clock, group=index) as staging:
            bufs, bucket = self._stage(group, sched)
        use_fused = False
        if self.cfg.schedule == "fused":
            if sched.fused_ok:
                use_fused = True
            else:
                # fused variant exists but was negotiated only
                # epsilon-equivalent (or was not compiled): serve the group
                # stage-by-stage so answers stay bit-identical
                self.stats["fused_fallback_groups"] += 1
        mode = "fused" if use_fused else "staged"
        cold = (variant, bucket, mode) not in self._warmed
        if cold:
            self._warmed.add((variant, bucket, mode))
            self._cold_run = True
        rec = GroupRecord(uids=tuple(r.uid for r in group), index=index,
                          variant=variant, bucket=bucket, size=len(group))
        self._next_index += 1
        t0 = self.wall()
        # dispatch the whole pipeline asynchronously FIRST; the window
        # trimming happens after, so group i+1 is always on the device
        # before the engine waits on group i.
        # Queueing ends where staging does: the rest is service
        rec.dispatch_t = staging.end
        if use_fused:
            with rt.Span("reason.enqueue", self.clock, rec, "enqueue_s",
                         group=index, stage="fused"):
                bufs = sched.jit_fused(consts, bufs)
            self.stats["dispatches"] += 1
            self.stats["fused_groups"] += 1
        else:
            for spec, fn in zip(sched.stages, sched.jit_stages):
                with rt.Span("reason.enqueue", self.clock, rec, "enqueue_s",
                             group=index, stage=spec.name):
                    bufs = fn(consts, bufs)
                self.stats["dispatches"] += 1
        self.stats["batches"] += 1
        self._inflight.append((group, bufs, rec, sched, cold, t0))
        # window backpressure: trim back down to max_inflight by draining
        # the oldest group(s) — strictly after the new dispatch, so this
        # wait is never the new group's service time
        while len(self._inflight) > self.cfg.max_inflight:
            self._drain_one()
        return rec

    def _drain_one(self) -> GroupRecord | None:
        if not self._inflight:
            return None
        group, bufs, rec, sched, cold, t0 = self._inflight.popleft()
        self._collect(group, bufs, rec, sched, cold=cold, t0=t0)
        return rec

    def _take_ready(self) -> dict[int, "ReasonResult"]:
        out, self._ready = self._ready, {}
        return out

    def drain_all(self) -> dict[int, "ReasonResult"]:
        """Drain every in-flight group, oldest first (blocking), and
        return all finished results ``{uid: ReasonResult}``."""
        while self._inflight:
            self._drain_one()
        return self._take_ready()

    @staticmethod
    def _leaf_ready(leaf) -> bool:
        """Conservative readiness probe for one buffer leaf.

        jax Arrays expose ``is_ready()``; host-side data (numpy / python
        scalars) is ready by definition.  Anything else — including
        donated-buffer surrogates a fused pipeline may leave behind —
        reports *not ready*, so ``drain_ready`` stays non-blocking instead
        of vacuously passing and then blocking inside ``_collect``."""
        probe = getattr(leaf, "is_ready", None)
        if probe is not None:
            return bool(probe())
        return isinstance(leaf, (np.ndarray, np.generic,
                                 int, float, bool, complex))

    def drain_ready(self) -> dict[int, "ReasonResult"]:
        """Collect in-flight groups whose device buffers have already
        materialized — non-blocking, oldest first (the front-door calls
        this while it would otherwise sleep waiting for traffic) — and
        return every finished result ``{uid: ReasonResult}``."""
        while self._inflight:
            _, bufs, _, _, _, _ = self._inflight[0]
            if not all(self._leaf_ready(l) for l in jax.tree.leaves(bufs)):
                break
            self._drain_one()
        return self._take_ready()

    @property
    def inflight(self) -> int:
        """Dispatched-but-undrained admission groups."""
        return len(self._inflight)

    @property
    def accepting(self) -> bool:
        """True while ``submit`` would dispatch without blocking on the
        depth-k in-flight window — the backpressure signal the
        front-door's overload path defers group closes on."""
        return len(self._inflight) < self.cfg.max_inflight

    # -- the offline loop ---------------------------------------------------

    def run(self, requests: Iterable[ReasonRequest],
            variant: str | None = None) -> dict[int, "ReasonResult"]:
        """Serve all requests; returns {uid: ReasonResult}.

        The offline loop over the group-level protocol: ingest/stage the
        next group while the device runs the in-flight window, dispatch
        its pipeline asynchronously (``submit``), then drain; host work
        never blocks the device.  ``variant`` overrides the config's per
        call.

        Appends a per-run record to ``self.runs`` ({schedule, variant,
        requests, wall_time_s, warmup, problems_per_s}); runs that
        jit-compiled a new (variant, bucket) shape are flagged ``warmup``
        and excluded from the cumulative measured stats that
        ``problems_per_s()`` reports.
        """
        variant, _ = self._resolve(variant)
        if self._inflight or self._ready:
            raise ValueError("engine has undrained in-flight groups "
                             "(call drain_all first)")
        self._cold_run = False
        self._in_run = True   # account at run level, not per group
        t_start = self.wall()
        try:
            for batch in self._batches(requests):
                # staging the next group (incl. any lazy per-request
                # preprocessing in the `requests` iterable) overlaps the
                # in-flight window on the device
                self.submit(batch, variant=variant)
            results = self.drain_all()
        finally:
            self._in_run = False
        dt = self.wall() - t_start
        kind = "warmup" if self._cold_run else "measured"
        self.stats[kind]["requests"] += len(results)
        self.stats[kind]["work"] += len(results)
        self.stats[kind]["wall_time_s"] += dt
        self.runs.append({
            "schedule": self.cfg.schedule, "variant": variant,
            "requests": len(results), "wall_time_s": dt,
            "warmup": self._cold_run,
            "problems_per_s": len(results) / dt if dt else 0.0,
        })
        return results

    @property
    def last_run(self) -> dict | None:
        """Per-run stats record of the most recent ``run()``."""
        return self.runs[-1] if self.runs else None

    def problems_per_s(self) -> float:
        """Measured steady-state throughput — warmup runs (the ones that
        jit-compiled a new shape) are excluded; ``stats["warmup"]`` keeps
        their totals separately, and only-warmup stats fall back to the
        all-runs number (see :func:`repro.serve.runtime.measured_rate`;
        ``work`` == requests for reasoning traffic)."""
        return rt.measured_rate(self.stats)

    def reset_stats(self):
        """Zero the cumulative stats and per-run records (jit caches and
        the warmed-shape set survive — compilations are not forgotten)."""
        self.stats = _fresh_stats()
        self.runs = []


def requests_from_batch(batch: dict, start_uid: int = 0
                        ) -> list[ReasonRequest]:
    """Adapt one ``data.raven.generate_batch`` dict into requests."""
    n = len(batch["answer"])
    return [ReasonRequest(
        uid=start_uid + i,
        context=batch["context"][i], candidates=batch["candidates"][i],
        context_attrs=batch["context_attrs"][i],
        candidate_attrs=batch["candidate_attrs"][i]) for i in range(n)]
