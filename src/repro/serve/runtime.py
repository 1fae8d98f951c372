"""The unified serving runtime: one engine protocol for every traffic class.

NSFlow's framing (paper Sec III) is that LM-style neural inference and
neuro-symbolic reasoning are *one* serving problem with heterogeneous
compute streams, not two products.  Before this module the repo had two
disjoint serving APIs — the slot-pool LM :class:`~repro.serve.engine.Engine`
(batch-level ``run()``) and the staged-pipeline
:class:`~repro.serve.reason.ReasonEngine` (``submit``/``drain``) — so the
online front-door could only multiplex NSAI engines.  ``EngineProtocol``
is the single runtime surface both engines now implement natively:

- ``submit(group) -> GroupRecord`` — dispatch one admission group.  The
  engine owns its constants (LM params / NSAI consts are bound at
  construction), so callers schedule *traffic*, not model state.
- ``drain_ready() -> {uid: result}`` — non-blocking: collect whatever has
  already finished (and, for engines that need host pumping like the LM
  slot pool, advance bounded work — one decode block per call).
- ``drain_all() -> {uid: result}`` — run the engine's in-flight window to
  completion and collect everything.
- ``inflight`` — dispatched-but-undrained admission groups.
- ``admission_cap`` — the largest group ``submit`` accepts (NSAI: the
  config batch size; LM: the slot-pool size).
- ``stats`` / ``runs`` — warmup-split accounting: wall time of runs that
  jit-compiled a new shape lands under ``stats["warmup"]``, steady-state
  runs under ``stats["measured"]`` (see :func:`fresh_split_stats`), with
  per-run records appended to ``engine.runs``.
- ``clock`` — timestamp source for :class:`GroupRecord` stamps; the
  front-door points every engine at one clock so queue/service latencies
  share an origin.

Layer spans (:class:`Span`) mark the serving path's boundaries —
``frontdoor.admit`` / ``frontdoor.close`` / ``frontdoor.poll`` in the
front-door, ``reason.stage`` / ``reason.enqueue`` / ``reason.wait`` /
``reason.collect`` in the NSAI engine.  Each is recorded from one call
site on the record the front-door returns (a stamp or a summed field, on
the injected clock) and in the profiler's trace, as an annotation of the
same name carrying ``group=<GroupRecord.index>``.

The *request/result envelope* is structural, not nominal: any request
object with a ``uid`` (``serve.engine.Request``, ``serve.reason.
ReasonRequest``) and any result with a ``uid`` plus its payload
(``tokens`` for LM, ``answer``/``answer_logprobs`` for NSAI) flow through
the same front-door.  :func:`work_units` maps a result to its throughput
unit — generated tokens for LM rows, one problem for NSAI rows — which is
how one :class:`~repro.serve.frontdoor.FrontDoorReport` reports tokens/s
and problems/s side by side.

``TRAFFIC_CLASSES`` is the runtime registry the launcher derives its
``--workload`` / ``--models`` choices from; ``repro.serve.deploy`` builds
protocol engines for any mix of entries and closes the paper's
generator -> architecture loop (``core.dse.explore`` output configures the
serving runtime).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterable, Mapping, Protocol, Sequence, \
    runtime_checkable

from jax.profiler import TraceAnnotation as _Annotation


@dataclasses.dataclass
class GroupRecord:
    """Provenance + timing of one dispatched admission group.

    ``dispatch_t`` is stamped (engine clock) when the group's first work is
    enqueued on the device — the staged pipeline's first stage, or the LM
    engine's prefill of the group's first admitted request.  ``done_t``
    stays None until every request of the group has its answer
    materialized on the host, so arrival -> dispatch is queueing and
    dispatch -> done is service.  ``bucket`` is the compiled batch shape
    the group ran at (NSAI: the covering batch bucket; LM: the slot-pool
    width the decode batch is compiled for).

    ``enqueue_s`` / ``wait_s`` / ``collect_s`` split the service time by
    layer span (engine clock, summed over the group's spans): the host's
    dispatch of each stage (``reason.enqueue``), the host blocked on the
    device (``reason.wait``) and the copy back and unpack
    (``reason.collect``).  Engines without those spans leave them 0.
    """

    uids: tuple[int, ...]
    index: int                    # engine-lifetime group counter
    variant: str
    bucket: int                   # compiled batch size the group ran at
    size: int                     # real requests in the group (<= bucket)
    dispatch_t: float | None = None
    done_t: float | None = None
    # which replica of a ReplicaPool served the group (None = the engine
    # is not pooled); stamped by ``serve.replica.ReplicaPool.submit``
    replica: int | None = None
    enqueue_s: float = 0.0
    wait_s: float = 0.0
    collect_s: float = 0.0


class Span:
    """One layer span of the serving path, recorded from one call site in
    two places: on a record, and in the profiler's trace.

    On entry it opens a ``jax.profiler.TraceAnnotation`` named ``name``
    with ``args`` as its stats (``group=<GroupRecord.index>``,
    ``stage=...``), so a traced window shows the span on the ``/host:CPU``
    plane on the device trace's clock, and reads ``clock`` (``start``, a
    stamp).  On exit it reads ``clock`` again (``end``, a stamp, and
    ``elapsed``) and, given a ``record``, adds ``elapsed`` to its
    ``field``.  ``annotate`` adds stats known only inside the span (the
    front-door learns a group's index from ``submit``).  With the profiler
    off no annotation is made: a span then costs its two clock reads and
    the ``with`` statement.
    """

    __slots__ = ("name", "clock", "record", "field", "args", "start", "end",
                 "elapsed", "_ann")

    def __init__(self, name: str, clock: Callable[[], float],
                 record: Any = None, field: str | None = None, **args):
        self.name, self.clock, self.args = name, clock, args
        self.record, self.field = record, field

    def __enter__(self) -> "Span":
        self._ann = None
        if _Annotation.is_enabled():
            self._ann = _Annotation(self.name, **self.args)
            self._ann.__enter__()
        self.start = self.clock()
        return self

    def annotate(self, **args) -> None:
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __exit__(self, *exc) -> None:
        self.end = self.clock()
        self.elapsed = self.end - self.start
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self.record is not None:
            setattr(self.record, self.field,
                    getattr(self.record, self.field) + self.elapsed)


@runtime_checkable
class RequestLike(Protocol):
    """Anything submittable: the envelope only pins the uid."""

    uid: int


@runtime_checkable
class ResultLike(Protocol):
    """Anything drainable: results are keyed and reported by uid."""

    uid: int


class EngineProtocol(Protocol):
    """The one serving-runtime API (see module docstring).

    Both ``serve.engine.Engine`` and ``serve.reason.ReasonEngine``
    implement this structurally; ``isinstance(eng, EngineProtocol)`` is
    intentionally not used for dispatch — the front-door just drives the
    methods.
    """

    stats: dict
    runs: list
    clock: Callable[[], float]

    @property
    def admission_cap(self) -> int: ...          # pragma: no cover

    @property
    def inflight(self) -> int: ...               # pragma: no cover

    def submit(self, group: Sequence[RequestLike]) -> GroupRecord:
        ...                                      # pragma: no cover

    def drain_ready(self) -> dict[int, Any]: ...  # pragma: no cover

    def drain_all(self) -> dict[int, Any]: ...    # pragma: no cover


def fresh_split_stats() -> dict:
    """The warmup/measured wall-time split both engines account under.

    A run that jit-compiles a new shape (first touch of a (variant,
    bucket) pipeline shape, a new padded prefill length, the first decode
    block) lands under ``warmup``; steady-state runs land under
    ``measured`` — so throughput helpers never fold compile time into the
    denominator.  ``work`` counts the class's throughput unit: problems
    for NSAI engines, generated tokens for LM engines.
    """
    return {
        "measured": {"requests": 0, "work": 0, "wall_time_s": 0.0},
        "warmup": {"requests": 0, "work": 0, "wall_time_s": 0.0},
    }


def measured_rate(stats: Mapping, field: str = "work") -> float:
    """Steady-state ``field``-per-second from a warmup-split stats dict.

    Warmup runs are excluded; if *only* warmup runs exist (e.g. a single
    run that first-touched a shape), falls back to the warmup totals
    rather than reporting 0 — check ``stats["measured"]["requests"]`` to
    tell the cases apart.
    """
    m, w = stats["measured"], stats["warmup"]
    if m["wall_time_s"]:
        return m[field] / m["wall_time_s"]
    if w["wall_time_s"]:
        return w[field] / w["wall_time_s"]
    return 0.0


def request_priority(request: Any) -> str:
    """Priority class of a request envelope.

    The envelope is structural (like ``uid``): any request may carry a
    ``priority`` attribute naming one of :data:`~repro.serve.slo.
    PRIORITIES`; envelopes without one serve as ``standard``.  The
    front-door validates the class at admission (named error), so
    engines never see an unknown class."""
    from repro.serve.slo import DEFAULT_PRIORITY

    return getattr(request, "priority", None) or DEFAULT_PRIORITY


def engine_observation(engine: Any) -> dict[str, Any]:
    """What the overload controller sees of one engine each tick.

    Prefers the engine's own ``observation()`` (``ReplicaPool`` merges
    across replicas there); otherwise derives the generic view from the
    protocol surface.  ``work_rate`` is the steady-state throughput in
    the engine's own unit (see :func:`measured_rate`)."""
    obs = getattr(engine, "observation", None)
    if callable(obs):
        return obs()
    return {"inflight": engine.inflight,
            "work_rate": measured_rate(engine.stats)}


def work_units(result: Any) -> int:
    """Throughput units one result carries: generated tokens for LM
    results, 1 problem for NSAI results."""
    tokens = getattr(result, "tokens", None)
    return len(tokens) if tokens is not None else 1


def work_unit_name(results: Iterable[Any]) -> str:
    """'tok' when any result carries generated tokens, else 'prob'."""
    return "tok" if any(getattr(r, "tokens", None) is not None
                        for r in results) else "prob"


# ---------------------------------------------------------------------------
# the runtime registry (launcher --workload / --models choices derive here)
# ---------------------------------------------------------------------------


def _lm_model_ids() -> tuple[str, ...]:
    """Arch ids the slot-pool Engine can serve (token-in/token-out kinds)."""
    from repro.configs import ARCHS

    return tuple(sorted(a for a, spec in ARCHS.items()
                        if spec.kind in ("lm", "rwkv", "griffin")))


def _reason_model_ids() -> tuple[str, ...]:
    from repro.configs.base import REASON_WORKLOADS

    return tuple(REASON_WORKLOADS)


def _all_model_ids() -> tuple[str, ...]:
    return _reason_model_ids() + _lm_model_ids()


@dataclasses.dataclass(frozen=True)
class TrafficClass:
    """One entry of the runtime registry: a serving traffic class."""

    name: str
    describe: str
    models: Callable[[], tuple[str, ...]]   # servable model ids (lazy)


TRAFFIC_CLASSES: dict[str, TrafficClass] = {
    "lm": TrafficClass(
        "lm", "continuous-batching generation through the slot-pool Engine",
        _lm_model_ids),
    "reason": TrafficClass(
        "reason", "batched NSAI reasoning through the staged-pipeline "
                  "ReasonEngine", _reason_model_ids),
    "frontdoor": TrafficClass(
        "frontdoor", "online mixed LM+NSAI traffic: DSE-deployed engines "
                     "behind one deadline-batched front-door",
        _all_model_ids),
}


def resolve_models(workload: str, models: Iterable[str]) -> tuple[str, ...]:
    """Validate a model list against a traffic class's registry entry."""
    tc = TRAFFIC_CLASSES.get(workload)
    if tc is None:
        raise KeyError(f"unknown workload {workload!r}; "
                       f"available: {tuple(TRAFFIC_CLASSES)}")
    known = tc.models()
    out = tuple(models)
    bad = [m for m in out if m not in known]
    if bad:
        raise ValueError(f"{workload}: unknown models {bad}; "
                         f"servable: {known}")
    return out
