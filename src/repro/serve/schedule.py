"""Schedule compilation: lower a dataflow graph to an executable pipeline.

NSFlow's design generator (paper Sec V-B) identifies workload data
dependencies and emits an optimized dataflow architecture; this module is
the serving-side realization of the same lowering.  ``compile_schedule``
takes a workload's *stage list* — jax-traceable callables with declared
stream tags (nn / vsa / simd, the paper's unit taxonomy) — and emits a
:class:`StagedSchedule`:

  - an ordered tuple of **jit-able stage callables** (one jit boundary per
    stage: the boundaries are exactly the points where the generic executor
    in ``serve.reason.ReasonEngine`` may drain / overlap),
  - a **fused whole-pipeline variant** (``jit_fused``): a single jit of the
    composed stages with the staged input buffer donated, so one admission
    group costs one dispatch instead of K.  The fused trace is negotiated
    against the staged one through the active
    :class:`~repro.backend.registry.LoweringPlan`: ``compile_schedule``
    records which kernel lowerings each trace selects
    (``registry.record_selections``) and declares the fused variant
    ``exact`` (bit-identical — the executor may substitute it freely) or
    ``epsilon`` (a fused-only kernel routed to a non-exact lowering — the
    executor falls back stage-by-stage unless fusion was forced),
  - **inter-stage buffer specs** (pytree shapes + byte counts, from
    ``jax.eval_shape`` chained through the stages — the serving analogue of
    the memory-cost annotation, Sec V-B step ⑤),
  - a traced :class:`~repro.core.dataflow.DataflowGraph` built by running
    ``core.trace`` on the composed pipeline's jaxpr (steps ①–③: critical
    path, depth assignment, inter-loop overlap model), plus per-stage op
    statistics from tracing each stage alone,
  - the **host/device overlap points** the executor honors (which host
    steps run while the device works, and where the previous batch is
    drained).

Stream tags are *declared* by the workload and *audited* against the trace:
at smoke scale XLA lowers blockwise circular convolution to gather +
dot_general (so a flops-dominance classifier would mislabel the symbolic
stream as ``nn``), which is exactly the "tracing is too fine-grained" case
the declared tags resolve.  The audit result per stage is kept on the
schedule (``stage_costs``) so benchmarks and tests can inspect both views.

The correspondence with the analytical side: ``core.dataflow.build`` on the
same graph drives the DSE; ``interloop_overlap`` predicts the steady-state
inter-loop pipeline speedup of the compiled schedule
(``predicted_overlap``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import numpy as np

from repro.backend import registry
from repro.core import dataflow as dfl
from repro.core import trace as trace_mod
from repro.core.opgraph import OpGraph

STREAMS = ("nn", "vsa", "simd")

# Host-side steps the generic executor overlaps with device compute, in
# pipeline order.  ``ingest``: pulling + preprocessing requests from the
# (possibly lazy) stream; ``stage``: stacking/padding to the compiled batch
# shape and device transfer; ``collect``: materializing the *previous*
# batch's answers.  All three run while the device works through the
# in-flight batch — the host/device realization of inter-loop overlap
# (paper Sec V-B step ③).
HOST_OVERLAP_POINTS = ("ingest", "stage", "collect")


@dataclasses.dataclass(frozen=True)
class StageSpec:
    """One pipeline stage: a jax-traceable callable with a stream tag.

    ``fn(consts, bufs) -> bufs`` — ``consts`` is the workload's constant
    pytree (params / codebooks / keys), ``bufs`` the previous stage's
    output pytree (stage 0 receives the staged request batch).
    """

    name: str
    stream: str        # nn | vsa | simd
    fn: Callable[[Any, Any], Any]

    def __post_init__(self):
        if self.stream not in STREAMS:
            raise ValueError(f"stage {self.name!r}: unknown stream "
                             f"{self.stream!r} (want one of {STREAMS})")


@dataclasses.dataclass(frozen=True)
class BufferSpec:
    """Inter-stage buffer: pytree of ShapeDtypeStructs + total bytes."""

    shapes: Any
    nbytes: int

    @staticmethod
    def from_tree(tree) -> "BufferSpec":
        leaves = jax.tree.leaves(tree)
        nbytes = int(sum(np.prod(l.shape) * np.dtype(l.dtype).itemsize
                         for l in leaves))
        return BufferSpec(shapes=tree, nbytes=nbytes)


@dataclasses.dataclass
class StagedSchedule:
    """An executable pipeline compiled from a workload's dataflow.

    ``jit_stages[i]`` is ``jax.jit(stages[i].fn)``; jit caches live on the
    schedule, so reuse schedules (engines share them per variant).  When
    input specs are known, ``buffers[0]`` describes the staged input batch
    and ``buffers[i + 1]`` the output of stage ``i`` (so ``len(buffers) ==
    len(stages) + 1``).  ``drain_stage`` is the stage index before whose
    dispatch the
    executor drains the previous in-flight batch (0 = PR 2's schedule:
    collect batch i-1 right before batch i's first device stage, so host
    work never blocks the device and co-scheduling contention is avoided).
    """

    workload: str
    variant: str
    stages: tuple[StageSpec, ...]
    jit_stages: tuple[Callable, ...]
    ingest: Callable                      # fn(request) -> pytree of np arrays
    collect: Callable                     # fn(host_out, i) -> result fields
    buffers: tuple[BufferSpec, ...] = ()  # input buffer + per-stage outputs
    stage_costs: tuple[dict, ...] = ()    # per-stage traced op statistics
    graph: dfl.DataflowGraph | None = None
    source: str = "declared"              # declared | trace
    drain_stage: int = 0
    host_overlap: tuple[str, ...] = HOST_OVERLAP_POINTS
    # compiled batch-size buckets, ascending; () = the single input_specs
    # batch size.  A partial admission group is padded to the smallest
    # covering bucket instead of the max (each bucket is its own jit cache
    # entry on the shared jit_stages).  ``buffers``/``stage_costs`` describe
    # the largest bucket.
    batch_buckets: tuple[int, ...] = ()
    # kept for lazy cost tracing (``predicted_overlap`` on schedules
    # compiled with ``trace_graph=False``): abstract consts + stage-0 specs
    input_specs: Any = None
    consts_spec: Any = None
    # the LoweringPlan baked into jit_stages: every stage traces (and
    # therefore compiles) under this plan, so the kernel lowerings a
    # deployment negotiated are pinned per schedule, independent of
    # whatever plan is active when the executor later calls the jits.
    plan: registry.LoweringPlan | None = None
    # -- fused whole-pipeline variant (one dispatch per group) -------------
    # ``jit_fused`` is a single jit of the composed (possibly substituted,
    # see ``fused_stages``) pipeline with the input buffer donated.
    # ``fused_equivalence`` is the negotiated conformance class of the
    # fused trace versus the staged one under ``plan``: "exact" when both
    # traces route every kernel through exact lowerings wherever they
    # differ (the executor substitutes the fused path freely), "epsilon"
    # when a differing kernel sits on a non-exact lowering
    # (``fused_epsilon`` = the max declared tolerance; the executor falls
    # back stage-by-stage unless ``fused_forced``).
    # ``fused_lowering_diff`` names the kernels whose selections differ.
    jit_fused: Callable | None = None
    fused_stages: tuple[StageSpec, ...] = ()
    fused_forced: bool = False
    fused_equivalence: str | None = None   # exact | epsilon | None
    fused_epsilon: float = 0.0
    fused_lowering_diff: tuple[str, ...] = ()

    @property
    def stage_names(self) -> tuple[str, ...]:
        return tuple(s.name for s in self.stages)

    @property
    def streams(self) -> tuple[str, ...]:
        return tuple(s.stream for s in self.stages)

    @property
    def fused_ok(self) -> bool:
        """May the executor substitute the fused pipeline for the staged
        one?  True when a fused jit exists and is either negotiated exact
        or explicitly forced (``compile_schedule(fused=True)``)."""
        return self.jit_fused is not None and (
            self.fused_forced or self.fused_equivalence == "exact")

    def covering_bucket(self, n: int) -> int:
        """Smallest compiled batch bucket that fits ``n`` requests."""
        if not self.batch_buckets:
            return n
        for b in self.batch_buckets:
            if b >= n:
                return b
        raise ValueError(
            f"{self.workload}/{self.variant}: admission group of {n} "
            f"exceeds the largest compiled bucket {self.batch_buckets[-1]}")

    def describe(self) -> str:
        """One-line pipeline rendering: name[stream] -> name[stream]."""
        parts = []
        for i, s in enumerate(self.stages):
            buf = ""
            if i < len(self.stages) - 1:
                buf = f" --{_fmt_bytes(self.buffers[i + 1].nbytes)}--> " \
                    if self.buffers else " -> "
            parts.append(f"{s.name}[{s.stream}]{buf}")
        return "".join(parts)


def _fmt_bytes(n: int) -> str:
    # 1023.95 threshold: anything that would render as "1024.0" after the
    # one-decimal rounding is promoted to the next unit (1048575 bytes is
    # "1.0MB", not "1024.0KB")
    x = float(n)
    for unit in ("B", "KB", "MB"):
        if x < (1024 if unit == "B" else 1023.95):
            return f"{x:.0f}B" if unit == "B" else f"{x:.1f}{unit}"
        x /= 1024
    return f"{x:.1f}GB"


def _graph_stats(g: OpGraph) -> dict:
    """Summarize a traced stage subgraph for the stream-tag audit."""
    flops = {k: g.total_flops(k) for k in STREAMS}
    total = sum(flops.values())
    dominant = max(flops, key=flops.get) if total else "simd"
    # Pallas/fft vsa nodes prove a symbolic stream even when the gather
    # fallback hides the flops inside dot_general (see module docstring)
    has_vsa = any(n.kind == "vsa" for n in g)
    return {
        "nodes": len(g), "flops": flops, "bytes": g.total_bytes(),
        "dominant": dominant, "has_vsa_nodes": has_vsa,
    }


def compose_stages(stages: tuple[StageSpec, ...]) -> Callable:
    """The whole pipeline as one callable — what ``jit_fused`` compiles and
    what ``trace_pipeline`` traces (the DataflowGraph already proves this
    composition is what the staged executor computes)."""

    def composed(consts, bufs):
        for s in stages:
            bufs = s.fn(consts, bufs)
        return bufs

    return composed


def trace_pipeline(stages: tuple[StageSpec, ...], consts, input_specs
                   ) -> dfl.DataflowGraph:
    """Trace the composed pipeline's jaxpr into a DataflowGraph (steps ①–③).

    This is ``core.trace`` on the model's jaxpr: the same graph the DSE
    consumes, built from the exact computation the schedule will execute.
    """
    opgraph = trace_mod.extract(compose_stages(stages), consts, input_specs)
    return dfl.build(opgraph)


def _fused_conformance(staged_sel: list, fused_sel: list
                       ) -> tuple[str, float, tuple[str, ...]]:
    """Negotiate the fused trace's equivalence class vs the staged trace.

    Both inputs are ``(kernel, lowering_name)`` selection logs from
    ``registry.record_selections``.  Kernels whose selection *sets* agree
    are bit-identical by construction (same lowerings, same shapes, same
    plan).  For each kernel that differs — typically a fused-only kernel
    like ``unbind_classify`` replacing the staged ``circ_conv`` + dense
    pair — the class is "exact" only if every lowering either side selected
    is exact; otherwise "epsilon" at the max declared tolerance.
    """
    staged: dict[str, set] = {}
    for kern, low in staged_sel:
        staged.setdefault(kern, set()).add(low)
    fused: dict[str, set] = {}
    for kern, low in fused_sel:
        fused.setdefault(kern, set()).add(low)
    diff = sorted(k for k in set(staged) | set(fused)
                  if staged.get(k, set()) != fused.get(k, set()))
    eps, exact = 0.0, True
    for k in diff:
        spec = registry.KERNELS[k]
        for name in staged.get(k, set()) | fused.get(k, set()):
            low = spec.by_name(name)
            if low.equivalence != "exact":
                exact = False
                eps = max(eps, low.epsilon)
    return ("exact" if exact else "epsilon"), eps, tuple(diff)


def donation_usable(input_specs, output_specs) -> bool:
    """True when some output leaf has an input leaf's shape and dtype —
    the only case in which donating the staged input lets XLA write an
    output into it.  Otherwise JAX drops the donation (and warns)."""
    def avals(tree):
        return {(tuple(x.shape), np.dtype(x.dtype))
                for x in jax.tree.leaves(tree)}

    return bool(avals(input_specs) & avals(output_specs))


def _abstract(tree):
    """ShapeDtypeStruct skeleton of a pytree (non-array leaves pass through)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        if hasattr(x, "shape") and hasattr(x, "dtype") else x, tree)


def _plan_scoped(fn: Callable, plan: registry.LoweringPlan) -> Callable:
    """Bind a stage fn to a LoweringPlan: tracing (and hence the lowering
    choices jit bakes into its cache) always happens under ``plan``.

    Stages also trace at ``highest`` matmul precision.  Workloads state
    reduced precision through operand dtypes (bf16, int8), so an f32
    product must be f32-accurate; XLA:TPU's default runs it as one bf16
    pass, which moved NVSA's served logprobs by 0.07 from the reference on
    a v5e.  XLA:CPU computes f32 products in f32 either way.
    """

    @functools.wraps(fn)
    def scoped(consts, bufs):
        with registry.use_plan(plan), jax.default_matmul_precision("highest"):
            return fn(consts, bufs)

    return scoped


def compile_schedule(workload: str, stages: tuple[StageSpec, ...] | list,
                     ingest: Callable, collect: Callable, *,
                     variant: str = "default", consts=None, input_specs=None,
                     graph: OpGraph | None = None, trace_graph: bool = True,
                     batch_buckets: tuple[int, ...] = (),
                     plan: registry.LoweringPlan | None = None,
                     fused: bool | str = "auto",
                     fused_stages: tuple[StageSpec, ...] | list | None = None
                     ) -> StagedSchedule:
    """Lower a stage list (+ its dataflow graph) to a StagedSchedule.

    ``input_specs``: pytree of ``jax.ShapeDtypeStruct`` for one staged
    request batch (stage 0's input).  When given, inter-stage buffer specs
    are derived by chaining ``jax.eval_shape`` through the stages, and —
    unless ``trace_graph`` is False (fast construction: no jaxpr walks,
    schedule still fully executable; ``predicted_overlap`` traces lazily
    on first use) — each stage plus the composed pipeline are traced with
    ``core.trace``: per-stage op statistics for the stream-tag audit, and
    a :class:`DataflowGraph` for provenance (``graph`` may instead supply
    a declared paper-scale ``OpGraph``, e.g. from ``core.workloads``,
    where tracing the reduced executable model would under-size the
    graph).  ``consts`` may be real arrays or ShapeDtypeStructs; it is
    only inspected abstractly.

    ``batch_buckets``: ascending compiled batch sizes (``input_specs``
    must describe the largest); the executor pads a partial admission
    group to the smallest covering bucket instead of the max.

    ``plan``: the :class:`~repro.backend.registry.LoweringPlan` the
    schedule compiles under (None = the plan active now, via
    ``registry.get_plan()``).  Stage fns are wrapped so both the buffer/
    cost tracing here and the later jit tracing happen under that plan.

    ``fused``: "auto" (default) also compiles the whole-pipeline fused
    variant and negotiates its equivalence class against the staged trace
    (the executor only substitutes it when bit-identical); ``True`` forces
    the fused path regardless of class; ``False`` skips it.
    ``fused_stages``: an alternate stage list for the fused trace (e.g.
    MIMONet's unbind+classify collapsed into the fused kernel) — requires
    ``input_specs`` so the output spec can be proven equal to the staged
    pipeline's.
    """
    stages = tuple(stages)
    if not stages:
        raise ValueError("schedule needs at least one stage")
    names = [s.name for s in stages]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate stage names: {names}")
    batch_buckets = tuple(batch_buckets)
    if batch_buckets:
        if list(batch_buckets) != sorted(set(batch_buckets)) \
                or batch_buckets[0] < 1:
            raise ValueError(f"batch_buckets must be ascending positive "
                             f"sizes, got {batch_buckets}")
    if fused not in (True, False, "auto"):
        raise ValueError(f"fused must be True, False or 'auto', got {fused!r}")
    if fused_stages is not None and input_specs is None:
        raise ValueError(
            f"{workload}/{variant}: an alternate fused stage list needs "
            "input_specs to prove its output spec matches the staged "
            "pipeline's")
    if plan is None:
        plan = registry.get_plan()
    stages = tuple(dataclasses.replace(s, fn=_plan_scoped(s.fn, plan))
                   for s in stages)
    fused_specs = stages
    if fused_stages is not None:
        fused_specs = tuple(dataclasses.replace(s, fn=_plan_scoped(s.fn, plan))
                            for s in fused_stages)

    buffers: tuple[BufferSpec, ...] = ()
    stage_costs: tuple[dict, ...] = ()
    df: dfl.DataflowGraph | None = None
    source = "declared"
    staged_sel: list = []
    staged_out = None
    if input_specs is not None:
        bufs = [BufferSpec.from_tree(jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), input_specs))]
        costs = []
        spec = input_specs
        # record which kernel lowerings the staged trace selects — the
        # fused trace below is diffed against this set (selections happen
        # in the wrappers' python dispatch layer, so abstract tracing
        # exercises exactly the lowerings that will serve)
        with registry.record_selections() as staged_sel:
            for s in stages:
                spec = jax.eval_shape(s.fn, consts, spec)
                bufs.append(BufferSpec.from_tree(spec))
                if trace_graph:
                    costs.append(_graph_stats(trace_mod.extract(
                        s.fn, consts, bufs[-2].shapes)))
        staged_out = spec
        buffers = tuple(bufs)
        stage_costs = tuple(costs)
        if graph is not None:
            df = dfl.build(graph)
        elif trace_graph:
            df = trace_pipeline(stages, consts, input_specs)
            source = "trace"
    elif graph is not None:
        df = dfl.build(graph)

    # -- fused whole-pipeline variant --------------------------------------
    jit_fused = None
    fused_equivalence: str | None = None
    fused_eps = 0.0
    fused_diff: tuple[str, ...] = ()
    if fused:
        composed = compose_stages(fused_specs)
        if input_specs is not None:
            with registry.record_selections() as fused_sel:
                fused_out = jax.eval_shape(composed, consts, input_specs)
            fo_l, fo_t = jax.tree.flatten(fused_out)
            st_l, st_t = jax.tree.flatten(staged_out)
            if fo_t != st_t or any(
                    a.shape != b.shape or a.dtype != b.dtype
                    for a, b in zip(fo_l, st_l)):
                raise ValueError(
                    f"{workload}/{variant}: fused pipeline output spec does "
                    f"not match the staged pipeline's")
            fused_equivalence, fused_eps, fused_diff = _fused_conformance(
                staged_sel, fused_sel)
        else:
            # same stage fns composed under the same plan: trivially exact
            fused_equivalence, fused_eps = "exact", 0.0
        # donate the staged input buffer so XLA can write an output into
        # it — where some output has its shape (CPU does not implement
        # donation)
        donate = (1,) if plan.platform != "cpu" and (
            input_specs is None
            or donation_usable(input_specs, fused_out)) else ()
        jit_fused = jax.jit(composed, donate_argnums=donate)

    return StagedSchedule(
        workload=workload, variant=variant, stages=stages,
        jit_stages=tuple(jax.jit(s.fn) for s in stages),
        ingest=ingest, collect=collect, buffers=buffers,
        stage_costs=stage_costs, graph=df, source=source,
        batch_buckets=batch_buckets,
        input_specs=_abstract(input_specs) if input_specs is not None
        else None,
        consts_spec=_abstract(consts) if input_specs is not None else None,
        plan=plan,
        jit_fused=jit_fused, fused_stages=fused_specs,
        fused_forced=fused is True, fused_equivalence=fused_equivalence,
        fused_epsilon=fused_eps, fused_lowering_diff=fused_diff)


def _ensure_stage_costs(schedule: StagedSchedule):
    """Lazily trace per-stage costs (+ the composed-pipeline graph) for
    schedules compiled with ``input_specs`` but ``trace_graph=False``;
    memoized on the schedule."""
    if schedule.stage_costs or schedule.input_specs is None:
        return
    costs = []
    spec = schedule.input_specs
    for s in schedule.stages:
        costs.append(_graph_stats(
            trace_mod.extract(s.fn, schedule.consts_spec, spec)))
        spec = jax.eval_shape(s.fn, schedule.consts_spec, spec)
    schedule.stage_costs = tuple(costs)
    if schedule.graph is None:
        schedule.graph = trace_pipeline(schedule.stages, schedule.consts_spec,
                                        schedule.input_specs)
        schedule.source = "trace"


def ensure_graph(schedule: StagedSchedule) -> dfl.DataflowGraph:
    """The schedule's :class:`DataflowGraph`, tracing lazily (memoized) for
    schedules compiled with ``trace_graph=False`` — this is what
    ``repro.serve.deploy`` hands to ``core.dse.explore`` to derive the
    serving configuration from the workload's dataflow dependencies."""
    if schedule.graph is None:
        _ensure_stage_costs(schedule)
    if schedule.graph is None:
        raise ValueError(
            f"{schedule.workload}/{schedule.variant}: schedule was compiled "
            "without input_specs — no graph to trace")
    return schedule.graph


def predicted_overlap(schedule: StagedSchedule, n_batches: int = 2) -> dict:
    """Analytical overlap prediction for the compiled schedule.

    Splits the traced per-stage costs into the NN-stream prefix vs the
    symbolic tail and runs ``core.dataflow.interloop_overlap`` — the same
    step-③ model the DSE uses.  Works on ``trace_graph=False`` schedules
    too: stage costs (and with them the graph) are traced lazily on first
    use.
    """
    _ensure_stage_costs(schedule)
    if not schedule.stage_costs:
        raise ValueError("schedule was compiled without input_specs "
                         "(no stage costs to trace)")
    t_nn = sum(sum(c["flops"].values()) for s, c in
               zip(schedule.stages, schedule.stage_costs) if s.stream == "nn")
    t_sy = sum(sum(c["flops"].values()) for s, c in
               zip(schedule.stages, schedule.stage_costs) if s.stream != "nn")
    return dfl.interloop_overlap(schedule.graph, max(1, t_nn), max(1, t_sy),
                                 n_loops=n_batches)
