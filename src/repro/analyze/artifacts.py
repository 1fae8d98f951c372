"""Per-stage jaxpr checks over compiled StagedSchedules (NSF001–NSF004).

A :class:`~repro.serve.schedule.StagedSchedule` carries everything needed
to re-derive the artifacts a deployment serves: abstract input/consts
specs, the raw stage callables, the lowering plan they trace under, and
the fused jit.  These checks retrace each stage with
:func:`jax.make_jaxpr` (abstract — no compile, no device work) and walk
the equation graph:

* **NSF001 precision flow** — any ``convert_element_type`` introducing
  float64 is an error (the stack is f32/bf16/int; a silent x64 upcast
  doubles every buffer and detunes every kernel); a float32→bf16/f16
  downcast inside a symbolic (``vsa``/``simd``) stage whose config
  declares int-quantized ``symb_precision`` — or an ``nn`` stage under
  int ``nn_precision`` — is an error too: the fake-quant int emulation is
  defined *in f32*, so a half-precision cast silently drops below the
  declared precision class.
* **NSF002 fake_quant axis consistency** — ``fake_quant`` lowers to
  ``abs`` feeding ``reduce_max``; two reductions of equal input rank with
  different axes in one stage mean one tensor quantizes per-problem and
  a same-shaped one globally (a request's numerics would depend on its
  admission group) — warning.
* **NSF003 host round-trips** — callback/infeed/outfeed primitives in a
  hot stage body block the device per dispatch.
* **NSF004 donation** — off-CPU schedules must donate the fused
  pipeline's input buffer where an output has its shape and dtype (the
  lowered text carries an aliasing annotation), CPU schedules must not
  (XLA:CPU ignores donation and warns); either mismatch means
  ``compile_schedule``'s donation policy and the artifact disagree.
"""

from __future__ import annotations

import jax
import numpy as np

from repro.analyze.findings import AnalysisReport, finding
from repro.backend import registry

_CALLBACK_MARKERS = ("callback", "infeed", "outfeed", "outside_call",
                     "debug_print")


def _subjaxprs(val):
    if hasattr(val, "eqns"):            # core.Jaxpr
        yield val
    elif hasattr(val, "jaxpr"):         # ClosedJaxpr
        yield val.jaxpr
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _subjaxprs(v)


def walk_eqns(jaxpr):
    """Every equation, recursing into pjit/scan/cond inner jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in _subjaxprs(val):
                yield from walk_eqns(sub)


def stage_jaxprs(sched):
    """Yield ``(stage, jaxpr)`` per stage, chaining abstract specs.

    Traces under the schedule's own lowering plan so the jaxprs are the
    ones the deployment actually serves.  Stage ``i``'s input spec is
    stage ``i-1``'s output spec (stage 0 takes the staged batch).
    """
    if sched.input_specs is None or sched.consts_spec is None:
        return
    plan = sched.plan or registry.get_plan()
    bufs = sched.input_specs
    with registry.use_plan(plan):
        for stage in sched.stages:
            yield stage, jax.make_jaxpr(stage.fn)(sched.consts_spec, bufs)
            bufs = jax.eval_shape(stage.fn, sched.consts_spec, bufs)


def _declared_precision(cfg, stream: str) -> str | None:
    """The config's declared precision class for a stage's stream."""
    attr = "nn_precision" if stream == "nn" else "symb_precision"
    return getattr(cfg, attr, None)


def _check_stage_precision(stage, jaxpr, cfg, where) -> list:
    out = []
    declared = _declared_precision(cfg, stage.stream) if cfg is not None \
        else None
    for eqn in walk_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        new = np.dtype(eqn.params.get("new_dtype"))
        old = eqn.invars[0].aval.dtype if eqn.invars else None
        if new == np.float64:
            out.append(finding(
                "NSF001", where,
                f"stage {stage.name!r} converts {old} -> float64 — silent "
                "x64 upcast in a hot stage body (doubles the buffer, "
                "detunes every kernel epsilon)"))
        elif declared in ("int8", "int4") and old == np.float32 \
                and new in (np.dtype("bfloat16"), np.float16):
            out.append(finding(
                "NSF001", where,
                f"stage {stage.name!r} ({stage.stream} stream) downcasts "
                f"float32 -> {new} while the config declares "
                f"{stage.stream}-stream precision {declared!r} — fake-quant "
                "int emulation is defined in f32; this cast drops below "
                "the declared class"))
    return out


def _check_stage_fake_quant(stage, jaxpr, where) -> list:
    abs_outs = set()
    for eqn in walk_eqns(jaxpr):
        if eqn.primitive.name == "abs":
            abs_outs.update(id(v) for v in eqn.outvars)
    seen: dict[int, set[tuple]] = {}
    for eqn in walk_eqns(jaxpr):
        if eqn.primitive.name == "reduce_max" and eqn.invars \
                and id(eqn.invars[0]) in abs_outs:
            rank = len(eqn.invars[0].aval.shape)
            axes = tuple(eqn.params.get("axes", ()))
            seen.setdefault(rank, set()).add(axes)
    out = []
    for rank, axes_set in seen.items():
        if len(axes_set) > 1:
            out.append(finding(
                "NSF002", where,
                f"stage {stage.name!r}: fake_quant amax reductions over "
                f"rank-{rank} inputs disagree on axes "
                f"({sorted(axes_set)}) — mixed global/per-problem scales "
                "make a request's numerics depend on its admission group"))
    return out


def _check_stage_callbacks(stage, jaxpr, where) -> list:
    out = []
    for eqn in walk_eqns(jaxpr):
        name = eqn.primitive.name
        if any(m in name for m in _CALLBACK_MARKERS):
            out.append(finding(
                "NSF003", where,
                f"stage {stage.name!r} contains host primitive {name!r} — "
                "a device->host round-trip per dispatch in a hot stage "
                "body"))
    return out


def check_donation(sched, where) -> list:
    """NSF004: the fused pipeline's donation must match the platform."""
    if sched.jit_fused is None or sched.input_specs is None \
            or sched.consts_spec is None:
        return []
    from repro.serve.schedule import donation_usable

    plan = sched.plan or registry.get_plan()
    with registry.use_plan(plan):
        text = sched.jit_fused.lower(sched.consts_spec,
                                     sched.input_specs).as_text()
        out = jax.eval_shape(sched.jit_fused, sched.consts_spec,
                             sched.input_specs)
    donated = text.count("aliasing_output") + text.count("jax.buffer_donor")
    usable = donation_usable(sched.input_specs, out)
    if plan.platform != "cpu" and usable and not donated:
        return [finding(
            "NSF004", where,
            f"fused pipeline on {plan.platform!r} carries no donation "
            "annotation — the inter-stage buffer is copied per group "
            "instead of updated in place")]
    if plan.platform == "cpu" and donated:
        return [finding(
            "NSF004", where,
            "fused pipeline donates its input buffer on CPU — XLA:CPU "
            "ignores donation and warns per compile; compile_schedule "
            "should pass donate_argnums=() off-accelerator",
            severity="warning")]
    return []


def check_schedule(sched, cfg=None, where: str | None = None
                   ) -> AnalysisReport:
    """All artifact checks over one compiled schedule."""
    report = AnalysisReport()
    where = where or f"{sched.workload}/{sched.variant}"
    for stage, jaxpr in stage_jaxprs(sched):
        stage_where = f"{where}/{stage.name}"
        report.extend(_check_stage_precision(stage, jaxpr, cfg, stage_where))
        report.extend(_check_stage_fake_quant(stage, jaxpr, stage_where))
        report.extend(_check_stage_callbacks(stage, jaxpr, stage_where))
        report.covered("stage_jaxprs")
    report.extend(check_donation(sched, where))
    if sched.jit_fused is not None:
        report.covered("fused_donation")
    return report
