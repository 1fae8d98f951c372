"""The lowering registry: per-kernel backend negotiation as a first layer.

NSFlow's versatility claim (paper Sec III) is that one framework picks the
right compute path *per heterogeneous kernel* and stays correct while doing
it.  Before this module the reproduction had three lowerings per kernel —
compiled Pallas, Pallas interpret mode, and the exact XLA reference — but
selection was scattered: four private ``_interpret()`` copies in
``kernels/*/ops.py`` (whose ``!= "tpu"`` test silently forced GPUs into
interpret mode), a separate size/pow2 threshold in ``vsa/ops.py``, and no
record anywhere of which path actually served traffic.

This registry makes lowering selection one explicit layer:

- every kernel (``circ_conv``, ``qmatmul``, ``simd_fused``,
  ``flash_attn`` — plus the VSA gather reference, registered as
  ``circ_conv``'s ``xla`` lowering) declares its :class:`Lowering`\\ s with
  capability predicates: which platforms may negotiate them, pow2 / size
  constraints, and an **equivalence class** versus the kernel's exact XLA
  reference (``exact`` = bit-identical, ``epsilon`` = within a declared
  tolerance — what trace replay diffs against, see ``serve.trace``);
- :func:`negotiate` probes the runtime platform (``jax.default_backend()``)
  and returns an explicit :class:`LoweringPlan` — a per-kernel *fallback
  chain* whose head is the preferred lowering and whose tail always ends in
  the universally-feasible ``xla`` reference;
- the plan is overridable via ``REPRO_BACKEND`` (``xla`` | ``interpret`` |
  ``pallas``, or per-kernel ``circ_conv=xla,qmatmul=pallas``) for
  forced-fallback / graceful-degradation runs;
- kernel wrappers call :func:`active` at trace time with their call-site
  capabilities (block dim ``d``), and the plan picks the first feasible
  lowering in the chain — so a non-pow2 ``d`` degrades past the compiled
  Pallas lowering (whose Mosaic tiling is only validated on pow2 block
  dims >= 8) instead of crashing the circulant builder; the interpreter
  serves any shape, bit-for-bit with the kernel semantics.

``serve.schedule.compile_schedule`` scopes every compiled stage to a plan
(the plan active while the stage's jaxpr is traced is the plan that serves
it), ``serve.deploy.deploy()`` negotiates once per deployment and records
the per-kernel tags in ``Deployment.report()``, and ``serve.trace`` replays
recorded traffic under arbitrary plans, diffing by the equivalence class
declared here.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Iterator, Mapping

PLATFORMS = ("cpu", "gpu", "tpu")
ENV_VAR = "REPRO_BACKEND"


def _is_pow2(d: int) -> bool:
    return d > 0 and (d & (d - 1)) == 0


@dataclasses.dataclass(frozen=True)
class Lowering:
    """One registered compute path for a kernel.

    ``name`` is the lowering tag recorded in plans, bench rows and traces:
    ``pallas`` (compiled Pallas kernel), ``interpret`` (same kernel under
    the Pallas interpreter — the CPU correctness path), or ``xla`` (the
    pure-jnp reference, the oracle every other lowering conforms to).

    Capability predicates: ``platforms`` gates *negotiation* (which
    platforms may prefer this lowering); ``requires_pow2`` / ``min_size``
    gate *call sites* (the Pallas circulant builder needs a power-of-two
    block dim).  ``equivalence`` declares the conformance class versus the
    kernel's ``xla`` reference: ``exact`` means bit-identical outputs,
    ``epsilon`` means agreement within ``epsilon`` — the tolerance
    golden-trace replay applies when two plans route a kernel differently.
    """

    kernel: str
    name: str                      # pallas | interpret | xla
    platforms: tuple[str, ...]     # where negotiate() may prefer this
    interpret: bool = False        # Pallas interpreter flag (xla: unused)
    equivalence: str = "exact"     # exact | epsilon (vs the xla reference)
    epsilon: float = 0.0
    requires_pow2: bool = False    # last-dim must be a power of two
    min_size: int = 0              # minimum last-dim size (0 = none)
    note: str = ""

    def __post_init__(self):
        if self.equivalence not in ("exact", "epsilon"):
            raise ValueError(f"{self.kernel}/{self.name}: equivalence must "
                             f"be 'exact' or 'epsilon'")
        if self.equivalence == "epsilon" and self.epsilon <= 0:
            raise ValueError(f"{self.kernel}/{self.name}: epsilon class "
                             "needs epsilon > 0")

    @property
    def is_ref(self) -> bool:
        """True for the XLA reference path (no Pallas kernel involved)."""
        return self.name == "xla"

    def feasible(self, *, size: int | None = None) -> bool:
        """Call-site capability check (shape constraints only)."""
        if self.requires_pow2 or self.min_size:
            if size is None:
                return False
            if self.requires_pow2 and not _is_pow2(size):
                return False
            if size < self.min_size:
                return False
        return True


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Registry entry: a kernel and its lowerings in preference order.

    ``dispatch_min_size`` is the perf threshold historically buried in
    ``vsa/ops.py``: below it the XLA reference beats the kernel on every
    platform, so *dispatch-level* selection (``vsa.bind`` /
    ``vsa.match_prob``) prefers the reference for small block dims even
    when the kernel is feasible.  Kernel-level wrappers ignore it (callers
    who reached ``kernels/*/ops.py`` asked for the kernel).
    """

    name: str
    describe: str
    lowerings: tuple[Lowering, ...]
    dispatch_min_size: int = 0

    def __post_init__(self):
        names = [l.name for l in self.lowerings]
        if len(set(names)) != len(names):
            raise ValueError(f"{self.name}: duplicate lowering names {names}")
        if "xla" not in names:
            raise ValueError(f"{self.name}: needs an 'xla' reference "
                             "lowering as the universal fallback")

    def by_name(self, name: str) -> Lowering:
        for l in self.lowerings:
            if l.name == name:
                return l
        raise KeyError(f"kernel {self.name!r} has no lowering {name!r}; "
                       f"registered: {[l.name for l in self.lowerings]}")


def _pallas_family(kernel: str, *, epsilon: float, requires_pow2=False,
                   min_size=0, note="") -> tuple[Lowering, Lowering]:
    """The compiled/interpret pair every Pallas kernel registers: compiled
    on accelerators (TPU *and* GPU — the old ``!= "tpu"`` test wrongly
    forced GPUs into the interpreter), interpret mode on CPU.

    Shape constraints (``requires_pow2`` / ``min_size``) gate only the
    *compiled* lowering: Mosaic's tiling for these kernels is validated on
    pow2 block dims, so off-shape call sites degrade to the reference on
    accelerators.  The interpreter executes the same kernel semantics in
    plain XLA and is conformant at any shape — the registry's earlier
    claim that the circulant builder itself needs pow2 was disproven by
    the kernel-vs-registry consistency check (NSF006): interpret output is
    bit-identical to the gather reference at non-pow2 / small block dims.
    """
    return (
        Lowering(kernel=kernel, name="pallas", platforms=("tpu", "gpu"),
                 interpret=False, equivalence="epsilon", epsilon=epsilon,
                 requires_pow2=requires_pow2, min_size=min_size, note=note),
        Lowering(kernel=kernel, name="interpret", platforms=("cpu",),
                 interpret=True, equivalence="epsilon", epsilon=epsilon,
                 note="Pallas interpreter (CPU correctness path; any shape)"),
    )


KERNELS: dict[str, KernelSpec] = {
    "circ_conv": KernelSpec(
        name="circ_conv",
        describe="blockwise circular conv/corr (VSA bind/unbind) via the "
                 "circulant-matmul Pallas kernel",
        lowerings=_pallas_family(
            "circ_conv", epsilon=1e-3, requires_pow2=True, min_size=8,
            note="Mosaic tiling validated on pow2 block dims >= 8") + (
            Lowering(kernel="circ_conv", name="xla", platforms=PLATFORMS,
                     note="exact gather reference (vsa.ops.circ_conv_ref)"),
        ),
        dispatch_min_size=128),
    "qmatmul": KernelSpec(
        name="qmatmul",
        describe="quantized int8/packed-int4 matmul (mixed-precision "
                 "attribute heads)",
        lowerings=_pallas_family("qmatmul", epsilon=1e-3) + (
            Lowering(kernel="qmatmul", name="xla", platforms=PLATFORMS,
                     note="integer-exact reference (qmatmul_ref)"),
        )),
    "simd_fused": KernelSpec(
        name="simd_fused",
        describe="fused normalize/dot/softmax match_prob (the SIMD unit)",
        lowerings=_pallas_family("simd_fused", epsilon=1e-3) + (
            Lowering(kernel="simd_fused", name="xla", platforms=PLATFORMS,
                     note="similarity_matrix + softmax reference"),
        ),
        dispatch_min_size=128),
    "flash_attn": KernelSpec(
        name="flash_attn",
        describe="flash attention over (B, S, H, hd) with GQA",
        lowerings=_pallas_family("flash_attn", epsilon=3e-2) + (
            Lowering(kernel="flash_attn", name="xla", platforms=PLATFORMS,
                     note="materialized-scores reference"),
        )),
    "unbind_classify": KernelSpec(
        name="unbind_classify",
        describe="fused VSA unbind (circular correlation) -> dense classify "
                 "head; one launch for the symbolic tail of the pipeline",
        lowerings=_pallas_family(
            "unbind_classify", epsilon=1e-3, requires_pow2=True, min_size=8,
            note="Mosaic tiling validated on pow2 block dims >= 8") + (
            Lowering(kernel="unbind_classify", name="xla", platforms=PLATFORMS,
                     note="exact gather unbind + dense reference"),
        ),
        dispatch_min_size=128),
}


@dataclasses.dataclass(frozen=True)
class LoweringPlan:
    """An explicit, negotiated per-kernel lowering assignment.

    ``chains[kernel]`` is the fallback chain for that kernel, preference
    first; the last entry is always feasible (the ``xla`` reference).
    ``select`` resolves a call site against the chain; ``tags()`` is the
    per-kernel headline choice — what deployments record and traces diff.
    """

    platform: str
    chains: Mapping[str, tuple[Lowering, ...]]
    source: str = "negotiated"     # negotiated | env:... | override:...

    def select(self, kernel: str, *, size: int | None = None,
               dispatch: bool = False) -> Lowering:
        """First feasible lowering in ``kernel``'s chain for this call.

        ``dispatch=True`` additionally applies the kernel's
        ``dispatch_min_size`` perf threshold (the ``vsa.bind`` /
        ``vsa.match_prob`` level of selection); kernel-level wrappers call
        without it.
        """
        spec = KERNELS.get(kernel)
        if spec is None:
            raise KeyError(f"unknown kernel {kernel!r}; "
                           f"registered: {tuple(KERNELS)}")
        floor = spec.dispatch_min_size if dispatch else 0
        for low in self.chains[kernel]:
            if not low.feasible(size=size):
                continue
            if floor and not low.is_ref and (size is None or size < floor):
                continue
            for rec in _RECORDERS:
                rec.append((kernel, low.name))
            return low
        raise RuntimeError(f"{kernel}: no feasible lowering for size={size} "
                           f"in chain {[l.name for l in self.chains[kernel]]}")

    def lowering(self, kernel: str) -> Lowering:
        """The headline (preferred) lowering for ``kernel``."""
        return self.chains[kernel][0]

    def run_interpret(self, low: Lowering) -> bool:
        """The Pallas ``interpret=`` flag to execute ``low`` with *here*.

        A forced override can put a compiled-Pallas lowering on a CPU host
        (e.g. ``REPRO_BACKEND=pallas`` in CI): Mosaic cannot compile for
        CPU, so execution degrades to the interpreter while the plan keeps
        the forced tag — graceful degradation, not a crash.
        """
        return low.interpret or self.platform == "cpu"

    def tags(self) -> dict[str, str]:
        """Per-kernel headline lowering names, e.g. {'circ_conv': 'xla'}."""
        return {k: chain[0].name for k, chain in self.chains.items()}

    def tag(self) -> str:
        """Compact one-token plan tag for bench rows / summaries:
        ``cpu/interpret`` when every kernel agrees, else
        ``cpu/circ_conv:xla+qmatmul:interpret+...``."""
        tags = self.tags()
        if len(set(tags.values())) == 1:
            return f"{self.platform}/{next(iter(tags.values()))}"
        return self.platform + "/" + "+".join(
            f"{k}:{v}" for k, v in sorted(tags.items()))


def _parse_override(spec: str) -> dict[str, str]:
    """``"xla"`` -> {'*': 'xla'}; ``"circ_conv=xla,qmatmul=pallas"`` ->
    per-kernel map.  Unknown kernels / lowerings raise with the choices."""
    forced: dict[str, str] = {}
    for part in (p.strip() for p in spec.split(",") if p.strip()):
        if "=" in part:
            kernel, _, name = part.partition("=")
            kernel, name = kernel.strip(), name.strip()
            if kernel not in KERNELS:
                raise ValueError(
                    f"{ENV_VAR}: unknown kernel {kernel!r} "
                    f"(registered: {tuple(KERNELS)})")
            KERNELS[kernel].by_name(name)  # validates the lowering name
            forced[kernel] = name
        else:
            for spec_ in KERNELS.values():
                spec_.by_name(part)  # every kernel must register the name
            forced["*"] = part
    return forced


def negotiate(platform: str | None = None,
              override: str | None = None) -> LoweringPlan:
    """Probe the runtime and return an explicit :class:`LoweringPlan`.

    ``platform``: ``cpu`` | ``gpu`` | ``tpu`` (None = probe
    ``jax.default_backend()``).  ``override``: a ``REPRO_BACKEND``-style
    spec forcing lowerings (None = read the env var; "" = no override).
    Forced lowerings skip the platform predicate (that is the point of a
    forced-fallback run) but keep the ``xla`` reference as the terminal
    fallback for call sites the forced lowering cannot serve (non-pow2
    block dims).  A platform outside :data:`PLATFORMS` raises: serving it
    an all-``xla`` plan would hide the device behind the reference.
    """
    if platform is None:
        import jax

        platform = jax.default_backend()
    if platform not in PLATFORMS:
        raise ValueError(f"no lowering plan for platform {platform!r} "
                         f"(known: {PLATFORMS})")
    source = "negotiated"
    if override is None:
        override = os.environ.get(ENV_VAR, "")
        if override:
            source = f"env:{override}"
    elif override:
        source = f"override:{override}"
    forced = _parse_override(override) if override else {}

    chains: dict[str, tuple[Lowering, ...]] = {}
    for kname, spec in KERNELS.items():
        ref = spec.by_name("xla")
        force = forced.get(kname, forced.get("*"))
        if force is not None:
            head = spec.by_name(force)
            chain = (head,) if head is ref else (head, ref)
        else:
            chain = tuple(l for l in spec.lowerings
                          if platform in l.platforms and l is not ref)
            chain = chain + (ref,)
        chains[kname] = chain
    return LoweringPlan(platform=platform, chains=chains, source=source)


# ---------------------------------------------------------------------------
# the active plan (what kernel wrappers consult at trace time)
# ---------------------------------------------------------------------------

_STACK: list[LoweringPlan] = []
_DEFAULT: list[LoweringPlan | None] = [None]
_RECORDERS: list[list] = []


@contextlib.contextmanager
def record_selections() -> Iterator[list]:
    """Capture every ``(kernel, lowering_name)`` pair any plan's ``select``
    resolves while the scope is open.

    Kernel wrappers consult the plan in their Python dispatch layer (outside
    the inner jits), so tracing a stage under ``jax.eval_shape`` exercises
    exactly the selections that will serve it.  ``serve.schedule`` records
    the staged and fused traces separately and diffs the two sets to decide
    whether the fused pipeline is bit-equal to the staged one (identical
    selections, or diffs confined to ``exact`` lowerings) or only
    epsilon-equivalent — the negotiation behind ``StagedSchedule.fused_ok``.
    """
    rec: list = []
    _RECORDERS.append(rec)
    try:
        yield rec
    finally:
        # by identity: list.remove would drop the first *equal* recorder,
        # which may be an enclosing scope's
        _RECORDERS[:] = [r for r in _RECORDERS if r is not rec]


def get_plan() -> LoweringPlan:
    """The active plan: innermost :func:`use_plan` scope, else the
    process-default plan (negotiated lazily once; re-negotiated whenever
    ``REPRO_BACKEND`` changes so env-forced subprocess runs just work)."""
    if _STACK:
        return _STACK[-1]
    env = os.environ.get(ENV_VAR, "")
    cached = _DEFAULT[0]
    if cached is None or (env and cached.source != f"env:{env}") \
            or (not env and cached.source.startswith("env:")):
        _DEFAULT[0] = negotiate()
    return _DEFAULT[0]


@contextlib.contextmanager
def use_plan(plan: LoweringPlan) -> Iterator[LoweringPlan]:
    """Scope the active plan — ``serve.schedule`` wraps every compiled
    stage in this so each schedule's jaxprs trace under its own plan."""
    _STACK.append(plan)
    try:
        yield plan
    finally:
        _STACK.pop()


def active(kernel: str, *, size: int | None = None,
           dispatch: bool = False) -> Lowering:
    """``get_plan().select(...)`` — the one call every kernel wrapper makes."""
    return get_plan().select(kernel, size=size, dispatch=dispatch)


def replay_tolerance(recorded: Mapping[str, str],
                     replayed: Mapping[str, str]) -> float:
    """Numeric tolerance for diffing traffic served under two plans.

    0.0 when every kernel kept its lowering (the plans are equivalent —
    replay must be **bit-exact**); otherwise the max declared ``epsilon``
    over the kernels whose lowering changed (each side's class counts:
    swapping ``interpret`` for ``xla`` diffs at ``interpret``'s epsilon).
    Kernels absent from either map are treated as unchanged.
    """
    tol = 0.0
    for kernel, new in replayed.items():
        old = recorded.get(kernel, new)
        if old == new:
            continue
        spec = KERNELS[kernel]
        tol = max(tol, spec.by_name(old).epsilon, spec.by_name(new).epsilon)
    return tol
