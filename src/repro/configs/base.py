"""ArchSpec — the uniform adapter every assigned architecture implements.

The launcher, dry-run, trainer, and smoke tests all consume this interface;
adding an architecture = one config file defining an ArchSpec.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.shapes import ShapeSpec
from repro.nn import init as nninit


@dataclasses.dataclass(frozen=True)
class ArchSpec:
    id: str
    family: str                   # moe | dense | ssm | hybrid | vlm | audio
    kind: str                     # lm | rwkv | griffin | vlm | encdec
    make_full: Callable[[], Any]
    make_smoke: Callable[[], Any]
    supports_long: bool = False
    fsdp: bool = False            # shard the non-TP weight dim over data
    opt_8bit: bool = False        # quantized AdamW moments
    note: str = ""
    source: str = ""


def _mod(kind: str):
    if kind == "lm":
        from repro.models import lm as m
    elif kind == "rwkv":
        from repro.models import rwkv6 as m
    elif kind == "griffin":
        from repro.models import griffin as m
    elif kind == "vlm":
        from repro.models import vlm as m
    elif kind == "encdec":
        from repro.models import encdec as m
    else:
        raise ValueError(kind)
    return m


def model_spec(arch: ArchSpec, cfg):
    m = _mod(arch.kind)
    return {"lm": getattr(m, "lm_spec", None), "rwkv": getattr(m, "rwkv_spec", None),
            "griffin": getattr(m, "griffin_spec", None),
            "vlm": getattr(m, "vlm_spec", None),
            "encdec": getattr(m, "encdec_spec", None)}[arch.kind](cfg)


def loss_fn(arch: ArchSpec, cfg):
    m = _mod(arch.kind)
    return lambda params, batch: m.loss_fn(params, cfg, batch)


def _dm(cfg, kind: str) -> int:
    return cfg.lm.d_model if kind == "vlm" else cfg.d_model


def train_batch_specs(arch: ArchSpec, cfg, shape: ShapeSpec):
    """ShapeDtypeStructs for one global training batch."""
    b, s = shape.global_batch, shape.seq_len
    tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
    if arch.kind == "vlm":
        return {
            "patch_embeds": jax.ShapeDtypeStruct((b, cfg.n_img_tokens,
                                                  cfg.lm.d_model), jnp.bfloat16),
            "tokens": tok, "targets": tok,
        }
    if arch.kind == "encdec":
        half = s // 2
        return {
            "frames": jax.ShapeDtypeStruct((b, half, cfg.d_model), jnp.bfloat16),
            "tgt_tokens": jax.ShapeDtypeStruct((b, half), jnp.int32),
            "tgt_targets": jax.ShapeDtypeStruct((b, half), jnp.int32),
        }
    return {"tokens": tok, "targets": tok}


def prefill_fn(arch: ArchSpec, cfg):
    """Full-context forward returning last-token logits (inference-prefill)."""
    m = _mod(arch.kind)
    if arch.kind == "lm":
        def f(params, tokens):
            hidden, _ = m.forward(params, cfg, tokens)
            return m.lm_logits(params, cfg, hidden[:, -1:])[:, 0]
    elif arch.kind == "rwkv":
        def f(params, tokens):
            hidden = m.forward(params, cfg, tokens)
            from repro.nn import layers
            return layers.dense(params["head"], hidden[:, -1], cfg.compute_dtype)
    elif arch.kind == "griffin":
        def f(params, tokens):
            hidden = m.forward(params, cfg, tokens)
            from repro.nn import layers
            return layers.logits(params["embed"], hidden[:, -1], cfg.compute_dtype)
    elif arch.kind == "vlm":
        def f(params, batch):
            hidden, _ = m.forward(params, cfg, batch["patch_embeds"], batch["tokens"])
            from repro.models import lm as lmm
            return lmm.lm_logits(params, cfg.lm, hidden[:, -1:])[:, 0]
    else:  # encdec
        def f(params, frames):
            enc = m.encode(params, cfg, frames)
            from repro.nn import layers
            return jnp.mean(enc, axis=1)  # encoder summary (decoder starts empty)
    return f


def prefill_input_specs(arch: ArchSpec, cfg, shape: ShapeSpec):
    b, s = shape.global_batch, shape.seq_len
    if arch.kind == "vlm":
        return ({"patch_embeds": jax.ShapeDtypeStruct(
            (b, cfg.n_img_tokens, cfg.lm.d_model), jnp.bfloat16),
            "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)},)
    if arch.kind == "encdec":
        return (jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16),)
    return (jax.ShapeDtypeStruct((b, s), jnp.int32),)


def decode_fn(arch: ArchSpec, cfg):
    m = _mod(arch.kind)
    def f(params, caches, token, pos):
        return m.decode_step(params, cfg, caches, token, pos)
    return f


def decode_state_specs(arch: ArchSpec, cfg, shape: ShapeSpec):
    """(caches, token, pos) ShapeDtypeStructs for one decode step."""
    m = _mod(arch.kind)
    b, s = shape.global_batch, shape.seq_len
    if arch.kind == "rwkv":
        caches = m.state_shapes(cfg, b)
    elif arch.kind == "griffin":
        caches = m.state_shapes(cfg, b, s)
    elif arch.kind == "encdec":
        caches = m.cache_shapes(cfg, b, min(s, 4096), src_len=s)
    elif arch.kind == "vlm":
        caches = m.cache_shapes(cfg, b, s)
    else:
        caches = m.cache_shapes(cfg, b, s)
    token = jax.ShapeDtypeStruct((b,), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return caches, token, pos


def serve_fns(arch: ArchSpec, cfg, max_len: int):
    """(decode_step, init_caches) pair for the continuous-batching Engine.

    ``decode_step`` accepts a per-slot (B,) position vector (or a scalar);
    ``init_caches(batch)`` allocates zeroed decode state with ``max_len``
    KV capacity per slot. Stateful kinds (rwkv, griffin) carry O(1) or
    windowed state and ignore/modulo the position as appropriate; their
    cumulative state cannot absorb bucketed prefill pad steps, so
    ``init_caches`` is tagged ``stateful_prefill = True`` and the Engine
    forces exact-length prefill scans (no caller needs to re-derive the
    arch kind).
    """
    m = _mod(arch.kind)
    step = decode_fn(arch, cfg)
    if arch.kind == "lm":
        init = lambda batch: m.init_caches(cfg, batch, max_len)
    elif arch.kind == "rwkv":
        init = lambda batch: m.init_state(cfg, batch)
    elif arch.kind == "griffin":
        init = lambda batch: m.init_state(cfg, batch, max_len)
    else:
        raise NotImplementedError(
            f"{arch.kind}: serving needs non-token inputs (patch embeddings / "
            "encoder frames) — use the model module's encode/decode directly")
    init.stateful_prefill = arch.kind in ("rwkv", "griffin")
    return step, init


# ---------------------------------------------------------------------------
# NSAI reasoning traffic: the workload registry
# (serve.schedule.compile_schedule -> serve.reason.ReasonEngine)
# ---------------------------------------------------------------------------
#
# Each entry declares how a workload serves: its pipeline *stage functions*
# (jax-traceable, with nn/vsa/simd stream tags), the staged-batch input
# specs, the constants every stage receives, request ingest/collect
# adapters, and a synthetic-traffic generator.  ``compile_reason_schedule``
# lowers an entry to an executable ``StagedSchedule`` (tracing the composed
# stages with ``core.trace`` into the same DataflowGraph the DSE consumes),
# and the generic ``ReasonEngine`` runs it.  Adding a workload = one
# registry entry; the engine, launcher, examples and benchmarks all derive
# their model lists from ``REASON_WORKLOADS``.


@dataclasses.dataclass(frozen=True)
class ReasonWorkload:
    """Registry entry: everything a workload contributes to the serving path.

    - ``variants``: named pipeline variants (first = default).  RAVEN
      reasoners expose ``cnn`` (neural perception) and ``oracle``
      (ground-truth PMFs: symbolic-stream-only serving).
    - ``make_config(**kw)``: config from generic launcher knobs (``d``,
      ``nn_precision``, ``symb_precision``); inapplicable knobs ignored.
    - ``make_consts(cfg, key)``: the constant pytree handed to every stage
      (params / codebooks / binding keys).
    - ``stage_specs(cfg, variant)``: ordered ``StageSpec`` tuple.
    - ``input_specs(cfg, batch_size, variant)``: ShapeDtypeStruct pytree of
      one staged batch (stage 0's input).
    - ``ingest(cfg, variant)``: per-request host adapter -> input pytree.
    - ``collect(cfg)``: ``(host_out, i) -> ReasonResult fields`` adapter.
    - ``paper_graph()``: the published-scale ``OpGraph`` from
      ``core.workloads`` (None -> trace only), for the analytic side.
    - ``fused_stage_specs(cfg, variant)``: optional alternate stage list
      for the whole-pipeline fused jit (e.g. MIMONet's unbind+classify
      collapsed into the fused kernel); None -> the fused jit composes
      ``stage_specs`` as-is.
    - ``make_requests(cfg, n, seed)``: ``(stream_factory, truth)`` where
      ``stream_factory()`` yields requests lazily (rendering runs inside
      the pipeline) and ``truth()`` lazily materializes ground truth.
    - ``score(results, truth_values)``: serving accuracy.
    """

    name: str
    describe: str
    variants: tuple[str, ...]
    make_config: Callable[..., Any]
    make_consts: Callable[[Any, jax.Array], Any]
    stage_specs: Callable[[Any, str], tuple]
    input_specs: Callable[[Any, int, str], Any]
    ingest: Callable[[Any, str], Callable]
    collect: Callable[[Any], Callable]
    make_requests: Callable[[Any, int, int], tuple]
    score: Callable[[dict, Any], float]
    paper_graph: Callable[[], Any] | None = None
    fused_stage_specs: Callable[[Any, str], tuple] | None = None


def _require(req, field: str):
    val = getattr(req, field)
    if val is None:
        raise ValueError(f"needs ReasonRequest.{field}")
    return val


def _raven_ingest(cfg, variant: str) -> Callable:
    import numpy as np

    if variant == "oracle":
        return lambda r: (
            np.asarray(_require(r, "context_attrs"), np.int32),
            np.asarray(_require(r, "candidate_attrs"), np.int32))
    return lambda r: (
        np.asarray(_require(r, "context"), np.float32),
        np.asarray(_require(r, "candidates"), np.float32))


def _raven_collect(cfg) -> Callable:
    import numpy as np

    def collect(host_out, i):
        logp, posts = host_out  # (B, 8), (A, B, R)
        return {"answer": int(np.argmax(logp[i])), "answer_logprobs": logp[i],
                "rule_posteriors": posts[:, i]}

    return collect


def _raven_input_specs(cfg, batch_size: int, variant: str):
    hw = cfg.raven.image_size
    a = cfg.raven.n_attrs
    if variant == "oracle":
        spec = jax.ShapeDtypeStruct((batch_size, 8, a), jnp.int32)
    else:
        spec = jax.ShapeDtypeStruct((batch_size, 8, hw, hw, 1), jnp.float32)
    return (spec, spec)


def _raven_requests(cfg, n: int, seed: int):
    """Lazy RAVEN request stream + lazily-materialized answers.  Answers
    are captured as the stream is pulled, so scoring after a serve run
    costs no second render pass."""
    import numpy as np

    from repro.data import raven

    answers: dict[int, int] = {}

    def factory():
        from repro.serve.reason import ReasonRequest

        for i in range(n):
            p = raven.generate_problem(cfg.raven, seed=seed + i)
            answers[i] = int(p["answer"])
            yield ReasonRequest(
                uid=i, context=p["context"], candidates=p["candidates"],
                context_attrs=p["context_attrs"],
                candidate_attrs=p["candidate_attrs"])

    def truth():
        for i in range(n):  # only re-render what was never pulled
            if i not in answers:
                answers[i] = int(raven.generate_problem(
                    cfg.raven, seed=seed + i)["answer"])
        return np.array([answers[i] for i in range(n)])

    return factory, truth


def _mean_match_score(results: dict, truth_values) -> float:
    """Mean answer==truth (elementwise for per-channel answer arrays)."""
    import numpy as np

    return float(np.mean([results[i].answer == truth_values[i]
                          for i in range(len(truth_values))]))


def _nvsa_frontend_stage(cfg, consts_key: str = "params"):
    """Shared CNN perception stage (NVSA frontend; eval-mode BN, so a
    request's PMFs are independent of its admission group).  ``consts_key``
    selects the frontend params in the workload's consts pytree (LVRF
    carries them under ``"frontend"`` beside its learned rules)."""
    from repro.models import nvsa as nv
    from repro.serve.schedule import StageSpec

    def frontend(consts, bufs):
        ctx, cand = bufs
        n, _, h, w, c = ctx.shape
        p = consts[consts_key]
        ctx_p, _ = nv.frontend_pmfs(p, cfg, ctx.reshape(n * 8, h, w, c))
        cand_p, _ = nv.frontend_pmfs(p, cfg, cand.reshape(n * 8, h, w, c))
        return (tuple(x.reshape(n, 8, -1) for x in ctx_p),
                tuple(x.reshape(n, 8, -1) for x in cand_p))

    return StageSpec("frontend", "nn", frontend)


def _oracle_stage(cfg):
    """Ground-truth one-hot PMFs (perception bypass: symbolic-only serving)."""
    from repro.models import nvsa as nv
    from repro.serve.schedule import StageSpec

    def oracle(consts, bufs):
        ctx_attrs, cand_attrs = bufs
        return (tuple(nv.oracle_pmfs(cfg, ctx_attrs)),
                tuple(nv.oracle_pmfs(cfg, cand_attrs)))

    return StageSpec("oracle", "simd", oracle)


# -- nvsa -------------------------------------------------------------------


def _nvsa_config(d: int = 128, nn_precision: str = "fp32",
                 symb_precision: str = "fp32", **_):
    from repro.models import nvsa as nv

    return nv.NVSAConfig(d=d, nn_precision=nn_precision,
                         symb_precision=symb_precision,
                         use_qmatmul=nn_precision in ("int8", "int4"))


def _nvsa_consts(cfg, key):
    from repro.models import nvsa as nv
    from repro.nn import init as nninit

    k1, k2 = jax.random.split(key)
    return {"params": nninit.materialize(nv.nvsa_spec(cfg), k1),
            "books": nv.nvsa_codebooks(cfg, k2)}


def _nvsa_stages(cfg, variant: str):
    from repro.models import nvsa as nv
    from repro.serve.schedule import StageSpec

    def symbolic(consts, bufs):
        ctx_pmfs, cand_pmfs = bufs
        books = nv.quantize_codebooks(cfg, consts["books"])
        return nv.reason(cfg, books, list(ctx_pmfs), list(cand_pmfs))

    first = _oracle_stage(cfg) if variant == "oracle" \
        else _nvsa_frontend_stage(cfg)
    return (first, StageSpec("symbolic", "vsa", symbolic))


# -- prae -------------------------------------------------------------------


def _prae_stages(cfg, variant: str):
    # PrAE shares the CNN perception frontend (cfg is an NVSAConfig); its
    # symbolic engine is PMF-native — scatter/shift/reduce, SIMD-shaped
    from repro.models import prae as pr
    from repro.serve.schedule import StageSpec

    pcfg = pr.PrAEConfig(raven=cfg.raven)

    def symbolic(consts, bufs):
        ctx_pmfs, cand_pmfs = bufs
        return pr.solve_from_pmfs(pcfg, list(ctx_pmfs), list(cand_pmfs))

    first = _oracle_stage(cfg) if variant == "oracle" \
        else _nvsa_frontend_stage(cfg)
    return (first, StageSpec("symbolic", "simd", symbolic))


# -- mimonet ----------------------------------------------------------------


def _mimonet_config(d: int = 128, **_):
    from repro.models import mimonet as mm

    return mm.MIMONetConfig(d=d)


def _mimonet_consts(cfg, key):
    from repro.models import mimonet as mm
    from repro.nn import init as nninit

    k1, k2 = jax.random.split(key)
    return {"params": nninit.materialize(mm.mimonet_spec(cfg), k1),
            "keys": mm.mimonet_keys(cfg, k2)}


def _mimonet_stages(cfg, variant: str):
    from repro.models import mimonet as mm
    from repro.serve.schedule import StageSpec

    return (
        StageSpec("encode", "nn",
                  lambda c, images: mm.encode(c["params"], cfg, images)),
        StageSpec("superpose", "vsa",
                  lambda c, codes: mm.superpose(c["keys"], codes)),
        StageSpec("trunk", "nn",
                  lambda c, x: mm.trunk(c["params"], x)),
        StageSpec("unbind", "vsa",
                  lambda c, x: mm.unbind(c["keys"], cfg, x)),
        StageSpec("classify", "simd",
                  lambda c, u: mm.classify(c["params"], u)),
    )


def _mimonet_fused_stages(cfg, variant: str):
    """Fused-pipeline stage list: the symbolic tail (unbind -> classify)
    collapses into the registry's fused ``unbind_classify`` kernel — one
    launch instead of two.  Only the fused jit composes this list; the
    staged schedule keeps the 5-stage pipeline, and ``compile_schedule``
    proves the two traces' lowerings equivalent before the executor may
    substitute one for the other."""
    from repro.models import mimonet as mm
    from repro.serve.schedule import StageSpec

    return _mimonet_stages(cfg, variant)[:3] + (
        StageSpec("unbind_classify", "simd",
                  lambda c, x: mm.unbind_classify(c["params"], c["keys"],
                                                  cfg, x)),
    )


def _mimonet_input_specs(cfg, batch_size: int, variant: str):
    hw = cfg.raven.image_size
    return jax.ShapeDtypeStruct(
        (batch_size, cfg.n_channels, hw, hw, 1), jnp.float32)


def _mimonet_ingest(cfg, variant: str):
    import numpy as np

    return lambda r: np.asarray(_require(r, "images"), np.float32)


def _mimonet_collect(cfg):
    import numpy as np

    def collect(host_out, i):
        logits = host_out[i]  # (K, n_classes)
        shifted = logits - logits.max(-1, keepdims=True)
        logp = shifted - np.log(np.exp(shifted).sum(-1, keepdims=True))
        return {"answer": np.argmax(logits, -1), "answer_logprobs": logp,
                "rule_posteriors": None}

    return collect


def _mimonet_requests(cfg, n: int, seed: int):
    """K-channel superposed-classification traffic from rendered RAVEN
    panels; truth = per-channel shape-type labels, captured alongside the
    rendered panels (no second render pass at scoring time)."""
    from repro.data import raven

    k = cfg.n_channels
    cache: dict = {}

    def _panels():
        if not cache:
            # 16 rendered panels per problem (8 ctx + 8 cand)
            probs = (n * k + 15) // 16
            cache["imgs"], cache["attrs"] = raven.panel_dataset(
                cfg.raven, seed=seed, n_problems=probs)
        return cache["imgs"], cache["attrs"]

    def factory():
        from repro.serve.reason import ReasonRequest

        imgs, _ = _panels()
        for i in range(n):
            yield ReasonRequest(uid=i, images=imgs[i * k:(i + 1) * k])

    def truth():
        _, attrs = _panels()
        return attrs[: n * k, 0].reshape(n, k)  # attr 0 = shape type

    return factory, truth


# -- lvrf -------------------------------------------------------------------


def _lvrf_config(d: int = 128, **_):
    from repro.models import lvrf as lv

    return lv.LVRFConfig(d=d)


def _lvrf_frontend_cfg(cfg):
    """NVSA-frontend config for LVRF's CNN perception (shared ResNet
    frontend; the symbolic side is LVRF's learned rules)."""
    from repro.models import nvsa as nv

    return nv.NVSAConfig(raven=cfg.raven)


def _lvrf_consts(cfg, key):
    from repro.models import lvrf as lv
    from repro.models import nvsa as nv
    from repro.nn import init as nninit

    k1, k2, k3 = jax.random.split(key, 3)
    fcfg = _lvrf_frontend_cfg(cfg)
    return {"params": nninit.materialize(lv.lvrf_spec(cfg), k1),
            "books": lv.lvrf_codebooks(cfg, k2),
            "frontend": nninit.materialize(nv.nvsa_spec(fcfg), k3)}


def _lvrf_stages(cfg, variant: str):
    from repro.models import lvrf as lv
    from repro.serve.schedule import StageSpec

    def abduce(consts, bufs):
        ctx_pmfs, cand_pmfs = bufs
        codes = lv.encode_codes(consts["books"], cfg, list(ctx_pmfs))
        posts = lv.abduce(consts["params"], cfg, codes)
        return (codes, posts, cand_pmfs)

    def execute(consts, bufs):
        codes, posts, cand_pmfs = bufs
        logp = lv.execute(consts["params"], consts["books"], cfg, codes,
                          posts, list(cand_pmfs))
        return (logp, posts)

    first = _oracle_stage(cfg) if variant == "oracle" \
        else _nvsa_frontend_stage(_lvrf_frontend_cfg(cfg),
                                  consts_key="frontend")
    return (first, StageSpec("abduce", "vsa", abduce),
            StageSpec("execute", "vsa", execute))


def _paper_graph(name: str):
    def build():
        from repro.core import workloads

        return workloads.WORKLOADS[name]()

    return build


REASON_WORKLOADS: dict[str, ReasonWorkload] = {
    "nvsa": ReasonWorkload(
        name="nvsa",
        describe="NVSA: ResNet perception -> FPE/VSA rule abduction -> "
                 "circ-conv rule execution (RAVEN)",
        variants=("cnn", "oracle"),
        make_config=_nvsa_config, make_consts=_nvsa_consts,
        stage_specs=_nvsa_stages, input_specs=_raven_input_specs,
        ingest=_raven_ingest, collect=_raven_collect,
        make_requests=_raven_requests, score=_mean_match_score,
        paper_graph=_paper_graph("nvsa")),
    "prae": ReasonWorkload(
        name="prae",
        describe="PrAE: shared CNN perception -> PMF-table abduction/"
                 "execution (SIMD-shaped symbolic stream)",
        variants=("cnn", "oracle"),
        make_config=_nvsa_config, make_consts=_nvsa_consts,
        stage_specs=_prae_stages, input_specs=_raven_input_specs,
        ingest=_raven_ingest, collect=_raven_collect,
        make_requests=_raven_requests, score=_mean_match_score),
    "mimonet": ReasonWorkload(
        name="mimonet",
        describe="MIMONet: K-channel superposed classification — bind -> "
                 "shared NN trunk -> unbind/classify",
        variants=("default",),
        make_config=_mimonet_config, make_consts=_mimonet_consts,
        stage_specs=_mimonet_stages, input_specs=_mimonet_input_specs,
        ingest=_mimonet_ingest, collect=_mimonet_collect,
        make_requests=_mimonet_requests, score=_mean_match_score,
        paper_graph=_paper_graph("mimonet"),
        fused_stage_specs=_mimonet_fused_stages),
    "lvrf": ReasonWorkload(
        name="lvrf",
        describe="LVRF: frontend -> learned-rule posterior -> posterior-"
                 "weighted circ-conv execution (RAVEN)",
        variants=("cnn", "oracle"),
        make_config=_lvrf_config, make_consts=_lvrf_consts,
        stage_specs=_lvrf_stages, input_specs=_raven_input_specs,
        ingest=_raven_ingest, collect=_raven_collect,
        make_requests=_raven_requests, score=_mean_match_score,
        paper_graph=_paper_graph("lvrf")),
}

# model lists everywhere (launcher --model choices, examples, benchmarks)
# derive from the registry — adding a workload is one entry above
REASON_MODELS = tuple(REASON_WORKLOADS)


def compile_reason_schedule(model: str, cfg, variant: str | None = None,
                            consts=None,
                            batch_size: int | tuple[int, ...] = 4,
                            trace_graph: bool = True, plan=None,
                            fused: bool | str = "auto"):
    """Lower one registry entry to an executable ``StagedSchedule``.

    ``consts`` may be the real constant pytree (params/codebooks) or None —
    then the entry's ``make_consts`` is abstractly evaluated for shapes
    only (nothing is materialized).  The compiled schedule carries the
    inter-stage buffer specs and the DataflowGraph traced from the composed
    stages (``trace_graph=False`` skips tracing for fast construction).

    ``batch_size`` may be a tuple of batch-size buckets (e.g. ``(1, 2,
    4, 8)``): the schedule's ``input_specs``/buffers describe the largest,
    and the engine pads a partial admission group to the smallest covering
    bucket instead of the max.

    ``plan``: a :class:`~repro.backend.registry.LoweringPlan` to compile
    under (None = the active plan); recorded on the schedule.

    ``fused``: forwarded to ``compile_schedule`` ("auto" also compiles the
    whole-pipeline fused jit and negotiates its equivalence class; the
    entry's ``fused_stage_specs``, when declared, supplies the fused-only
    stage list, e.g. the ``unbind_classify`` kernel).
    """
    from repro.serve import schedule as sch

    if model not in REASON_WORKLOADS:
        raise KeyError(f"unknown reasoning workload {model!r}; "
                       f"available: {tuple(REASON_WORKLOADS)}")
    entry = REASON_WORKLOADS[model]
    variant = variant or entry.variants[0]
    if variant not in entry.variants:
        raise KeyError(f"{model}: unknown variant {variant!r}; "
                       f"available: {entry.variants}")
    if consts is None:
        consts = jax.eval_shape(lambda k: entry.make_consts(cfg, k),
                                jax.random.PRNGKey(0))
    buckets = tuple(sorted(set(batch_size))) \
        if isinstance(batch_size, (tuple, list)) else ()
    max_batch = buckets[-1] if buckets else batch_size
    fused_stages = entry.fused_stage_specs(cfg, variant) \
        if entry.fused_stage_specs is not None else None
    return sch.compile_schedule(
        model, entry.stage_specs(cfg, variant),
        entry.ingest(cfg, variant), entry.collect(cfg), variant=variant,
        consts=consts,
        input_specs=entry.input_specs(cfg, max_batch, variant),
        trace_graph=trace_graph, batch_buckets=buckets, plan=plan,
        fused=fused, fused_stages=fused_stages)


def reason_engine(model: str, cfg, reason_cfg=None, consts=None,
                  variants: tuple[str, ...] | None = None,
                  trace_graph: bool = True, plan=None,
                  fused: bool | str = "auto", device=None):
    """Compile all (or the given) variants of a workload and wrap them in
    the generic N-stage ``ReasonEngine``.  ``reason_cfg.buckets`` (when
    set) compiles every variant with that tuple of batch-size buckets.
    ``consts`` (the workload's constant pytree) is bound onto the engine,
    which therefore implements the consts-free runtime protocol; with
    ``consts=None`` the schedules compile against abstract shapes and the
    engine can only be inspected, not served.  ``fused`` goes to
    :func:`compile_reason_schedule`; ``device`` is where the engine stages
    its inputs (None = the default device)."""
    from repro.serve.reason import ReasonConfig, ReasonEngine

    entry = REASON_WORKLOADS.get(model)
    if entry is None:
        raise KeyError(f"unknown reasoning workload {model!r}; "
                       f"available: {tuple(REASON_WORKLOADS)}")
    reason_cfg = reason_cfg or ReasonConfig()
    schedules = {
        v: compile_reason_schedule(
            model, cfg, variant=v, consts=consts,
            batch_size=reason_cfg.buckets or reason_cfg.batch_size,
            trace_graph=trace_graph, plan=plan, fused=fused)
        for v in (variants or entry.variants)}
    return ReasonEngine(schedules, reason_cfg, consts=consts, device=device)


def reason_engine_pool(model: str, cfg, reason_cfg=None, consts=None,
                       variants: tuple[str, ...] | None = None,
                       replicas: int = 1, trace_graph: bool = False,
                       plan=None, fused: bool | str = "auto"):
    """``replicas`` data-parallel :func:`reason_engine` copies behind one
    :class:`~repro.serve.replica.ReplicaPool`.

    Each replica gets the *same* constants (bit-identical answers
    whichever replica serves a request) ``jax.device_put`` onto its own
    device — ``jax.devices()[i % ndev]`` — and stages its inputs there,
    so jit executions of different
    replicas land on different devices and overlap (fake host devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` work the same
    way).  All replicas share ONE compiled schedule dict: stage jit caches
    live on the ``StagedSchedule``, so the pipeline compiles once per
    device, not once per replica.  ``replicas=1`` returns the bare engine
    (no pool indirection on the single-replica path)."""
    import dataclasses as _dc

    import jax as _jax

    from repro.serve.reason import ReasonConfig, ReasonEngine
    from repro.serve.replica import ReplicaPool

    if replicas < 1:
        raise ValueError(f"replicas must be >= 1, got {replicas}")
    reason_cfg = reason_cfg or ReasonConfig()
    if replicas == 1:
        return reason_engine(model, cfg, reason_cfg, consts=consts,
                             variants=variants, trace_graph=trace_graph,
                             plan=plan, fused=fused)
    if consts is None:
        raise ValueError("a replica pool needs real consts (answers must "
                         "be replica-invariant, so every replica binds the "
                         "same materialized constants)")
    devs = _jax.devices()
    engines = []
    schedules = None
    for i in range(replicas):
        dev = devs[i % len(devs)]
        c = _jax.device_put(consts, dev)
        rcfg = _dc.replace(reason_cfg)
        if schedules is None:
            eng = reason_engine(model, cfg, rcfg, consts=c,
                                variants=variants, trace_graph=trace_graph,
                                plan=plan, fused=fused, device=dev)
            schedules = eng.schedules
        else:
            eng = ReasonEngine(schedules, rcfg, consts=c, device=dev)
        engines.append(eng)
    return ReplicaPool(engines)


def lm_engine(arch_id: str, cfg, serve_cfg=None, key=None, tp: int = 1,
              device=None):
    """Materialize arch ``arch_id`` at model config ``cfg`` (its
    ``make_smoke()`` or ``make_full()``) and wrap it in the slot-pool LM
    ``Engine`` with params bound — the LM counterpart of
    :func:`reason_engine`, so both engine classes come out implementing
    the unified runtime protocol.  Returns ``(engine, model_cfg)``
    (callers need ``model_cfg.vocab`` to build token traffic).

    ``tp > 1`` binds the params tensor-parallel over a ``(data=1,
    model=tp)`` host mesh through ``distributed.sharding_rules``
    (``TP_RULES`` with the ``FALLBACK_TP_AXES`` escape for shapes whose
    preferred axis does not divide; the fallback size floor is disabled so
    smoke-scale params shard too).  The engine itself is unchanged: its
    jits follow the committed param shardings, so decode runs SPMD over
    the mesh — and stays token-for-token identical to single-device
    (greedy argmax over ulp-level psum reordering; regression-tested).
    Needs ``tp <= jax.device_count()`` (fake host devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).

    ``device`` pins the (unsharded) params, and the engine's KV caches and
    decode inputs, onto one device — the data-parallel replica path
    (mutually exclusive with ``tp > 1``)."""
    import jax as _jax

    from repro.configs import ARCHS
    from repro.serve.engine import Engine, ServeConfig

    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if tp > 1 and device is not None:
        raise ValueError("pass tp= (tensor-parallel) or device= (replica "
                         "placement), not both")
    arch = ARCHS[arch_id]
    serve_cfg = serve_cfg or ServeConfig()
    spec = model_spec(arch, cfg)
    params = nninit.materialize(spec,
                                key if key is not None
                                else _jax.random.PRNGKey(0))
    if tp > 1:
        if tp > len(_jax.devices()):
            raise ValueError(
                f"tp={tp} exceeds jax.device_count()={len(_jax.devices())} "
                "— on CPU, fake a mesh with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={tp}")
        from repro.distributed import sharding_rules as sr
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(data=1, model=tp)
        shardings = sr.param_shardings(spec, mesh, fsdp=arch.fsdp,
                                       min_shard_elems=0)
        params = _jax.tree.map(_jax.device_put, params, shardings)
    elif device is not None:
        params = _jax.device_put(params, device)
    step, init_caches = serve_fns(arch, cfg, max_len=serve_cfg.max_len)
    return Engine(step, init_caches, serve_cfg, params=params,
                  device=device), cfg


def lm_engine_pool(arch_id: str, cfg, serve_cfg=None, key=None,
                   replicas: int = 1, tp: int = 1):
    """``replicas`` data-parallel LM engines behind one ``ReplicaPool``
    (each replica's params on its own device, same PRNG key so token
    streams are replica-invariant), or a single (optionally
    tensor-parallel) engine when ``replicas == 1``.  Returns ``(engine,
    model_cfg)`` like :func:`lm_engine`."""
    import jax as _jax

    from repro.serve.replica import ReplicaPool

    if replicas < 1:
        raise ValueError(f"replicas must be >= 1, got {replicas}")
    if replicas > 1 and tp > 1:
        raise ValueError(
            f"replicas={replicas} with tp={tp}: combined data x tensor "
            "parallel LM serving is not wired up — pick one axis")
    if replicas == 1:
        return lm_engine(arch_id, cfg, serve_cfg, key=key, tp=tp)
    devs = _jax.devices()
    engines = []
    for i in range(replicas):
        eng, cfg = lm_engine(arch_id, cfg, serve_cfg, key=key,
                             device=devs[i % len(devs)])
        engines.append(eng)
    return ReplicaPool(engines), cfg


def param_count(arch: ArchSpec, cfg) -> int:
    return nninit.param_count(model_spec(arch, cfg))


def active_param_count(arch: ArchSpec, cfg) -> int:
    """MoE-aware active parameters per token (for MODEL_FLOPS = 6·N_active·D)."""
    import numpy as np

    spec = model_spec(arch, cfg)
    moe_cfg = getattr(cfg, "moe", None)
    if moe_cfg is None:
        return nninit.param_count(spec)
    total = 0
    for p in jax.tree.leaves(spec, is_leaf=lambda x: isinstance(x, nninit.P)):
        n = int(np.prod(p.shape))
        if "experts" in p.axes:  # routed-expert weight: top_k of E active
            n = n * moe_cfg.top_k // moe_cfg.n_experts
        total += n
    return total
