"""Slot-based continuous-batching serving engine.

The engine owns a fixed pool of ``max_slots`` KV-cache slots sized for
``max_len`` tokens each. Requests wait in a FIFO queue and are admitted the
moment a slot frees up (continuous batching): admission runs a ragged,
padding-masked prefill for the whole admission group at once, then the decode
loop resumes with every live slot at its own position — the per-slot ``pos``
vector is threaded through ``decode_step`` (see ``nn.attention.decode_step``).

Decode dispatches ``decode_block`` tokens per XLA call via ``jax.lax.scan``
(the seed engine paid one dispatch per token, which on CPU/accelerator alike
is dominated by launch overhead). Inside the scan each slot samples with
temperature / top-k from its own PRNG stream, emits EOS, retires early, and
keeps emitting ``pad_id`` until the block ends; retired slots are refilled
from the queue at the next block boundary.

This is the NSFlow inter-loop overlap story mapped onto serving: admission
(prefill) of waiting requests and decode of resident requests are disjoint
compute streams scheduled back-to-back over one shared slot pool.

The engine implements the unified :class:`~repro.serve.runtime.
EngineProtocol` natively — model parameters are bound at construction, so
callers schedule *traffic*, not model state:

- ``submit(group)`` dispatches one admission group: requests join the FIFO
  queue and free slots are prefilled immediately (the group's
  :class:`~repro.serve.runtime.GroupRecord` gets ``dispatch_t`` stamped at
  the prefill of its first admitted request).
- ``drain_ready()`` advances bounded work — one decode block, with freed
  slots refilled at the boundary — and hands out whatever requests have
  finished (``{uid: Result}``).  The front-door calls it while it would
  otherwise sleep waiting for traffic, which is how decode makes progress
  between arrivals in the single-threaded serve loop.
- ``drain_all()`` runs queue + resident slots to completion.
- ``run(requests)`` is the offline loop over the three calls above
  (admission groups of ``admission_cap``, then drain everything) — token
  streams are byte-identical to serving the same uids online because
  sampling is keyed by (seed, uid, token index), never by slot, admission
  order, or co-residents.

Stats are split so jit warmup cannot pollute throughput numbers: a run that
compiled a new shape (the first decode block, a new padded prefill length)
is accounted under ``stats["warmup"]``, steady-state runs under
``stats["measured"]`` (which ``tokens_per_s()`` reports), with per-run
records in ``engine.runs`` — mirroring ``ReasonEngine``.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve import runtime as rt
from repro.serve.runtime import GroupRecord


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32      # default per-request generation budget
    temperature: float = 0.0      # 0 = greedy, > 0 = categorical sampling
    top_k: int | None = None      # restrict sampling to the k best logits
    eos_id: int | None = None     # stop + retire the slot when sampled
    pad_id: int = 0               # emitted by retired slots after EOS
    max_slots: int = 4            # KV slot pool size == decode batch
    max_len: int = 128            # per-slot KV capacity (prompt + new tokens)
    decode_block: int = 8         # tokens fused into one scan dispatch
    prefill_bucket: int = 16      # pad prompt scans to a multiple of this
    # Sampling PRNG: every request gets its own stream derived from
    # (seed, uid), and each token folds in a per-request counter — so the
    # tokens a request samples depend only on (seed, uid, prompt), never on
    # which slot it landed in, which requests are co-resident, or the
    # admission order. Engine.run is therefore submission-order invariant.
    seed: int = 0
    # Positional KV caches (linear and ring-buffer/windowed alike) tolerate
    # ragged padded prefill: per-slot positions are clamped to the prompt
    # length, so pad steps only rewrite the one entry at position plen,
    # which the first decode step overwrites before attending. One bucketed
    # scan therefore serves the whole admission group. Cumulative recurrent
    # state (rwkv wkv, griffin lru/conv) would still be corrupted by the
    # extra pad steps — set True to prefill each distinct prompt length with
    # an exact-length scan instead (more dispatches, state-safe).
    stateful_prefill: bool = False


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                 # (P,) int32
    max_new_tokens: int | None = None  # falls back to ServeConfig default
    # traffic class for overload control (see serve.slo.PRIORITIES);
    # the engine ignores it — the front-door sheds and orders by it
    priority: str = "standard"


@dataclasses.dataclass
class Result:
    uid: int
    tokens: np.ndarray        # generated ids, EOS included when hit
    prompt_len: int
    finished_by_eos: bool
    slot: int                 # which slot served the request


@dataclasses.dataclass
class _Slot:
    request: Request | None = None
    tokens: list = dataclasses.field(default_factory=list)
    budget: int = 0
    served: int = 0           # requests completed by this slot (reuse stat)


def _fresh_stats(max_slots: int) -> dict:
    return {
        "requests": 0, "tokens": 0, "decode_blocks": 0,
        "slot_steps": 0, "active_slot_steps": 0, "prefills": 0,
        "decode_time_s": 0.0, "wall_time_s": 0.0,
        "slots_served": [0] * max_slots,
        # wall-time split: runs that compiled a new shape (first decode
        # block, new padded prefill length) land in "warmup", steady-state
        # runs in "measured" (``work`` == generated tokens for LM traffic)
        **rt.fresh_split_stats(),
    }


class Engine:
    """Continuous-batching generation over an arch adapter's decode_step.

    ``decode_step(params, caches, token (B,), pos (B,)) -> (caches, logits)``
    must accept a per-slot position vector. ``init_caches(batch)`` allocates
    a zeroed cache pytree whose leaves carry a batch axis; for positional KV
    caches its per-slot capacity must be at least ``cfg.max_len`` (the engine
    cannot see the length axis generically — ``configs.base.serve_fns`` takes
    the same ``max_len``, pass one value to both).

    ``params`` is the model's parameter pytree, bound at construction so the
    engine implements the params-free :class:`~repro.serve.runtime.
    EngineProtocol` (``configs.base.lm_engine`` binds it for you).  ``clock``
    is the timestamp source for :class:`~repro.serve.runtime.GroupRecord`
    stamps (the front-door injects its own so queue/service latencies share
    one origin); ``wall`` is the real wall-clock the throughput accounting
    reads — separate so a virtual front-door clock never distorts measured
    rates, injectable so the accounting itself is testable.  ``device``
    is where the KV caches and per-block inputs live (None = the default
    device); a replica passes the device its params were put on.
    """

    def __init__(self, decode_step: Callable, init_caches: Callable,
                 cfg: ServeConfig, params=None, clock=time.perf_counter,
                 wall=time.perf_counter, device=None):
        # configs.base.serve_fns tags init_caches for archs whose cumulative
        # recurrent state would be silently corrupted by bucketed pad steps —
        # honor the tag so no caller has to remember to set the flag
        if getattr(init_caches, "stateful_prefill", False) \
                and not cfg.stateful_prefill:
            cfg = dataclasses.replace(cfg, stateful_prefill=True)
        self.cfg = cfg
        self.init_caches = init_caches
        self.params = params
        self.device = device
        self.clock = clock
        self.wall = wall
        self._raw_decode_step = decode_step
        # batch axis per cache leaf: the one axis whose size tracks `batch`
        # (probed at 2 vs 1 so any max_slots >= 1 works)
        big = jax.eval_shape(lambda: init_caches(2))
        small = jax.eval_shape(lambda: init_caches(1))

        def batch_axis(path, a, b):
            for i, (x, y) in enumerate(zip(a.shape, b.shape)):
                if x != y:
                    return i
            raise ValueError(
                f"cache leaf {jax.tree_util.keystr(path)} has shape {a.shape} "
                "at any batch size — every leaf needs an axis that tracks the "
                "slot count (shared/global state is unsupported)")

        self._batch_axes = jax.tree_util.tree_map_with_path(batch_axis,
                                                            big, small)

        self._decode_block = jax.jit(self._make_decode_block(),
                                     donate_argnums=(1,))
        self._prefill = jax.jit(self._make_prefill(), donate_argnums=(1,))
        # donating the pool lets XLA update admitted rows in place instead of
        # copying the whole KV pool per admission (leaves whose batch axis is
        # not leading may still warn as non-donatable; that's benign)
        self._merge = jax.jit(self._make_merge(), donate_argnums=(0,))
        self._sample_jit = jax.jit(self._sample)
        self.stats = _fresh_stats(cfg.max_slots)
        self.runs: list[dict] = []    # per-run records from run()
        # protocol state: FIFO queue, lazily-allocated slot pool, finished
        # results awaiting a drain call, and open (undrained) group records
        self._queue: collections.deque = collections.deque()
        self._slots = [_Slot() for _ in range(cfg.max_slots)]
        self._caches = None           # allocated on first submit
        self._state: dict | None = None
        self._ready: dict[int, Result] = {}
        self._resident: set[int] = set()   # queued or slot-resident uids
        self._open: list[GroupRecord] = []
        self._rec_left: dict[int, int] = {}    # rec.index -> unfinished uids
        self._uid_rec: dict[int, GroupRecord] = {}
        self._next_index = 0
        self._warmed: set = set()     # compiled shapes (prefill len, decode)
        self._cold_run = False

    # -- device-side pieces -------------------------------------------------

    def _sample(self, logits: jax.Array, keys: jax.Array) -> jax.Array:
        """Greedy when temperature == 0, else per-slot top-k categorical.

        ``keys``: (B, 2) uint32 — one PRNG key per slot, already folded with
        the request's token counter (per-request streams, see ServeConfig).
        """
        cfg = self.cfg
        if cfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits.astype(jnp.float32) / cfg.temperature
        if cfg.top_k is not None:
            k = min(cfg.top_k, scaled.shape[-1])
            kth = jax.lax.top_k(scaled, k)[0][..., -1:]
            scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
        return jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)

    def _request_key(self, uid: int) -> np.ndarray:
        """Per-request PRNG stream root: fold the uid into the engine seed.

        Folded in two 32-bit halves so uids differing anywhere in their low
        64 bits (incl. the sign bit) get distinct streams.
        """
        key = jax.random.PRNGKey(self.cfg.seed)
        key = jax.random.fold_in(key, np.uint32(uid & 0xFFFFFFFF))
        return np.asarray(
            jax.random.fold_in(key, np.uint32((uid >> 32) & 0xFFFFFFFF)))

    def _make_prefill(self):
        """Ragged-prompt prefill: (B, P) right-padded tokens + (B,) lengths.

        Scans the prompt through decode_step to populate a scratch cache.
        Per-slot positions are clamped to the prompt length, so every pad
        step past a slot's length rewrites the single cache entry at
        position ``plen`` — the first decode step (also at ``plen``) then
        overwrites it with real K/V before attending. Unclamped positions
        would march past ``plen`` and, on ring-buffer (sliding-window) KV
        caches, wrap around and clobber real entries whenever the padded
        scan length exceeds the window. Returns (caches, last-real-token
        logits per slot).
        """
        decode_step = self._raw_decode_step

        def prefill(params, caches, tokens, plens):
            def step(caches, inp):
                tok_t, t = inp
                pos = jnp.minimum(t, plens)  # (B,): freeze pad steps at plen
                caches, logits = decode_step(params, caches, tok_t, pos)
                return caches, logits

            positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
            caches, logits = jax.lax.scan(step, caches, (tokens.T, positions))
            # logits: (P, B, V) -> last real prompt token's logits per slot
            idx = jnp.clip(plens - 1, 0, tokens.shape[1] - 1)
            last = jnp.take_along_axis(
                logits, idx[None, :, None], axis=0)[0]
            return caches, last

        return prefill

    def _make_merge(self):
        """Copy admitted slots' rows from scratch caches into the pool."""
        batch_axes = self._batch_axes

        def merge(pool, scratch, admit_mask):
            def one(axis, dst, src):
                shape = [1] * dst.ndim
                shape[axis] = dst.shape[axis]
                return jnp.where(admit_mask.reshape(shape), src, dst)

            return jax.tree.map(one, batch_axes, pool, scratch)

        return merge

    def _make_decode_block(self):
        cfg = self.cfg
        decode_step = self._raw_decode_step
        eos = cfg.eos_id

        def block(params, caches, tok, pos, active, budget, keys, gen):
            def step(carry, _):
                caches, tok, pos, active, budget, gen = carry
                caches, logits = decode_step(params, caches, tok, pos)
                sub = jax.vmap(jax.random.fold_in)(keys, gen)
                nxt = self._sample(logits, sub)
                emit = jnp.where(active, nxt, cfg.pad_id)
                pos = jnp.where(active, pos + 1, pos)
                gen = jnp.where(active, gen + 1, gen)
                budget = jnp.where(active, budget - 1, budget)
                alive = active & (budget > 0) & (pos < cfg.max_len)
                if eos is not None:
                    alive = alive & (emit != eos)
                return (caches, emit, pos, alive, budget, gen), (emit, active)

            carry = (caches, tok, pos, active, budget, gen)
            carry, (toks, valid) = jax.lax.scan(step, carry, None,
                                                length=cfg.decode_block)
            caches, tok, pos, active, budget, gen = carry
            return caches, tok, pos, active, budget, gen, toks, valid

        return block

    # -- host-side scheduling ----------------------------------------------

    def _budget(self, req: Request) -> int:
        cfg = self.cfg
        return (req.max_new_tokens if req.max_new_tokens is not None
                else cfg.max_new_tokens)

    def _validate(self, req: Request):
        plen, budget = len(np.asarray(req.prompt).reshape(-1)), self._budget(req)
        if plen == 0:
            raise ValueError(f"request {req.uid}: empty prompt")
        if budget < 1:
            raise ValueError(f"request {req.uid}: max_new_tokens must be >= 1")
        if plen + budget > self.cfg.max_len:
            raise ValueError(
                f"request {req.uid}: prompt {plen} + budget {budget} "
                f"exceeds max_len {self.cfg.max_len}")

    def _put(self, x):
        """Host array -> the engine's device."""
        return jax.device_put(x, self.device)

    def _new_caches(self):
        """A zeroed cache pool, allocated on the engine's device."""
        with jax.default_device(self.device):
            return self.init_caches(self.cfg.max_slots)

    def _ensure_pool(self):
        if self._caches is None:
            cfg = self.cfg
            self._caches = self._new_caches()
            self._state = {
                "tok": np.full((cfg.max_slots,), cfg.pad_id, np.int32),
                "pos": np.zeros((cfg.max_slots,), np.int32),
                "active": np.zeros((cfg.max_slots,), bool),
                "budget": np.zeros((cfg.max_slots,), np.int32),
                # per-slot PRNG stream roots (keyed by the resident
                # request's uid) + per-request token counters — see
                # ServeConfig.seed
                "keys": np.zeros((cfg.max_slots, 2), np.uint32),
                "gen": np.zeros((cfg.max_slots,), np.int32),
            }

    def _active(self) -> bool:
        return self._state is not None and bool(self._state["active"].any())

    def _admit(self):
        """Fill free slots from the queue with one ragged batched prefill."""
        cfg = self.cfg
        slots, state = self._slots, self._state
        free = [i for i, s in enumerate(slots) if s.request is None]
        if not free or not self._queue:
            return
        group = []
        while free and self._queue:
            group.append((free.pop(0), self._queue.popleft()))
        for slot_idx, req in group:
            slots[slot_idx].request = req
            slots[slot_idx].tokens = []
            slots[slot_idx].budget = self._budget(req)

        if cfg.stateful_prefill:
            # one exact-length scan per distinct prompt length (state-safe)
            by_len: dict[int, list] = {}
            for slot_idx, req in group:
                by_len.setdefault(len(req.prompt), []).append((slot_idx, req))
            plan = [(items, length) for length, items in sorted(by_len.items())]
        else:
            plen_max = max(len(r.prompt) for _, r in group)
            bucket = cfg.prefill_bucket
            plan = [(group, -(-plen_max // bucket) * bucket)]

        for items, padded in plan:
            shape_key = ("prefill", padded)
            if shape_key not in self._warmed:
                self._warmed.add(shape_key)
                self._cold_run = True
            tokens = np.full((cfg.max_slots, padded), cfg.pad_id, np.int32)
            plens = np.zeros((cfg.max_slots,), np.int32)
            admit = np.zeros((cfg.max_slots,), bool)
            for slot_idx, req in items:
                p = np.asarray(req.prompt, np.int32).reshape(-1)
                tokens[slot_idx, : len(p)] = p
                plens[slot_idx] = len(p)
                admit[slot_idx] = True
                # the group's first work hits the device here
                rec = self._uid_rec.get(req.uid)
                if rec is not None and rec.dispatch_t is None:
                    rec.dispatch_t = self.clock()

            scratch, last_logits = self._prefill(self.params,
                                                 self._new_caches(),
                                                 self._put(tokens),
                                                 self._put(plens))
            self._caches = self._merge(self._caches, scratch,
                                       self._put(admit))
            self.stats["prefills"] += 1

            # first token: sample from each admitted request's own stream at
            # counter 0 (non-admitted rows are computed but never read)
            for slot_idx, req in items:
                state["keys"][slot_idx] = self._request_key(req.uid)
                state["gen"][slot_idx] = 0
            sub = jax.vmap(jax.random.fold_in)(self._put(state["keys"]),
                                               self._put(state["gen"]))
            first = np.asarray(self._sample_jit(last_logits, sub))
            for slot_idx, req in items:
                state["tok"][slot_idx] = first[slot_idx]
                state["pos"][slot_idx] = plens[slot_idx]
                state["active"][slot_idx] = True
                state["budget"][slot_idx] = slots[slot_idx].budget
                state["gen"][slot_idx] = 1
            # a first token can already finish the request (EOS / budget 1)
            for slot_idx, req in items:
                self._push_token(slot_idx, int(first[slot_idx]))

    def _push_token(self, i: int, token: int):
        """Record one generated token; retire the slot when done."""
        cfg = self.cfg
        slot, state = self._slots[i], self._state
        slot.tokens.append(token)
        state["budget"][i] -= 1
        hit_eos = cfg.eos_id is not None and token == cfg.eos_id
        if hit_eos or state["budget"][i] <= 0:
            req = slot.request
            self._ready[req.uid] = Result(
                uid=req.uid, tokens=np.asarray(slot.tokens, np.int32),
                prompt_len=len(req.prompt), finished_by_eos=hit_eos, slot=i)
            self.stats["requests"] += 1
            self.stats["tokens"] += len(slot.tokens)
            self.stats["slots_served"][i] += 1
            slot.served += 1
            slot.request = None
            state["active"][i] = False
            self._resident.discard(req.uid)
            rec = self._uid_rec.pop(req.uid, None)
            if rec is not None:
                self._rec_left[rec.index] -= 1
                if not self._rec_left[rec.index]:
                    del self._rec_left[rec.index]
                    rec.done_t = self.clock()
                    self._open.remove(rec)

    def _decode_once(self):
        """One fused decode block over the resident slots."""
        if "decode" not in self._warmed:
            self._warmed.add("decode")
            self._cold_run = True
        state, slots = self._state, self._slots
        t0 = self.wall()
        (caches, tok, pos, active, budget, gen, toks, valid) = \
            self._decode_block(
                self.params, self._caches, *(self._put(state[k]) for k in (
                    "tok", "pos", "active", "budget", "keys", "gen")))
        self._caches = caches
        toks, valid = np.asarray(toks), np.asarray(valid)
        self.stats["decode_time_s"] += self.wall() - t0
        self.stats["decode_blocks"] += 1
        self.stats["slot_steps"] += toks.size
        self.stats["active_slot_steps"] += int(valid.sum())
        state["tok"] = np.array(tok)  # copies: host mirrors stay writable
        state["pos"] = np.array(pos)
        state["gen"] = np.array(gen)
        # replay emissions on the host mirror (handles retirement)
        for k in range(toks.shape[0]):
            for i in np.nonzero(valid[k])[0]:
                if slots[i].request is not None:
                    self._push_token(int(i), int(toks[k, i]))

    def _step(self):
        """One scheduler step: admit waiting requests, decode one block,
        refill freed slots at the boundary."""
        self._admit()
        if self._active():
            self._decode_once()
            self._admit()

    def _take_ready(self) -> dict[int, Result]:
        out, self._ready = self._ready, {}
        return out

    # -- group-level API (the front-door drives these) ----------------------

    @property
    def admission_cap(self) -> int:
        """Largest admission group ``submit`` accepts (the slot pool)."""
        return self.cfg.max_slots

    @property
    def inflight(self) -> int:
        """Dispatched-but-undrained admission groups."""
        return len(self._open)

    @property
    def accepting(self) -> bool:
        """True while ``submit`` would start real work promptly: no
        earlier requests are still queued waiting for slots.  The
        front-door's overload path defers group closes on this signal so
        backlog accumulates in its bounded (sheddable) queue instead of
        the engine's unbounded one."""
        return not self._queue

    def submit(self, group: Sequence[Request]) -> GroupRecord:
        """Dispatch one admission group: enqueue, prefill what fits.

        Requests that don't fit the free slots wait in the FIFO queue and
        are prefilled as slots retire (during ``drain_*`` calls).  The
        returned :class:`GroupRecord` gets ``dispatch_t`` stamped at the
        prefill of the group's first admitted request and ``done_t`` when
        its last request finishes.
        """
        group = list(group)
        if self.params is None:
            raise ValueError(
                "engine has no params bound — pass params= to Engine "
                "(configs.base.lm_engine binds them for you)")
        if not group:
            raise ValueError("empty admission group")
        if len(group) > self.admission_cap:
            raise ValueError(f"admission group of {len(group)} exceeds "
                             f"the {self.admission_cap}-slot pool")
        for req in group:
            self._validate(req)
        uids = [r.uid for r in group]
        dupes = sorted({u for u in uids if uids.count(u) > 1} |
                       {u for u in uids
                        if u in self._resident or u in self._ready})
        if dupes:
            raise ValueError(f"duplicate request uids: {dupes} "
                             "(results are keyed by uid)")
        self._ensure_pool()
        rec = GroupRecord(uids=tuple(uids), index=self._next_index,
                          variant="lm", bucket=self.cfg.max_slots,
                          size=len(group))
        self._next_index += 1
        self._open.append(rec)
        self._rec_left[rec.index] = len(group)
        for req in group:
            self._uid_rec[req.uid] = rec
            self._resident.add(req.uid)
        self._queue.extend(group)
        self._admit()
        return rec

    def drain_ready(self) -> dict[int, Result]:
        """Advance bounded work — one decode block, freed slots refilled —
        and return every finished result ``{uid: Result}``.  The
        front-door calls this while it would otherwise sleep waiting for
        traffic; decode progress between arrivals happens here."""
        if self._queue or self._active():
            self._step()
        return self._take_ready()

    def drain_all(self) -> dict[int, Result]:
        """Serve queue + resident slots to completion (blocking) and
        return all finished results ``{uid: Result}``."""
        while self._queue or self._active():
            self._step()
        return self._take_ready()

    # -- the offline loop ---------------------------------------------------

    def run(self, requests: Iterable[Request]) -> dict[int, Result]:
        """Serve all requests to completion; returns {uid: Result}.

        The offline loop over the group-level protocol: admission groups
        of ``admission_cap`` are submitted (the first fills the slot pool
        with one ragged prefill, the rest queue), then ``drain_all`` runs
        the continuous-batching loop — byte-identical to the pre-protocol
        monolithic loop because admission order and the per-request
        sampling streams are unchanged.

        Appends a per-run record to ``self.runs`` ({requests, tokens,
        wall_time_s, warmup, tokens_per_s}); runs that jit-compiled a new
        shape are flagged ``warmup`` and excluded from the cumulative
        measured stats that ``tokens_per_s()`` reports.
        """
        reqs = list(requests)
        for req in reqs:  # fail fast, before any request is served
            self._validate(req)
        uids = [req.uid for req in reqs]
        if len(set(uids)) != len(uids):
            dupes = sorted({u for u in uids if uids.count(u) > 1})
            raise ValueError(f"duplicate request uids: {dupes} "
                             "(results are keyed by uid)")
        if self._open or self._queue or self._active() or self._ready:
            raise ValueError("engine has undrained in-flight requests "
                             "(call drain_all first)")
        self._cold_run = False
        tok0 = self.stats["tokens"]
        t_start = self.wall()
        cap = self.admission_cap
        for i in range(0, len(reqs), cap):
            self.submit(reqs[i: i + cap])
        results = self.drain_all()
        dt = self.wall() - t_start
        toks = self.stats["tokens"] - tok0
        self.stats["wall_time_s"] += dt
        kind = "warmup" if self._cold_run else "measured"
        self.stats[kind]["requests"] += len(results)
        self.stats[kind]["work"] += toks
        self.stats[kind]["wall_time_s"] += dt
        self.runs.append({
            "requests": len(results), "tokens": toks, "wall_time_s": dt,
            "warmup": self._cold_run,
            "tokens_per_s": toks / dt if dt else 0.0,
        })
        return results

    @property
    def last_run(self) -> dict | None:
        """Per-run stats record of the most recent ``run()``."""
        return self.runs[-1] if self.runs else None

    # -- convenience APIs ---------------------------------------------------

    def generate(self, prompts, max_new_tokens: int | None = None
                 ) -> np.ndarray:
        """Batch API: prompts (B, P) array or list of ragged 1-D arrays.

        Returns (B, max_new_tokens) int32, pad_id-filled after EOS.
        """
        cfg = self.cfg
        budget = max_new_tokens if max_new_tokens is not None \
            else cfg.max_new_tokens
        prompts = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
        reqs = [Request(uid=i, prompt=p, max_new_tokens=budget)
                for i, p in enumerate(prompts)]
        results = self.run(reqs)
        out = np.full((len(prompts), budget), cfg.pad_id, np.int32)
        for uid, res in results.items():
            out[uid, : len(res.tokens)] = res.tokens
        return out

    def utilization(self) -> float:
        """Fraction of decode slot-steps spent on live requests."""
        if not self.stats["slot_steps"]:
            return 0.0
        return self.stats["active_slot_steps"] / self.stats["slot_steps"]

    def tokens_per_s(self) -> float:
        """Measured steady-state generation throughput — warmup runs (the
        ones that jit-compiled a new shape) are excluded; falls back to
        the warmup totals when only warmup runs exist (see
        :func:`repro.serve.runtime.measured_rate`)."""
        return rt.measured_rate(self.stats)

    def reset_stats(self):
        """Zero the cumulative stats and per-run records (jit caches and
        the warmed-shape set survive — compilations are not forgotten)."""
        self.stats = _fresh_stats(self.cfg.max_slots)
        self.runs = []


class LockstepEngine:
    """The seed engine: one XLA dispatch per token, greedy, no EOS handling.

    Kept as the benchmark baseline for ``benchmarks/bench_serve.py`` — do not
    use for serving (it predates the runtime protocol and takes params
    explicitly).
    """

    def __init__(self, decode_step: Callable, init_caches: Callable,
                 cfg: ServeConfig):
        self.decode_step = jax.jit(decode_step, donate_argnums=(1,))
        self.init_caches = init_caches
        self.cfg = cfg

        def prefill_scan(params, caches, tokens):
            def step(caches, tok_t):
                caches, logits = decode_step(params, caches, tok_t[0], tok_t[1])
                return caches, logits

            positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
            caches, logits = jax.lax.scan(step, caches, (tokens.T, positions))
            return caches, logits[-1]

        self._prefill = jax.jit(prefill_scan, donate_argnums=(1,))

    def generate(self, params, prompts: np.ndarray) -> np.ndarray:
        """prompts: (B, P) int32 (uniform length). Returns (B, new) int32."""
        b, p = prompts.shape
        caches = self.init_caches(b)
        caches, logits = self._prefill(params, caches, jnp.asarray(prompts))
        outs = []
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        pos = p
        for _ in range(self.cfg.max_new_tokens):
            outs.append(tok)
            caches, logits = self.decode_step(params, caches, tok,
                                              jnp.int32(pos))
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            pos += 1
        return np.stack([np.asarray(o) for o in outs], axis=1)
