"""Continuous-batching scheduler: admit → step → retire over any family.

The static serving loop (``engine.prefill`` → ``engine.greedy_decode``)
processes one batch to completion: every sequence holds its state until
the *slowest* one finishes.  Serving-class traffic (requests arriving
continuously, wildly mixed prompt/output lengths) wants the vLLM-style
loop instead — and the sequence-state registry (``serving/state.py``)
makes it one loop for every family: the scheduler speaks only the
``StateHandler`` contract (capacity / admit / free / fork / advance /
occupancy), so attention models serve over a paged pool, mamba2 over
per-row SSM slots, and zamba2 over both, through the *same* code path:

  * **admit** — while a batch slot is free and the handler can claim
    state for ``prompt + budget`` tokens (pages for ``paged_kv`` —
    admission waits when the pool can't cover the head-of-queue
    request; always-admissible slots for the SSM families), pop the
    next queued request and prefill its prompt.  If a live sequence
    shares a prompt prefix and the handler supports sharing, the
    prefix's full pages are *aliased* instead of recomputed
    (``allocator.fork_sequence``: refcounted read-only sharing, eager
    CoW on the boundary page) and only the suffix is prefilled.
  * **step** — one decode step for the whole live batch through the
    *same* jitted scan body ``greedy_decode`` uses
    (``engine._greedy_run`` with ``n_steps=1``, cache donated): the
    static-batch loop is literally the special case of this loop where
    every slot is admitted at tick 0 and nothing arrives later.  Idle
    slots ride along masked (their table rows point at the reserved
    scratch page; their lengths are re-zeroed after the step).
  * **retire** — finished sequences (budget exhausted or EOS) release
    their state through the handler: page references drop (pages whose
    refcount reaches zero return to the free list), SSM slots zero
    their recurrent state.

Prompts are right-padded to a bucket multiple before prefill so the
number of distinct prefill shapes — and with it the trace count — stays
O(max_len / bucket) instead of O(#distinct prompt lengths).

``benchmarks/serving.py`` drives a mixed-arrival trace through this
loop against the static-batch baseline; ``examples/serve_quantized.py``
shows it end to end with int8 projections.  Architecture notes:
``docs/DESIGN.md`` §4.
"""
from __future__ import annotations

import dataclasses
import warnings
from collections import deque
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models.config import ModelConfig
from repro.serving.cache import CacheConfig, cache_shardings, init_cache
from repro.serving.engine import (_greedy_run, draft_prefill_row, prefill,
                                  spec_step)
from repro.serving.state import default_serving_config, state_handler

__all__ = ["Request", "Scheduler", "PoolOccupancy", "SpecConfig"]


class PoolOccupancy(NamedTuple):
    """Pool usage snapshot.  ``used``/``total`` are global page counts;
    ``per_shard`` is ((used, size), …) for each pool shard.  Under
    per-shard free lists the global number alone is a lie when shards are
    imbalanced: admission gates on *every* shard covering its round-robin
    share, so the fullest shard in ``per_shard`` is the binding
    constraint, not ``total - used``."""

    used: int
    total: int
    per_shard: tuple[tuple[int, int], ...]


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Draft-and-verify speculative decode (``docs/DESIGN.md`` §8).

    ``draft_params``/``draft_cfg``: the proposal model — a smaller config
    from ``configs/`` or a truncated self-speculation stack; it must share
    the target's tokenizer (same vocab ids).  ``n_draft``: tokens proposed
    per scheduler tick; the target verifies all of them (plus the input
    token) in one ``n_draft+1``-row pass through the paged flash
    schedule's verify mode, so each tick emits 1..n_draft tokens.

    The scheduler honors this only when the family's state handler sets
    ``supports_speculative`` (attention families over paged KV) and no
    multi-device model axis is active; otherwise it degrades to plain
    1-token decode with a warning — SSM/hybrid recurrent state folds
    every token into one fixed-size state and cannot rewind a rejected
    tail.
    """

    draft_params: dict
    draft_cfg: ModelConfig
    n_draft: int = 4


@dataclasses.dataclass
class Request:
    """One generation request: ``prompt`` (token ids) and a generation
    budget.  ``max_new_tokens`` bounds the page reservation at admission;
    generation may stop earlier on ``eos_id``."""

    rid: int
    prompt: np.ndarray
    max_new_tokens: int


@dataclasses.dataclass
class _Slot:
    """Host-side state of one live batch row."""

    req: Request
    generated: list
    last_token: int
    admitted: int = 0
    # scheduler tick at which each generated token materialized (the
    # admission tick for the prefill token): benchmarks turn these into
    # TTFT / per-token latency percentiles via per-tick wall times
    token_ticks: list = dataclasses.field(default_factory=list)


class Scheduler:
    """Continuous-batching serving loop over any family's decode state
    (dispatching through the sequence-state registry, ``serving/state``).

    Args:
      params / cfg: the model — attention, MoE, pure-SSM (mamba2) or
        hybrid (zamba2); ``cfg.family`` picks the state handler.
      slots: batch width B of the decode step (live-sequence capacity).
      max_len: per-sequence context bound (page-table width; shared-KV
        S_max for hybrid; SSM slot state is O(1), so for pure SSM this
        only sizes nothing — capacity is unbounded).
      config: a ``CacheConfig``.  Attention families need
        ``layout="paged"``, ``alloc="dynamic"`` — pool geometry
        (``page_size`` / ``pool_pages``; the pool may be far below
        ``slots * ceil(max_len/page_size)`` — admission control and
        prefix sharing are what make oversubscription safe),
        ``kv_quant`` (int8 pools roughly halve page bytes, so the same
        pool serves ~2x the tokens per HBM byte; prefix sharing and CoW
        carry the scale rows), and the ``mesh`` knob: under a mesh the
        pool is partitioned, the allocator runs per-shard free lists,
        and every decode tick goes through the shard_map'd partitioned
        attention.  SSM families use the dense layout (their state is
        per-slot, not paged).  Default: the family's
        ``default_serving_config`` — dynamic 16-token pages for
        attention (the scheduler's historical pages, not CacheConfig's
        64-token serving default), plain dense for SSM/hybrid.
      prefill_chunk: commit prompts in fixed-size chunks through the
        paged flash path (None = one pass; right below ~1k prompts).
      share_prefix: alias common prompt-prefix pages between live
        sequences instead of recomputing them.
      bucket: prompts are right-padded to a multiple of this before
        prefill (bounds the number of traced prefill shapes).
      eos_id: optional early-stop token id.
      spec: a ``SpecConfig`` enabling draft-and-verify speculative
        decode — each tick proposes ``n_draft`` tokens with the draft
        model and verifies them in one target pass, emitting 1..n_draft
        tokens per tick with greedy output identical to 1-token decode
        (bitwise under the ref kernel mode).  Families whose handler
        lacks ``supports_speculative`` (SSM/hybrid) and mesh-sharded
        pools degrade to plain decode with a warning.
      page_size / pool_pages / kv_quant: **deprecated** keyword spelling
        of the ``config`` fields (pre-PR-7); still honored with a
        ``DeprecationWarning``, mutually exclusive with ``config``.
    """

    def __init__(self, params, cfg: ModelConfig, *, slots: int = 4,
                 max_len: int = 256,
                 config: CacheConfig | None = None,
                 page_size: int | None = None,
                 pool_pages: int | None = None,
                 kv_quant: str | None = None,
                 prefill_chunk: int | None = None,
                 share_prefix: bool = True, bucket: int = 16,
                 eos_id: int | None = None, dtype=jnp.float32,
                 spec: SpecConfig | None = None):
        legacy = {k: v for k, v in (("page_size", page_size),
                                    ("pool_pages", pool_pages),
                                    ("kv_quant", kv_quant)) if v is not None}
        if legacy:
            if config is not None:
                raise TypeError(
                    "Scheduler: pass either config=CacheConfig(...) or the "
                    f"legacy keywords {sorted(legacy)}, not both")
            warnings.warn(
                f"Scheduler keyword(s) {sorted(legacy)} are deprecated; "
                "pass config=CacheConfig(layout='paged', alloc='dynamic', "
                "...) instead", DeprecationWarning, stacklevel=2)
            config = CacheConfig(layout="paged", alloc="dynamic",
                                 page_size=page_size or 16,
                                 pool_pages=pool_pages,
                                 kv_quant=kv_quant or "none")
        if config is None:
            config = default_serving_config(cfg)
        self.handler = state_handler(cfg, config)
        self.handler.require_scheduler_config()
        self.params, self.cfg, self.config = params, cfg, config
        self.page_size, self.bucket = config.page_size, bucket
        self.prefill_chunk, self.share_prefix = prefill_chunk, share_prefix
        self.eos_id = eos_id
        self.cache = init_cache(cfg, slots, max_len, dtype=dtype,
                                config=config)
        # expected leaf placements (mesh only): eager admission paths
        # (slice-view prefill copy-backs, allocator scatters) re-pin
        # against these so the partitioned-pool invariant survives
        # between jitted ticks
        self._shardings = (cache_shardings(cfg, self.cache, config)
                           if config.mesh is not None else None)
        self.spec: SpecConfig | None = None
        self.draft_cache: dict | None = None
        # speculative accounting: proposed/accepted draft tokens and
        # emitted totals per decode tick (benchmarks report acceptance
        # rate and tokens/step from these)
        self.spec_stats = {"ticks": 0, "proposed": 0, "accepted": 0,
                           "emitted": 0}
        if spec is not None:
            if not self.handler.supports_speculative:
                warnings.warn(
                    f"state handler {self.handler.name!r} does not support "
                    "speculative rollback; degrading to 1-token decode",
                    stacklevel=2)
            elif config.model_size() > 1:
                warnings.warn(
                    "speculative decode is not supported over a sharded "
                    "page pool; degrading to 1-token decode", stacklevel=2)
            else:
                assert spec.n_draft >= 1, spec.n_draft
                self.spec = spec
                # the draft's dense cache must hold KV through position
                # c + n_draft - 1 where c can reach capacity - 1
                cap = self.handler.capacity(self.cache) or max_len
                self.draft_cache = init_cache(
                    spec.draft_cfg, slots, cap + spec.n_draft, dtype=dtype)
        self.slots: list[_Slot | None] = [None] * slots
        self.queue: deque[Request] = deque()
        self.finished: dict[int, np.ndarray] = {}
        # per-request event ticks (submitted / admitted / token_ticks),
        # kept after retirement — the latency-percentile benchmarks join
        # these against per-tick wall times
        self.request_log: dict[int, dict] = {}
        self.occupancy_log: list[int] = []
        self._next_rid = 0
        self._admitting_rid = -1     # rid of the admission in progress
        self._ticks = 0

    # -- request intake ----------------------------------------------------
    def submit(self, prompt, max_new_tokens: int, rid: int | None = None):
        """Queue a request; returns its id.  May be called between any
        two ``step``s — that is the point.  Rejects (loudly, here — not
        mid-tick) requests whose page reservation could never fit the
        per-sequence table, which would otherwise wedge the queue head."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        assert prompt.size >= 1 and max_new_tokens >= 1
        if "page_table" in self.cache:
            width = self.cache["page_table"].shape[1]
            need = -(-(prompt.size + max_new_tokens) // self.page_size)
            if need > width:
                raise ValueError(
                    f"request needs {need} pages (prompt {prompt.size} + "
                    f"budget {max_new_tokens} tokens) but the table holds "
                    f"{width} (max_len {width * self.page_size})")
        else:
            # slot families: pure-SSM state has no positional bound
            # (capacity None); hybrid is bounded by the shared-KV S_max
            cap = self.handler.capacity(self.cache)
            if cap is not None and prompt.size + max_new_tokens > cap:
                raise ValueError(
                    f"request needs {prompt.size + max_new_tokens} tokens "
                    f"(prompt {prompt.size} + budget {max_new_tokens}) but "
                    f"the cache capacity is {cap} tokens")
        if rid is None:
            rid = self._next_rid
        self._next_rid = max(self._next_rid, rid + 1)
        self.queue.append(Request(rid, prompt, max_new_tokens))
        self.request_log[rid] = {"submitted": self._ticks}
        return rid

    # -- introspection -----------------------------------------------------
    def pool_occupancy(self) -> PoolOccupancy:
        """Global *and* per-shard usage right now (``PoolOccupancy``;
        indexes [0]/[1] stay (used, total) for tuple-shaped callers).
        Units are the handler's allocation grain: pages for attention
        families, busy batch slots for the SSM families."""
        used, total, per_shard = self.handler.occupancy(self.cache)
        return PoolOccupancy(used, total, per_shard)

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    # -- the loop ----------------------------------------------------------
    def step(self) -> list[int]:
        """One scheduler tick: admit from the queue, run one decode step
        for the live batch, retire rows that just finished (their pages
        return to the pool before the next tick's admissions).  Returns
        the ids of requests that finished this tick.

        Each layer of the tick is a ``serving.*`` span in the profiler's
        trace (``docs/DESIGN.md`` §6); with no profiler running a span
        records nothing."""
        with TraceAnnotation("serving.step", tick=self._ticks):
            self._admit()
            self._decode()
            done = self._retire()
            self._ticks += 1
            with TraceAnnotation("serving.occupancy") as span:
                used = self.handler.used(self.cache)
                span.set_metadata(pages_used=used)
            self.occupancy_log.append(used)
        return done

    def run(self, max_ticks: int | None = None) -> dict[int, np.ndarray]:
        """Drive ``step`` until queue and batch drain; returns
        ``{rid: generated tokens}`` (first token from the prefill logits,
        the rest from decode steps).  ``max_ticks`` bounds the ticks of
        *this* call (the scheduler may have stepped before)."""
        start = self._ticks
        while self.queue or self.n_active:
            self.step()
            if max_ticks is not None and self._ticks - start > max_ticks:
                raise RuntimeError(f"scheduler did not drain in "
                                   f"{max_ticks} ticks")
        return self.finished

    # -- internals ---------------------------------------------------------
    def _finished(self, slot: _Slot) -> bool:
        if len(slot.generated) >= slot.req.max_new_tokens:
            return True
        return self.eos_id is not None and slot.last_token == self.eos_id

    def _retire(self) -> list[int]:
        done = []
        with TraceAnnotation("serving.retire") as span:
            for b, slot in enumerate(self.slots):
                if slot is not None and self._finished(slot):
                    self.cache = self.handler.free(self.cache, b)
                    if self.spec is not None:
                        self.draft_cache = self.handler.draft_free(
                            self.draft_cache, b)
                    self.finished[slot.req.rid] = np.asarray(slot.generated,
                                                             np.int32)
                    self.request_log[slot.req.rid].update(
                        admitted=slot.admitted, token_ticks=slot.token_ticks)
                    done.append(slot.req.rid)
                    self.slots[b] = None
            span.set_metadata(finished=len(done))
        return done

    def _prefix_match(self, prompt: np.ndarray):
        """Longest shareable prefix with a live sequence: (slot, length).
        Capped at ``len(prompt) - 1`` — the last prompt token must be
        prefilled so its logits exist to seed generation.  Matches
        shorter than one page are reported as no match: they would alias
        zero full pages and pay a boundary-page copy for nothing (think
        a shared BOS token)."""
        best_b, best_len = -1, 0
        for b, slot in enumerate(self.slots):
            if slot is None:
                continue
            other = slot.req.prompt
            n = min(prompt.size - 1, other.size)
            eq = np.equal(prompt[:n], other[:n])
            common = n if eq.all() else int(eq.argmin())
            if common > best_len:
                best_b, best_len = b, common
        if best_len < self.page_size:
            return -1, 0
        return best_b, best_len

    def _admit(self):
        while self.queue:
            try:
                b = self.slots.index(None)
            except ValueError:
                return                       # batch full
            req = self.queue[0]
            budget = int(req.prompt.size) + req.max_new_tokens
            parent, shared = (-1, 0)
            if self.share_prefix and self.handler.supports_prefix_sharing:
                parent, shared = self._prefix_match(req.prompt)
            # one span per attempt: the claim, and once it is granted the
            # prefill and the first token's read
            with TraceAnnotation("serving.admit", rid=req.rid,
                                 prompt_tokens=req.prompt.size,
                                 shared_tokens=shared):
                if shared > 0:
                    self.cache, ok = self.handler.fork(
                        self.cache, parent, b, shared, budget)
                    if bool(ok) and self.spec is not None:
                        # the child wakes with the parent's committed
                        # prefix: the draft model must see the same context
                        self.draft_cache = self.handler.draft_fork(
                            self.draft_cache, parent, b)
                else:
                    self.cache, ok = self.handler.admit(self.cache, b,
                                                        budget)
                if not bool(ok):
                    if self.n_active == 0:
                        raise RuntimeError(
                            f"request {req.rid} needs more pages than an "
                            f"empty pool of {self.pool_occupancy()[1]} "
                            f"offers")
                    return                   # pool full: wait for retires
                self.queue.popleft()
                self._admitting_rid = req.rid
                logits = self._prefill_slot(b, req.prompt, start=shared)
                with TraceAnnotation("serving.first_token", rid=req.rid):
                    first = int(jnp.argmax(logits))
            self.slots[b] = _Slot(req, [first], first,
                                  admitted=self._ticks,
                                  token_ticks=[self._ticks])

    def _prefill_slot(self, b: int, prompt: np.ndarray,
                      start: int) -> jax.Array:
        """Commit ``prompt[start:]`` into row ``b``'s pages (positions
        ``start..``) and return the logits (V,) after its last token."""
        from repro.kernels.tiled_matmul.ops import kernel_mode
        suffix = prompt[start:]
        pad = -suffix.size % self.bucket
        padded = np.pad(suffix, (0, pad))
        with TraceAnnotation("serving.prefill", rid=self._admitting_rid,
                             tokens=suffix.size, padded=padded.size):
            view = self.handler.slot_view(self.cache, b)
            nl, view = prefill(
                self.params, view, jnp.asarray(padded[None]),
                jnp.asarray([prompt.size], jnp.int32), self.cfg,
                chunk=self.prefill_chunk, start_pos=start,
                config=self.config)
            self.cache = self.handler.merge_slot(self.cache, view, b)
            if self.spec is not None:
                # commit the prompt into the draft model's dense row too
                # (the prefix-shared part was copied by draft_fork; only
                # the suffix runs), one fused jitted call per admission
                self.draft_cache = draft_prefill_row(
                    self.spec.draft_params, self.draft_cache,
                    jnp.asarray(padded[None]),
                    jnp.asarray([prompt.size], jnp.int32),
                    jnp.asarray(start, jnp.int32), jnp.asarray(b, jnp.int32),
                    self.spec.draft_cfg, kernel_mode())
            self._pin_shardings()
            return nl[0]

    def _pin_shardings(self):
        """Re-place cache leaves on their expected shardings (mesh only).
        Eager host-side mutations (admission scatters, prefill view
        copy-backs) can leave a leaf with a propagated-but-different
        placement; the jitted tick donates the cache, so its leaves must
        arrive partitioned exactly as compiled or XLA reshards (or worse,
        gathers) per tick.  ``device_put`` onto the matching sharding is
        a no-op for already-correct leaves."""
        if self._shardings is None:
            return
        self.cache = {k: jax.device_put(v, self._shardings[k])
                      for k, v in self.cache.items()}

    def _decode(self):
        if not self.n_active:
            return
        if self.spec is not None:
            self._spec_decode()
            return
        from repro.kernels.tiled_matmul.ops import kernel_mode
        with TraceAnnotation("serving.decode", tick=self._ticks,
                             live=self.n_active):
            active = np.asarray([s is not None for s in self.slots])
            tok = jnp.asarray([[s.last_token if s else 0]
                               for s in self.slots], jnp.int32)
            # the donated cache must arrive partitioned exactly as
            # compiled — eager retire/admit scatters since the last tick
            # may have moved placements
            self._pin_shardings()
            # the static-batch loop's own jitted scan body, n_steps=1: one
            # compile shared with greedy_decode, cache donated in and out
            toks, self.cache = _greedy_run(
                self.params, self.cache, tok, jnp.asarray(0, jnp.int32),
                None, self.cfg, 1, True, kernel_mode(), self.config.mesh)
            with TraceAnnotation("serving.decode.wait"):
                nxt = np.asarray(toks)[0, :, 0]
            with TraceAnnotation("serving.decode.advance"):
                # idle rows advanced their (zero) lengths and wrote
                # garbage to their scratch targets; the handler re-pins
                # them so an idle row's masked walk never grows
                self.cache = self.handler.advance(self.cache, active)
                for b, slot in enumerate(self.slots):
                    if slot is not None and not self._finished(slot):
                        slot.last_token = int(nxt[b])
                        slot.generated.append(slot.last_token)
                        slot.token_ticks.append(self._ticks)

    def _spec_decode(self):
        """One draft-and-verify tick (``engine.spec_step``): each live
        row emits 1..n_draft tokens; rejected drafts roll back in-engine
        (``seq_lens`` rewind + page-state invalidation — pages never
        move).  The event log records one ``token_tick`` per *emitted*
        token, so a multi-accept step contributes that many entries at
        the same tick and the latency percentiles stay per-token."""
        spec = self.spec
        with TraceAnnotation("serving.decode", tick=self._ticks,
                             live=self.n_active):
            active = np.asarray([s is not None for s in self.slots])
            tok = jnp.asarray([[s.last_token if s else 0]
                               for s in self.slots], jnp.int32)
            # rows at budget already (e.g. admitted this tick with an
            # exhausted budget) emit 0 and roll their whole verify back
            budget_left = jnp.asarray(
                [s.req.max_new_tokens - len(s.generated) if s else 0
                 for s in self.slots], jnp.int32)
            self._pin_shardings()
            pred, m, acc, self.cache, self.draft_cache = spec_step(
                self.params, spec.draft_params, self.cache,
                self.draft_cache, tok, budget_left, jnp.asarray(active),
                self.cfg, spec.draft_cfg, n_draft=spec.n_draft,
                eos_id=self.eos_id, config=self.config)
            with TraceAnnotation("serving.decode.wait"):
                pred, m, acc = (np.asarray(pred), np.asarray(m),
                                np.asarray(acc))
            with TraceAnnotation("serving.decode.advance"):
                self.cache = self.handler.advance(self.cache, active)
                st = self.spec_stats
                st["ticks"] += 1
                st["proposed"] += int(active.sum()) * spec.n_draft
                st["emitted"] += int(m.sum())
                # accepted = emitted tokens that were draft proposals
                # (min(k, m) in-engine: on a full match every emitted
                # token is a draft)
                st["accepted"] += int(acc.sum())
                for b, slot in enumerate(self.slots):
                    if slot is None or not m[b]:
                        continue
                    emitted = [int(t) for t in pred[b, :m[b]]]
                    slot.generated.extend(emitted)
                    slot.token_ticks.extend([self._ticks] * len(emitted))
                    slot.last_token = emitted[-1]
