"""Sequence-state registry: decode state made polymorphic per family.

The serving stack (``engine`` / ``allocator`` / ``scheduler``) grew up
attention-first — admission allocated *pages*, retirement freed *pages*,
occupancy counted *pages*.  The mamba2 / zamba2 configs carry a decode
state that is O(1) in context length (a fixed (H, P, N) recurrent state
plus conv tails per layer), and granite/qwen3 MoE configs are ordinary
paged-attention consumers; what they all share is not a layout but a
*contract*: per-sequence state that must be claimed at admission,
recycled at retirement, advanced per decode tick, and reported for
occupancy.  This module names that contract (``StateHandler``) and
registers one handler per family:

  ``paged_kv``  — attention families.  Admission/free/fork delegate to
                  the free-list page allocator (``serving/allocator``);
                  prefix sharing is supported (refcount + boundary CoW).
  ``ssm_slot``  — pure SSM (mamba2).  A batch row *is* the allocation
                  unit: admission zeroes the row's slot state
                  (``SLOT_STATE_KEYS``) and its length; there is no pool
                  to run out of, so ``admit`` always succeeds while a
                  batch slot is free and ``capacity`` is None (no
                  positional bound to exceed).
  ``hybrid``    — zamba2: slot-based like ``ssm_slot`` plus the shared
                  attention block's dense KV rows (``shared_k/v``),
                  which bound capacity at their S_max.  Admission does
                  NOT zero the shared KV row: visibility is governed by
                  ``seq_lens`` (prefill overwrites ``[0, prompt)``,
                  decode overwrites slot by slot before attending — the
                  overwrite-before-visible invariant, docs/DESIGN.md
                  §2), so a stale row from the slot's previous occupant
                  is never attended.

Handlers are thin, host-side, and eager — exactly like the allocator
glue they wrap; the jitted decode tick never sees them.  The scheduler
asks the registry (``state_handler``) once at construction and then
speaks only the contract, which is what makes admit → step → retire
identical across families.  ``occupancy`` returns plain
``(used, total, per_shard)`` tuples — the scheduler wraps them in its
``PoolOccupancy`` (keeping this module import-cycle-free: it depends
only on ``engine``/``allocator``/``cache``).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.serving import allocator as alloc
from repro.serving.cache import PAGE_STATE_KEYS, CacheConfig
from repro.serving.engine import cache_capacity

__all__ = ["SLOT_STATE_KEYS", "StateHandler", "PagedKVHandler",
           "SlotStateHandler", "HybridHandler", "state_handler",
           "default_serving_config"]

# the per-slot recurrent state of an SSM family cache: everything a slot
# admission must reset (the conv tails feed the recurrence, so a stale
# tail would leak the previous occupant's suffix into token 0)
SLOT_STATE_KEYS = ("ssm_h", "conv_x", "conv_B", "conv_C")


class StateHandler:
    """Uniform per-family contract over a decode cache's sequence state.

    All methods are eager (host-side admission/retirement glue); the
    cache dict goes in and comes back out functionally updated.  ``slot``
    / ``parent`` / ``child`` are batch-row indices — the batch row is the
    universal addressing unit; what *backs* a row (pages, an SSM slot,
    both) is the handler's business.
    """

    name = "base"
    supports_prefix_sharing = False
    # can this family's state roll back a rejected speculative tail?
    # (requires the overwrite-before-visible invariant: rewinding
    # seq_lens must be a complete rollback.  Recurrent SSM state folds
    # every token into one fixed-size state — there is nothing to
    # rewind — so SSM/hybrid keep False and the scheduler degrades those
    # families to plain 1-token decode under spec=...)
    supports_speculative = False

    def __init__(self, cfg: ModelConfig, config: CacheConfig | None = None):
        self.cfg = cfg
        self.config = config

    # -- capacity & occupancy ---------------------------------------------
    def capacity(self, cache: dict) -> int | None:
        """Max tokens one sequence may reach, or None (no positional
        bound — pure-SSM state is O(1) in context length)."""
        return cache_capacity(cache)

    def occupancy(self, cache: dict):
        """(used, total, per_shard) in this handler's allocation units
        (pages for ``paged_kv``, batch slots for the slot families)."""
        raise NotImplementedError

    def used(self, cache: dict) -> int:
        """``occupancy``'s ``used`` alone: what the scheduler samples
        every tick."""
        return self.occupancy(cache)[0]

    # -- admission lifecycle ----------------------------------------------
    def admit(self, cache: dict, slot: int, n_tokens: int):
        """Claim state for a sequence of up to ``n_tokens`` tokens in
        batch row ``slot``.  Returns ``(cache, ok)``; on ``ok=False`` the
        cache is unchanged (admission control = caller branches)."""
        raise NotImplementedError

    def free(self, cache: dict, slot: int) -> dict:
        """Retire row ``slot``, recycling whatever it held."""
        raise NotImplementedError

    def fork(self, cache: dict, parent: int, child: int, prefix_len: int,
             n_tokens: int):
        """Admit ``child`` sharing ``parent``'s first ``prefix_len``
        committed tokens.  Returns ``(cache, ok)``; handlers without
        prefix sharing return ``(cache, False)`` — the caller falls back
        to a plain ``admit``."""
        return cache, False

    def reset_rows(self, cache: dict, slot: int) -> dict:
        """Zero row ``slot``'s per-sequence state and length."""
        raise NotImplementedError

    def advance(self, cache: dict, active) -> dict:
        """Post-tick fixup: idle rows advanced their (zero) lengths
        inside the batched decode step — re-pin them so an idle row's
        masked walk never grows.  ``active`` is a (B,) bool mask."""
        cache = dict(cache)
        cache["seq_lens"] = jnp.where(jnp.asarray(active),
                                      cache["seq_lens"], 0)
        return cache

    # -- single-row prefill views -----------------------------------------
    def slot_view(self, cache: dict, b: int) -> dict:
        """A batch-1 view of row ``b`` for eager per-row prefill: the
        per-sequence leaves are sliced to ``[b:b+1]``, shared leaves
        (pools, layer state of other rows) ride along whole."""
        raise NotImplementedError

    def merge_slot(self, cache: dict, view: dict, b: int) -> dict:
        """Fold a prefilled ``slot_view`` back into row ``b``."""
        raise NotImplementedError

    # -- draft-model state (speculative decode, docs/DESIGN.md §8) ---------
    def draft_free(self, draft_cache: dict, slot: int) -> dict:
        """Retire row ``slot`` of the dense draft cache.  Deliberately a
        no-op by default: draft visibility is governed by the target's
        ``seq_lens`` (overwrite-before-visible — a new occupant's prefill
        overwrites its rows before any draft step attends them)."""
        return draft_cache

    def draft_fork(self, draft_cache: dict, parent: int, child: int) -> dict:
        """Copy ``parent``'s draft-cache row into ``child`` (prefix
        sharing admits the child with the parent's committed prefix, so
        the draft model must see the same context).  Only meaningful for
        handlers with ``supports_speculative``."""
        raise NotImplementedError

    # -- scheduler contract ------------------------------------------------
    def require_scheduler_config(self) -> None:
        """Raise if ``self.config`` cannot back a continuous-batching
        scheduler for this family."""


class PagedKVHandler(StateHandler):
    """Attention families: sequence state is refcounted KV pages."""

    name = "paged_kv"
    supports_prefix_sharing = True
    supports_speculative = True

    def require_scheduler_config(self) -> None:
        c = self.config
        if c is None or c.layout != "paged" or c.alloc != "dynamic":
            raise ValueError(
                "Scheduler needs CacheConfig(layout='paged', "
                f"alloc='dynamic'); got layout="
                f"{c.layout if c else None!r}, "
                f"alloc={c.alloc if c else None!r}")

    def occupancy(self, cache):
        used, total = alloc.pool_occupancy(cache)
        return used, total, alloc.shard_occupancy(cache)

    def used(self, cache):
        return alloc.pool_occupancy(cache)[0]

    def admit(self, cache, slot, n_tokens):
        return alloc.admit_sequence(cache, slot, n_tokens)

    def free(self, cache, slot):
        return alloc.free_sequence(cache, slot)

    def fork(self, cache, parent, child, prefix_len, n_tokens):
        return alloc.fork_sequence(cache, parent, child, prefix_len,
                                   n_tokens)

    def reset_rows(self, cache, slot):
        cache = dict(cache)
        width = cache["page_table"].shape[1]
        cache["page_table"] = cache["page_table"].at[slot].set(
            jnp.full((width,), alloc.SCRATCH_PAGE, jnp.int32))
        cache["seq_lens"] = cache["seq_lens"].at[slot].set(0)
        return cache

    def slot_view(self, cache, b):
        view = dict(cache)
        view["page_table"] = cache["page_table"][b:b + 1]
        view["seq_lens"] = cache["seq_lens"][b:b + 1]
        return view

    def merge_slot(self, cache, view, b):
        cache = dict(cache)
        # the row's writes landed in the shared pools (indirected through
        # its private table row): take the pools whole, fold the length
        for key in PAGE_STATE_KEYS:
            if key in view:
                cache[key] = view[key]
        cache["seq_lens"] = cache["seq_lens"].at[b].set(
            view["seq_lens"][0])
        return cache

    def draft_fork(self, draft_cache, parent, child):
        draft_cache = dict(draft_cache)
        for key in ("k", "v"):
            draft_cache[key] = draft_cache[key].at[:, child].set(
                draft_cache[key][:, parent])
        return draft_cache


class SlotStateHandler(StateHandler):
    """Pure SSM (mamba2): the batch row is the allocation unit.

    There is no pool — a free batch slot *is* free capacity, so ``admit``
    always succeeds (the scheduler's batch-full check is the only gate)
    and ``occupancy`` counts busy slots (``seq_lens > 0``).
    """

    name = "ssm_slot"

    def require_scheduler_config(self) -> None:
        c = self.config
        if c is not None and c.layout != "dense":
            raise ValueError(
                f"family {self.cfg.family!r} keeps its O(1) SSM state "
                f"dense; got CacheConfig(layout={c.layout!r})")

    def occupancy(self, cache):
        total = int(cache["seq_lens"].shape[0])
        used = int(jnp.sum(cache["seq_lens"] > 0))
        return used, total, ((used, total),)

    def admit(self, cache, slot, n_tokens):
        # a zeroed slot is a fresh sequence: exp(0·A)=1 decay on nothing
        return self.reset_rows(cache, slot), True

    def free(self, cache, slot):
        return self.reset_rows(cache, slot)

    def reset_rows(self, cache, slot):
        cache = dict(cache)
        for key in SLOT_STATE_KEYS:
            cache[key] = cache[key].at[:, slot].set(0.0)
        cache["seq_lens"] = cache["seq_lens"].at[slot].set(0)
        return cache

    def slot_view(self, cache, b):
        view = dict(cache)
        for key in SLOT_STATE_KEYS:
            view[key] = cache[key][:, b:b + 1]
        view["seq_lens"] = cache["seq_lens"][b:b + 1]
        return view

    def merge_slot(self, cache, view, b):
        cache = dict(cache)
        for key in SLOT_STATE_KEYS:
            cache[key] = cache[key].at[:, b].set(view[key][:, 0])
        cache["seq_lens"] = cache["seq_lens"].at[b].set(
            view["seq_lens"][0])
        return cache


class HybridHandler(SlotStateHandler):
    """zamba2: SSM slots plus the shared attention block's dense KV rows.

    ``shared_k/v`` travel with the slot in views/merges, but admission
    deliberately does NOT zero them: ``seq_lens`` governs visibility
    (the overwrite-before-visible invariant), so the previous occupant's
    stale KV is never attended — zeroing S_max·KVH·hd per admission
    would be pure write traffic.
    """

    name = "hybrid"

    def slot_view(self, cache, b):
        view = super().slot_view(cache, b)
        view["shared_k"] = cache["shared_k"][:, b:b + 1]
        view["shared_v"] = cache["shared_v"][:, b:b + 1]
        return view

    def merge_slot(self, cache, view, b):
        cache = super().merge_slot(cache, view, b)
        cache["shared_k"] = cache["shared_k"].at[:, b].set(
            view["shared_k"][:, 0])
        cache["shared_v"] = cache["shared_v"].at[:, b].set(
            view["shared_v"][:, 0])
        return cache


def state_handler(cfg: ModelConfig,
                  config: CacheConfig | None = None) -> StateHandler:
    """The registry: family → handler instance."""
    if cfg.family == "ssm":
        return SlotStateHandler(cfg, config)
    if cfg.family == "hybrid":
        return HybridHandler(cfg, config)
    return PagedKVHandler(cfg, config)


def default_serving_config(cfg: ModelConfig) -> CacheConfig:
    """The continuous-batching default per family: dynamic 16-token pages
    for attention KV (the scheduler's historical default), the dense
    layout for slot-state families (their state is O(1) — nothing to
    page)."""
    if cfg.family in ("ssm", "hybrid"):
        return CacheConfig()
    return CacheConfig(layout="paged", alloc="dynamic", page_size=16)
