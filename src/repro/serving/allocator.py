"""Dynamic page allocator + prefix-sharing tables for the paged KV cache.

PR 4's paged layout made the page table the *only* way the kernel
addresses KV — physical placement is opaque (``docs/DESIGN.md`` §2
invariant 3).  This module exploits exactly that opacity: instead of
``default_page_table``'s build-time striping (every sequence owns a
static rectangle of pages forever), a **free-list allocator** hands pages
out at admission time and recycles them at retirement, so a pool can
serve an unbounded request stream (``serving/scheduler.py``) and two
sequences with a common prompt prefix can *share* the prefix's pages.

All state is arrays and the core operations (``alloc_pages`` /
``free_pages`` / ``share_pages``) are pure masked-scatter functions of
it, so they compose with jit and the state rides inside the cache pytree
(donated into the serving loop like everything else).  The cache-level
helpers (``admit_sequence`` / ``free_sequence`` / ``fork_sequence``) are
the scheduler's host-side admission path — they branch on the returned
``ok`` eagerly.

**Shard-local state** — the pool may be partitioned ``shards`` ways over
a device mesh (``docs/DESIGN.md`` §3: the pool's page dim takes the
``model`` axis when KV heads do not divide it).  Shard ``s`` owns the
contiguous global page range ``[s·P/S, (s+1)·P/S)`` and keeps its *own*
free stack, stack pointer, and refcount row, so allocator state shards
exactly like the pool it manages (nothing global to replicate but the
per-sequence ``held`` counts):

  free stack   (S, P/S) int32  ``free[s, :top[s]]`` are free *global* ids
                               owned by shard ``s``
  top          (S,)     int32  free pages per shard (stack pointers)
  refcounts    (S, P/S) int32  live references; global page ``p`` lives
                               at ``(p // (P/S), p % (P/S))`` (0 = free)

Allocation stripes a request's pages **round-robin** across shards (page
``j`` of a request comes from shard ``j mod S``), keeping shards
balanced, and admission is taken on the **global minimum** of free
pages: a request is admitted iff *every* shard can cover its share —
one ``min`` over the ``(S,)`` stack pointers (the psum-min when the
state is mesh-sharded), no host round-trip, and deliberately
conservative: a pool whose *total* free count covers the request is
still refused when one shard is too loaded, because the pages must
physically come from somewhere.  ``shards=1`` reduces every operation to
the flat PR-5 free list bit for bit.

Embedded in a ``layout="paged"`` cache (``CacheConfig(alloc="dynamic")``)
the arrays appear as ``alloc_free`` / ``alloc_top`` / ``alloc_ref`` plus
``alloc_held`` (B,) int32 — how many leading ``page_table`` entries each
row actually references (owned or shared).

**Reserved scratch page** — global page id 0 (shard 0's first page) is
never allocated (its refcount is pinned at init).  Idle batch slots and
the unallocated tail of every table row point at it, so their masked
writes land somewhere harmless without violating validity (invariant 1):
the scratch page is never named by a live sequence's walked range.  The
reservation makes shard 0 one page smaller than the rest — a permanent,
deliberate imbalance that keeps the global-min admission rule honest in
tests.

**Prefix sharing (refcount + boundary CoW)** — ``fork_sequence`` builds
a child row whose first ``prefix_len // page_size`` entries alias the
parent's pages (refcount++, read-only from then on), while the partially
filled *boundary* page is **copied eagerly** into a private child page:
the child will write positions ``>= prefix_len`` and the first of those
lands mid-page, so the copy-on-write happens at fork time, before any
write can alias.  Writes therefore only ever target pages with
refcount 1 — the *disjoint writable sets* invariant (``docs/DESIGN.md``
§2, which this module relaxes from full disjointness).

``free_sequence`` decrements refcounts along the row and pushes only the
pages that drop to zero back on their owning shard's stack, so shared
prefixes survive until their last referencing sequence retires.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.tiling import ceil_div

__all__ = ["ALLOC_KEYS", "init_allocator", "can_admit", "alloc_pages",
           "free_pages", "share_pages", "attach_allocator",
           "allocator_state", "store_allocator", "admit_sequence",
           "free_sequence", "fork_sequence", "rewind_sequence",
           "pool_occupancy", "shard_occupancy", "SCRATCH_PAGE"]

SCRATCH_PAGE = 0          # reserved sink page, never allocated
_RESERVED = 1             # global pages [0, _RESERVED) are pinned at init

ALLOC_KEYS = ("alloc_free", "alloc_top", "alloc_ref", "alloc_held")


# ---------------------------------------------------------------------------
# Core free-list operations (pure array-state functions, jit-compatible)
# ---------------------------------------------------------------------------
def init_allocator(n_pages: int, shards: int = 1) -> dict:
    """Fresh allocator over a pool of ``n_pages`` physical pages split
    into ``shards`` shard-local free lists.

    Per shard, free pages are stacked ascending (top of stack = highest
    id, so early allocations land at each shard's far end — deliberately
    nothing like the contiguous layout, keeping the indirection honest);
    global page 0 is the pinned scratch page, so shard 0 starts one page
    short.  ``shards`` must divide ``n_pages``.
    """
    assert n_pages % shards == 0, (n_pages, shards)
    per = n_pages // shards
    assert per > _RESERVED, f"shard of {per} pages is all-reserved"
    ids = jnp.arange(n_pages, dtype=jnp.int32).reshape(shards, per)
    col = jnp.arange(per, dtype=jnp.int32)[None, :]
    srow = jnp.arange(shards, dtype=jnp.int32)[:, None]
    # shard 0 drops the scratch page: [1..per-1, pad]; others keep all
    free = jnp.where(srow == 0, jnp.where(col < per - 1, ids + 1, 0), ids)
    top = jnp.where(jnp.arange(shards) == 0, per - _RESERVED,
                    per).astype(jnp.int32)
    ref = jnp.zeros((shards, per), jnp.int32).at[0, SCRATCH_PAGE].set(1)
    return {"free": free, "top": top, "ref": ref}


def _shard_need(n, shards: int) -> jnp.ndarray:
    """(S,) pages shard ``s`` must supply for a round-robin grab of ``n``:
    ``|{j in [0, n) : j mod S == s}|``."""
    s = jnp.arange(shards, dtype=jnp.int32)
    return jnp.maximum(0, (jnp.asarray(n, jnp.int32) - s + shards - 1)
                       // shards)


def can_admit(state: dict, n) -> jnp.ndarray:
    """bool scalar — can every shard cover its round-robin share of ``n``
    pages right now?  One min over the stack pointers (the global-min
    admission rule; lowers to a cross-shard min when the state is
    mesh-sharded)."""
    shards = state["free"].shape[0]
    return jnp.min(state["top"] - _shard_need(n, shards)) >= 0


def alloc_pages(state: dict, n, width: int):
    """Pop ``n`` pages — round-robin across shards — into a ``(width,)``
    table row of global page ids (entries past ``n`` are scratch).
    Returns ``(state, row, ok)``; when ``ok`` is False (some shard cannot
    cover its share) the state is unchanged and the row is all-scratch —
    admission control is the caller branching on ``ok``.
    """
    n = jnp.asarray(n, jnp.int32)
    shards, per = state["free"].shape
    n_pool = shards * per
    need = _shard_need(n, shards)
    ok = jnp.min(state["top"] - need) >= 0
    j = jnp.arange(width, dtype=jnp.int32)
    sh = j % shards                         # owning shard of slot j
    rank = j // shards                      # earlier slots on that shard
    take = (j < n) & ok
    idx = jnp.clip(state["top"][sh] - 1 - rank, 0, per - 1)
    row = jnp.where(take, state["free"][sh, idx], SCRATCH_PAGE)
    # scatter-add on the flat refcounts (global id == flat index); dropped
    # out-of-range targets guard the no-op case
    ref = state["ref"].reshape(-1).at[
        jnp.where(take, row, n_pool)].add(1, mode="drop")
    top = jnp.where(ok, state["top"] - need, state["top"])
    return {"free": state["free"], "top": top,
            "ref": ref.reshape(shards, per)}, row, ok


def free_pages(state: dict, row: jnp.ndarray, count) -> dict:
    """Drop one reference from the first ``count`` entries of ``row``;
    pages whose refcount reaches zero go back on their owning shard's
    free stack."""
    count = jnp.asarray(count, jnp.int32)
    shards, per = state["free"].shape
    n_pool = shards * per
    width = row.shape[0]
    held = jnp.arange(width, dtype=jnp.int32) < count
    ref = state["ref"].reshape(-1).at[
        jnp.where(held, row, n_pool)].add(-1, mode="drop")
    released = held & (ref[row] == 0)
    sh = row // per                          # owning shard per entry
    # pack released ids onto their shard's stack: the k-th released page
    # of shard s lands at free[s, top[s] + k]
    belong = (sh[:, None] == jnp.arange(shards, dtype=jnp.int32)[None, :])
    contrib = (released[:, None] & belong).astype(jnp.int32)   # (w, S)
    rank = jnp.take_along_axis(jnp.cumsum(contrib, axis=0) - 1,
                               sh[:, None], axis=1)[:, 0]
    pos = state["top"][sh] + rank
    safe = released & (pos < per)
    free = state["free"].reshape(-1).at[
        jnp.where(safe, sh * per + pos, n_pool)].set(row, mode="drop")
    top = state["top"] + jnp.sum(contrib, axis=0)
    return {"free": free.reshape(shards, per), "top": top,
            "ref": ref.reshape(shards, per)}


def share_pages(state: dict, row: jnp.ndarray, count) -> dict:
    """Add a reference to the first ``count`` entries of ``row`` (a new
    sequence aliasing an existing prefix, read-only from now on)."""
    count = jnp.asarray(count, jnp.int32)
    shards, per = state["free"].shape
    n_pool = shards * per
    held = jnp.arange(row.shape[0], dtype=jnp.int32) < count
    ref = state["ref"].reshape(-1).at[
        jnp.where(held, row, n_pool)].add(1, mode="drop")
    return {"free": state["free"], "top": state["top"],
            "ref": ref.reshape(shards, per)}


# ---------------------------------------------------------------------------
# Cache-level glue: the allocator owns page_table / seq_lens
# ---------------------------------------------------------------------------
def attach_allocator(cache: dict, n_pages: int, shards: int = 1) -> dict:
    """Embed fresh allocator state into a paged cache dict (one donatable
    pytree; called by ``init_cache`` for ``alloc="dynamic"``)."""
    state = init_allocator(n_pages, shards)
    batch = cache["page_table"].shape[0]
    cache["alloc_free"] = state["free"]
    cache["alloc_top"] = state["top"]
    cache["alloc_ref"] = state["ref"]
    cache["alloc_held"] = jnp.zeros((batch,), jnp.int32)
    return cache


def allocator_state(cache: dict) -> dict:
    return {"free": cache["alloc_free"], "top": cache["alloc_top"],
            "ref": cache["alloc_ref"]}


def store_allocator(cache: dict, state: dict) -> dict:
    cache = dict(cache)
    cache["alloc_free"], cache["alloc_top"], cache["alloc_ref"] = \
        state["free"], state["top"], state["ref"]
    return cache


def _page_size(cache: dict) -> int:
    from repro.serving.cache import pool_page_size
    return pool_page_size(cache["k_pages"])


def pool_occupancy(cache: dict) -> tuple[int, int]:
    """(pages in use, pool size) globally — reserved scratch pages count
    as used.  Per-shard truth (which is what admission actually gates on)
    is ``shard_occupancy``."""
    shards, per = cache["alloc_free"].shape
    n = shards * per
    return n - int(jnp.sum(cache["alloc_top"])), n


def shard_occupancy(cache: dict) -> tuple[tuple[int, int], ...]:
    """((pages in use, shard size), …) per pool shard.  Under imbalance
    the global ``pool_occupancy`` number overstates headroom — a request
    is admitted only when *every* shard covers its round-robin share, so
    the binding constraint is the fullest shard reported here."""
    shards, per = cache["alloc_free"].shape
    tops = [int(t) for t in cache["alloc_top"]]
    return tuple((per - t, per) for t in tops)


def admit_sequence(cache: dict, slot: int, n_tokens: int):
    """Allocate pages for a sequence of up to ``n_tokens`` tokens into
    batch row ``slot``.  Returns ``(cache, ok)``; on success the row's
    table entries are the fresh pages (tail = scratch), ``seq_lens`` is
    reset to 0 and ``alloc_held`` records the page count for the
    eventual ``free_sequence``.  On failure the cache is unchanged.
    """
    width = cache["page_table"].shape[1]
    need = ceil_div(int(n_tokens), _page_size(cache))
    assert need <= width, (n_tokens, width)
    state, row, ok = alloc_pages(allocator_state(cache), need, width)
    cache = store_allocator(cache, state)
    cache["page_table"] = cache["page_table"].at[slot].set(
        jnp.where(ok, row, cache["page_table"][slot]))
    cache["seq_lens"] = cache["seq_lens"].at[slot].set(
        jnp.where(ok, 0, cache["seq_lens"][slot]))
    cache["alloc_held"] = cache["alloc_held"].at[slot].set(
        jnp.where(ok, need, cache["alloc_held"][slot]))
    return cache, ok


def free_sequence(cache: dict, slot: int) -> dict:
    """Retire row ``slot``: release its page references (recycling those
    that drop to zero), point the row at scratch, zero its length."""
    row = cache["page_table"][slot]
    state = free_pages(allocator_state(cache), row, cache["alloc_held"][slot])
    cache = store_allocator(cache, state)
    width = cache["page_table"].shape[1]
    cache["page_table"] = cache["page_table"].at[slot].set(
        jnp.full((width,), SCRATCH_PAGE, jnp.int32))
    cache["seq_lens"] = cache["seq_lens"].at[slot].set(0)
    cache["alloc_held"] = cache["alloc_held"].at[slot].set(0)
    return cache


def rewind_sequence(cache: dict, slot: int, new_len: int) -> dict:
    """Rewind row ``slot``'s committed length to ``new_len`` tokens
    (speculative rollback, ``docs/DESIGN.md`` §8).

    The page reservation is untouched — pages are held for the sequence's
    lifetime, so rolling back never moves or frees a page; ``seq_lens``
    drops and every rewound token's row in *every* per-page array
    (``PAGE_STATE_KEYS`` — §2 invariant 5: a quantized pool's scale rows
    rewind with their int8 pages) is zeroed, so a later fork or prefix
    share of the boundary page can never observe rejected-draft state.
    Host-side eager spelling; the in-engine traced form is
    ``cache.invalidate_token_rows``.
    """
    from repro.serving.cache import invalidate_token_rows
    lens = cache["seq_lens"]
    old = int(lens[slot])
    new_len = int(new_len)
    assert 0 <= new_len <= old, (slot, new_len, old)
    cache = dict(cache)
    if old > new_len:
        span = old - new_len
        b = lens.shape[0]
        tok = jnp.broadcast_to(
            new_len + jnp.arange(span, dtype=jnp.int32)[None, :], (b, span))
        inv = jnp.broadcast_to(
            (jnp.arange(b) == slot)[:, None], (b, span))
        cache = invalidate_token_rows(cache, tok, inv)
    cache["seq_lens"] = lens.at[slot].set(new_len)
    return cache


def fork_sequence(cache: dict, parent: int, child: int, prefix_len: int,
                  n_tokens: int, *, copy: bool = False):
    """Admit row ``child`` sharing the first ``prefix_len`` committed
    tokens of row ``parent`` (capacity ``n_tokens`` total).

    The ``prefix_len // page_size`` *full* prefix pages are aliased into
    the child's table (refcount++, read-only); a partially filled
    boundary page is **copied** into a private child page (eager CoW —
    the child's first write lands mid-page), and the remaining capacity
    gets fresh private pages.  ``copy=True`` copies the full pages too
    (no aliasing) — the disjoint twin the sharing tests compare against.

    The child wakes with ``seq_lens = prefix_len``: the prefix is already
    committed, so prefill only runs the suffix.  Returns ``(cache, ok)``.
    """
    page = _page_size(cache)
    width = cache["page_table"].shape[1]
    prefix_len = int(prefix_len)
    full = prefix_len // page if not copy else 0
    copied_pages = ceil_div(prefix_len, page) - full  # boundary (or all) pages
    total = ceil_div(int(n_tokens), page)
    assert prefix_len <= n_tokens and total <= width, (prefix_len, n_tokens)
    private = total - full

    state, prow, ok = alloc_pages(allocator_state(cache), private, width)
    if not bool(ok):
        return store_allocator(cache, state), ok
    state = share_pages(state, cache["page_table"][parent], full)
    cache = store_allocator(cache, state)

    j = jnp.arange(width, dtype=jnp.int32)
    row = jnp.where(j < full, cache["page_table"][parent],
                    jnp.where(j < total,
                              prow[jnp.clip(j - full, 0, width - 1)],
                              SCRATCH_PAGE))
    # eager CoW: copy the parent's partially-committed pages (just the
    # boundary page, or every prefix page under copy=True) into the
    # child's private ids before any child write can land there.  The
    # copy spans every per-page array — a quantized pool's scale rows
    # must travel with their int8 pages or the child would dequantize
    # the copied prefix with stale scales.
    from repro.serving.cache import PAGE_STATE_KEYS
    for c in range(copied_pages):
        src = cache["page_table"][parent, full + c]
        dst = row[full + c]
        for key in PAGE_STATE_KEYS:
            if key in cache:
                cache[key] = cache[key].at[:, dst].set(cache[key][:, src])
    cache["page_table"] = cache["page_table"].at[child].set(row)
    cache["seq_lens"] = cache["seq_lens"].at[child].set(prefix_len)
    cache["alloc_held"] = cache["alloc_held"].at[child].set(total)
    return cache, ok
