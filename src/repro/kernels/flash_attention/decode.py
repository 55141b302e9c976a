"""Pallas TPU kernel: paged-KV flash attention (decode + chunked prefill).

Serving-side companion of ``kernel.py``'s prefill engine, extending the
same schedule vocabulary to the serving cache: instead of a rectangular
``(B, KH, T, D)`` KV tensor, KV lives in a head-major **page pool** ``(P,
KH, page, D)`` addressed through a per-sequence **page table** — and the
KV sweep walks only the pages a sequence actually occupies.  One
physical page holds every KV head as one contiguous ``(KH, page, D)``
slab whose last two dims are whole (sublane, lane) tiles; ``page`` must
be a whole number of the pool dtype's sublane tiles
(``serving/cache.CacheConfig``):

  * **Page blocks, fetched by hand** — the pools stay in HBM
    (``memory_space=pltpu.HBM``) and the kernel issues one DMA per live
    page, ``pool.at[page_table[b, j]]`` → a ``(KH, page, D)`` slot of a
    2-slot VMEM buffer ``(2, pages_per_block, KH, page, D)``.  The page
    table and the per-sequence context lengths ride in scalar-prefetch
    memory (``pltpu.PrefetchScalarGridSpec``), so the kernel resolves
    physical pages itself.  The buffer is **double-buffered across grid
    steps**: each live block starts the copies of the next live block in
    grid order — the next block of its own sequence, or the first block
    of the next (sequence, q block) — before it waits on its own, so the
    page stream overlaps the compute (the scheme of
    ``jax.experimental.pallas.ops.tpu.paged_attention``).  No copy is
    issued for a page outside ``[j_lo, j_hi]``, and a block wholly past
    ``j_hi`` does nothing at all.
  * **Length-aware sweep** — the grid's block extent is the *static*
    page budget ``max_steps`` (page-table width, pruned by the sliding
    window) over ``pages_per_block``, but the per-sequence bounds
    ``[j_lo, j_hi]`` are *dynamic*, read from ``lengths``: a 300-token
    sequence in a 4k-page-table batch streams ceil(300/page) pages, not
    4k/page.
  * **Multi-query-row q blocks** — the q extent is chunked like the
    prefill kernel's (grid dim ``num_q_blocks``, ``q_chunk`` rows per
    block), and each block's page range is bounded by *its own* causal
    horizon: block ``i`` of a cache-writing prefill chunk walks pages
    ``[j_lo(i), (base + (i+1)·q_chunk - 1) // page]`` only.  ``q_len``
    is 1 for plain decode (one block) and a whole prompt chunk for the
    engine's chunked paged prefill (``serving/engine.py``) — the path
    that used to fall back to a dense gather past
    ``attention.PAGED_FLASH_MAX_Q``.
  * **Sliding-window page pruning** — a window of W tokens bounds each
    q block's visible span to ``q_chunk + W - 1`` tokens, i.e. at most
    ``ceil((q_chunk + W - 1)/page) + 1`` pages, independent of context
    length; ``j_lo`` starts the walk at the window's first page.
  * **GQA-native grouping, all KV heads a step** — one grid step serves
    every KV head of its sequence: each fetched ``(KH, page, D)`` slab
    is consumed by all ``KH`` query groups in a static loop over the
    heads, and each head's ``g = H // KH`` query heads are rows of one
    ``(g · q_chunk, D)`` q block — so each page is fetched **once** per
    (sequence, q block).  A block is one QKᵀ contraction
    ``(g·q_chunk, D) × (pages_per_block·page, D)ᵀ`` and one online-softmax
    update per head.
  * **Block size from shapes** — ``pages_per_block`` is the most pages
    (at most ``MAX_PAGES_PER_BLOCK``, at most the page budget) whose
    buffers, scores and accumulators fit ``tiling.VMEM_PLAN_BUDGET``
    (``flash_decode_schedule``): a decode step's 8 q rows a head get a
    large block, a chunked prefill's ``g · 128`` rows a smaller one.
  * **In-kernel masking** — causality against the per-row position
    ``base + i·q_chunk + (row mod q_chunk)`` (``base = ctx - q_len``)
    and the window bound are fused broadcasted-iota compares, exactly the
    prefill kernel's machinery.  The same compare masks the
    partially-filled last page and the buffer slots past ``j_hi``,
    which hold no fetched page (stale or uninitialised VMEM): scores are
    replaced by ``where``, and K and V rows past the block's last fetched
    token are zeroed by ``where`` before the contractions (0 · NaN would
    poison them).  Partial q chunks are native: out-of-range rows produce
    row-local garbage that Pallas drops at the out-of-range output
    store.
  * **n-token verify mode** — an optional third scalar-prefetch operand
    ``new_lens`` (B,) makes the live new-token count *per sequence*
    dynamic: row ``r`` of sequence ``b`` sits at position
    ``ctx - new_lens[b] + r`` and rows ``r >= new_lens[b]`` are fully
    masked (0 output, the all-masked-row convention).  This is the
    speculative draft-and-verify step (``serving/engine.py``): the
    causal compare against per-row positions IS the commit horizon — a
    drafted token's KV row is visible only to later rows of its own
    step, never to any committed position, so rejecting it is a pure
    ``seq_lens`` rewind (``docs/DESIGN.md`` §8).  ``new_lens=None``
    keeps the exact 2-operand launch (bitwise-identical plain decode).

Grid (b, i, jb): b the sequence, i the q block, jb the block of
``pages_per_block`` schedule-relative pages, innermost; every dim runs in
order (the cross-step prefetch needs it).  VMEM scratch carries (acc f32
(KH, g·q_chunk, D), m, l) across jb and re-initializes per (b, i).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tiling import (LANES, VMEM_LIMIT_BYTES, VMEM_PLAN_BUDGET,
                               ceil_div, round_up, vmem_tile_bytes)

NEG_INF = -2.3819763e38
# past 16 pages a block saves few grid steps more while the masked tail
# of a sequence's last block grows
MAX_PAGES_PER_BLOCK = 16

__all__ = ["FlashDecodeSchedule", "blocks_touched", "flash_decode_schedule",
           "grid_steps", "paged_decode_kernel", "pages_touched"]


@dataclasses.dataclass(frozen=True)
class FlashDecodeSchedule:
    """Static plan for one paged attention launch.

    ``max_steps`` is the page budget per q block the sweep plans for; the
    pages actually streamed are the dynamic per-(sequence, block)
    ``[j_lo, j_hi]`` ranges — ``pages_touched`` counts them for a
    concrete batch of lengths.  ``max_steps < max_pages`` whenever the
    sliding window prunes the walk.  ``q_len`` is the total new rows per
    sequence, processed as ``num_q_blocks`` blocks of ``q_chunk`` rows
    (one block for plain decode).  Each grid step streams up to
    ``pages_per_block`` pages for every KV head; ``vmem_bytes`` is the
    VMEM a step holds at that block size.
    """

    page_size: int
    max_pages: int
    q_len: int
    window: int | None
    max_steps: int
    q_chunk: int = 1
    num_q_blocks: int = 1
    pages_per_block: int = 1
    vmem_bytes: int = 0

    @property
    def num_blocks(self) -> int:
        """Launched page blocks per (sequence, q block)."""
        return ceil_div(self.max_steps, self.pages_per_block)


def decode_vmem_bytes(pages_per_block: int, *, rows: int, kv_heads: int,
                      page_size: int, head_dim: int, kv_dtype) -> int:
    """VMEM one grid step holds: the 2-slot page buffers (and scale
    buffers and dequantized K/V for int8 pools), the pipelined q and
    output blocks (counted as f32), the accumulators, and the score-sized
    f32/int32 temporaries of one head (scores, probabilities, iotas,
    masks).  ``rows`` is ``g · q_chunk``, the q rows of one head."""
    ppb, kh, ps, d = pages_per_block, kv_heads, page_size, head_dim
    kv_item = jnp.dtype(kv_dtype).itemsize
    quant = jnp.issubdtype(kv_dtype, jnp.integer)
    cols = ppb * ps
    total = 2 * 2 * ppb * kh * vmem_tile_bytes(ps, d, kv_item)
    if quant:
        total += 2 * 2 * ppb * vmem_tile_bytes(kh, ps, 4)
        total += 2 * 2 * ppb * kh * vmem_tile_bytes(ps, d, 4)
    total += 2 * 2 * kh * vmem_tile_bytes(rows, d, 4)       # q, out
    total += kh * (vmem_tile_bytes(rows, d, 4)
                   + 2 * vmem_tile_bytes(rows, 1, 4))       # acc, m, l
    total += 6 * vmem_tile_bytes(rows, cols, 4)             # s, p, masks
    total += 2 * vmem_tile_bytes(cols, d, 4)                # K, V values
    return total


def flash_decode_schedule(max_pages: int, page_size: int, *,
                          q_len: int = 1,
                          window: int | None = None,
                          q_chunk: int | None = None,
                          group: int = 1, kv_heads: int = 1,
                          head_dim: int = 128,
                          kv_dtype=jnp.bfloat16) -> FlashDecodeSchedule:
    """Plan the paged KV sweep for a decode / chunked-prefill step.

    Args:
      max_pages: page-table width (logical page budget per sequence).
      page_size: tokens per page.
      q_len: new tokens attended per step (1 for plain decode; the
        prompt-chunk size for chunked paged prefill).
      window: sliding-window size in tokens, or None for global layers.
      q_chunk: q rows per block (default: all of ``q_len`` in one block
        — right for decode-sized steps; chunked prefill passes a fixed
        block size so VMEM holds ``g · q_chunk`` rows, not the chunk).
      group, kv_heads, head_dim, kv_dtype: query heads per KV head, KV
        heads, head size and pool dtype — the shapes the page-block size
        is fitted to.

    The launched KV extent is ``max_pages`` for global layers; a window
    bounds each q block's visible token span to ``q_chunk + window - 1``
    and with it the page span to ``ceil(span / page_size) + 1`` (the +1
    covers an unaligned window straddling one extra page boundary).
    ``pages_per_block`` is the largest block, up to
    ``MAX_PAGES_PER_BLOCK`` and the page budget, whose
    ``decode_vmem_bytes`` fit ``VMEM_PLAN_BUDGET``.
    """
    assert max_pages >= 1 and page_size >= 1 and q_len >= 1
    q_chunk = min(q_chunk or q_len, q_len)
    num_q_blocks = ceil_div(q_len, q_chunk)
    max_steps = max_pages
    if window is not None:
        span = q_chunk + window - 1
        max_steps = min(max_pages, ceil_div(span, page_size) + 1)
    footprint = functools.partial(
        decode_vmem_bytes, rows=group * q_chunk, kv_heads=kv_heads,
        page_size=page_size, head_dim=head_dim, kv_dtype=kv_dtype)
    ppb = min(max_steps, MAX_PAGES_PER_BLOCK)
    while ppb > 1 and footprint(ppb) > VMEM_PLAN_BUDGET:
        ppb -= 1
    return FlashDecodeSchedule(page_size=page_size, max_pages=max_pages,
                               q_len=q_len, window=window,
                               max_steps=max_steps, q_chunk=q_chunk,
                               num_q_blocks=num_q_blocks,
                               pages_per_block=ppb,
                               vmem_bytes=footprint(ppb))


def _page_bounds(ctx, i, *, q_len, q_chunk, page_size, window,
                 _min=jnp.minimum, _max=jnp.maximum):
    """Inclusive [j_lo, j_hi] logical-page range visible to q block ``i``
    of a context of ``ctx`` tokens (the step's ``q_len`` rows occupy
    positions ``ctx - q_len .. ctx - 1``; block ``i`` holds rows
    ``i*q_chunk .. (i+1)*q_chunk - 1`` of those).

    Traced int32 in the kernel body; Python ints (with ``min``/``max``
    passed in) in the counters.
    """
    base = ctx - q_len
    last = _min(base + (i + 1) * q_chunk - 1, ctx - 1)
    j_hi = _max(last, 0) // page_size
    j_lo = 0
    if window is not None:
        # first k visible to the block's oldest row (pos base + i*q_chunk):
        # k > pos - window  =>  k_min = max(pos - window + 1, 0)
        first_k = _max(base + i * q_chunk - window + 1, 0)
        j_lo = _min(first_k // page_size, j_hi)
    return j_lo, j_hi


def _walks(lengths, sched: FlashDecodeSchedule):
    """[j_lo, j_hi] of every (sequence, q block) over concrete lengths."""
    for ctx in lengths:
        for i in range(sched.num_q_blocks):
            yield _page_bounds(int(ctx), i, q_len=sched.q_len,
                               q_chunk=sched.q_chunk,
                               page_size=sched.page_size,
                               window=sched.window, _min=min, _max=max)


def pages_touched(lengths, sched: FlashDecodeSchedule) -> int:
    """KV pages streamed for one step over a batch of context lengths
    (post-write, i.e. including the step's new tokens) — the analytic
    benchmark counter (cf. ``FlashSchedule.blocks_touched``).  Sums over
    the q blocks: a chunked prefill streams early pages once per later
    block, exactly as the launched walk does."""
    return sum(j_hi - j_lo + 1 for j_lo, j_hi in _walks(lengths, sched))


def blocks_touched(lengths, sched: FlashDecodeSchedule) -> int:
    """Page blocks that stream and compute for one step (each holds one
    to ``pages_per_block`` live pages of every KV head); the rest of
    ``grid_steps`` are blocks past ``j_hi`` that do nothing."""
    ppb = sched.pages_per_block
    return sum((j_hi - j_lo) // ppb + 1
               for j_lo, j_hi in _walks(lengths, sched))


def grid_steps(sched: FlashDecodeSchedule, batch: int) -> int:
    """Grid steps one launch over ``batch`` sequences pays for."""
    return batch * sched.num_q_blocks * sched.num_blocks


def _head_scales(rows, h, page_size):
    """Row ``h`` of a (KH, lanes) block of scale rows as a (page, 1)
    column, one scale per page slot (the rows are lane-padded past
    ``page_size``).  The row is picked by a masked sum (exact: one value
    plus zeros), which needs no dynamic sublane slice."""
    sel = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0) == h
    row = jnp.sum(jnp.where(sel, rows, 0.0), axis=0, keepdims=True)
    return row.reshape(rows.shape[1], 1)[:page_size]


def _pad_lanes(x):
    """``x`` with its minor dim zero-padded to whole 128-lane tiles."""
    pad = round_up(x.shape[-1], LANES) - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def _decode_kernel(pt_ref, len_ref, *rest, scale, window, softcap,
                   sched: FlashDecodeSchedule, batch, out_dtype,
                   quant: bool, has_new_lens: bool = False):
    if has_new_lens:
        # verify mode: third scalar-prefetch operand — per-sequence live
        # new-row counts (rows past them are fully masked)
        nl_ref, rest = rest[0], rest[1:]
    else:
        nl_ref = None
    # HBM sources and their VMEM page buffers, in the same order: K, V
    # and, for the int8 layout, the (KH, ps) scale rows of each page
    n_src = 4 if quant else 2
    q_ref, rest = rest[0], rest[1:]
    srcs, rest = rest[:n_src], rest[n_src:]
    o_ref, rest = rest[0], rest[1:]
    bufs, rest = rest[:n_src], rest[n_src:]
    sem, slot_ref, acc_ref, m_ref, l_ref, *deq = rest

    b = pl.program_id(0)
    i = pl.program_id(1)
    jb = pl.program_id(2)
    ps, qc = sched.page_size, sched.q_chunk
    ppb, nq = sched.pages_per_block, sched.num_q_blocks
    kh, rows, d = acc_ref.shape
    cols = ppb * ps
    bounds = functools.partial(_page_bounds, q_len=sched.q_len, q_chunk=qc,
                               page_size=ps, window=window)

    def for_live_pages(sb, si, sjb, slot, action):
        """``action`` on the copy of each live page of block ``sjb`` of
        (sb, si) — page ``j_lo + sjb·ppb + t`` of every source into page
        slot ``t`` of buffer slot ``slot`` — and on no other."""
        j_lo, j_hi = bounds(len_ref[sb], si)
        j0 = j_lo + sjb * ppb

        def page(t, carry):
            phys = pt_ref[sb, j0 + t]
            for n, (src, buf) in enumerate(zip(srcs, bufs)):
                action(pltpu.make_async_copy(src.at[phys], buf.at[slot, t],
                                             sem.at[n, slot]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(j_hi - j0 + 1, ppb), page, 0)

    def start(copy):
        copy.start()

    def wait(copy):
        copy.wait()

    ctx = len_ref[b]
    j_lo, j_hi = bounds(ctx, i)
    j0 = j_lo + jb * ppb                    # first page of this block

    @pl.when(jb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j0 <= j_hi)
    def _block():
        # block 0 of every (b, i) is live, so the grid's first step is
        # the first live block and every later one was prefetched
        @pl.when((b == 0) & (i == 0) & (jb == 0))
        def _first():
            slot_ref[0] = 0
            for_live_pages(b, i, jb, 0, start)

        cur = slot_ref[0]
        # the next live block in grid order: this (b, i)'s next block,
        # else block 0 of the next q block, else of the next sequence
        more = j0 + ppb <= j_hi
        next_i = i + 1 < nq
        nb = jnp.where(more | next_i, b, b + 1)
        ni = jnp.where(more, i, jnp.where(next_i, i + 1, 0))
        njb = jnp.where(more, jb + 1, 0)

        @pl.when(nb < batch)
        def _prefetch():
            for_live_pages(nb, ni, njb, 1 - cur, start)
            slot_ref[0] = 1 - cur

        for_live_pages(b, i, jb, cur, wait)

        # rows are the query group laid out (g, qc) flattened: row r is
        # query token i*qc + r % qc at position ctx - q_len + i*qc + r % qc
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
        k_pos = j0 * ps + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
        if has_new_lens:
            # verify mode: the live new-row count is dynamic per sequence
            # (ctx = committed + new_lens[b]); rows at or past it belong
            # to no token and are masked outright
            row_idx = i * qc + row % qc
            q_pos = ctx - nl_ref[b] + row_idx
            allowed = (k_pos <= q_pos) & (row_idx < nl_ref[b])
        else:
            q_pos = ctx - sched.q_len + i * qc + row % qc
            # causal + page tail in one; slots past j_hi hold positions
            # past every row of the block, so this masks them too
            allowed = k_pos <= q_pos
        if window is not None:
            allowed &= k_pos > q_pos - window
        # K/V rows up to the block's last fetched token: past it lie the
        # uncommitted tail of the last page and the slots past j_hi,
        # which hold stale VMEM (0 · NaN would poison the contractions)
        kv_pos = j0 * ps + jax.lax.broadcasted_iota(jnp.int32, (cols, d), 0)
        kv_live = kv_pos < jnp.minimum(ctx, (j_hi + 1) * ps)

        k_src, v_src = bufs[:2]
        if quant:
            # fused dequant: values·scale in f32, right off the DMA — the
            # fp page never exists in HBM, only this VMEM block, laid out
            # as the fp page buffers so both layouts share what follows
            k_src, v_src = deq
            for t in range(ppb):
                for h in range(kh):
                    for val, scl, out in ((0, 2, k_src), (1, 3, v_src)):
                        out[cur, t, h] = (
                            bufs[val][cur, t, h].astype(jnp.float32)
                            * _head_scales(bufs[scl][cur, t], h, ps))

        for h in range(kh):
            q = q_ref[0, h].reshape(rows, d)            # (g·qc, D)
            if quant:
                q = q.astype(jnp.float32)
            k = jnp.where(kv_live, k_src[cur, :, h].reshape(cols, d), 0)
            v = jnp.where(kv_live, v_src[cur, :, h].reshape(cols, d), 0)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(allowed, s, NEG_INF)

            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            # rows with no visible KV yet have m_new == NEG_INF → exp(0):
            # re-mask
            p = jnp.where(allowed, p, 0.0)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(jb == pl.num_programs(2) - 1)
    def _epilogue():
        g = o_ref.shape[2]
        for h in range(kh):
            o = acc_ref[h] / jnp.maximum(l_ref[h], 1e-37)
            o_ref[0, h] = o.reshape(g, qc, d).astype(out_dtype)


def paged_decode_kernel(q: jax.Array, k_pages: jax.Array,
                        v_pages: jax.Array, page_table: jax.Array,
                        lengths: jax.Array, *, scale: float,
                        window: int | None = None,
                        softcap: float | None = None,
                        q_chunk: int | None = None,
                        k_scales: jax.Array | None = None,
                        v_scales: jax.Array | None = None,
                        new_lens: jax.Array | None = None,
                        out_dtype=None, interpret: bool = False):
    """Paged flash attention over a page pool.  Shapes:

      q          (B, H, q_len, D) — the step's new queries (1 for plain
                 decode, a whole prompt chunk for chunked prefill),
      k_pages    (P, KH, page, D) — one layer's head-major KV page pool
                 (v_pages alike),
      page_table (B, max_pages) int32 — physical page of logical page j,
      lengths    (B,) int32 — context length *including* the q_len new
                 tokens (their K/V must already be committed to the pages).

    Returns (B, H, q_len, D) in ``out_dtype`` (default q.dtype).  H must
    be a multiple of KH; each page is fetched once per (sequence,
    q block) as one ``(KH, page, D)`` slab, in blocks of
    ``pages_per_block`` pages, and consumed by every query group.
    ``q_chunk`` bounds the rows resident per block (default: all of
    q_len in one block — right for decode-sized steps); the page table
    and lengths travel via scalar prefetch so the kernel resolves
    physical pages before each DMA.

    ``k_scales``/``v_scales`` (P, KH, page) f32 select the quantized
    layout (``kv_quant="int8"``): the pools hold int8 rows and each
    page's ``(KH, page)`` scale rows are copied beside its values, with
    dequantization (``values.astype(f32) * scale``) fused into the
    kernel body ahead of the QK/PV contractions.  The fp pages never
    materialize in HBM; the per-step KV bytes drop to ``1 + 4/D`` per
    element vs 2 for bf16.

    ``new_lens`` (B,) int32 selects the n-token **verify mode**
    (speculative decode): row ``r`` of sequence ``b`` is live iff
    ``r < new_lens[b]`` and sits at position ``lengths[b] - new_lens[b]
    + r`` (``lengths`` stays committed + live new tokens).  Dead rows
    come back fully masked (0 output).  The page walk keeps the static
    ``q_len`` bounds — a conservative superset whose extra pages
    contribute exact zeros to the online softmax — and ``None`` keeps
    the 2-operand launch bitwise identical to plain decode.
    """
    b, h, qs, d = q.shape
    p_total, kh, ps, dk = k_pages.shape
    assert d == dk and h % kh == 0, (q.shape, k_pages.shape)
    assert v_pages.shape == k_pages.shape
    quant = k_scales is not None
    assert quant == (v_scales is not None), "need both scale pools or neither"
    if quant:
        assert k_scales.shape == (p_total, kh, ps), (
            k_scales.shape, k_pages.shape)
        assert v_scales.shape == k_scales.shape
    max_pages = page_table.shape[1]
    assert page_table.shape == (b, max_pages)
    g = h // kh
    out_dtype = out_dtype or q.dtype
    sched = flash_decode_schedule(max_pages, ps, q_len=qs, window=window,
                                  q_chunk=q_chunk, group=g, kv_heads=kh,
                                  head_dim=d, kv_dtype=k_pages.dtype)
    qc, ppb = sched.q_chunk, sched.pages_per_block

    # Mosaic slices a page out of an HBM pool only if the pool's minor
    # dim is whole 128-lane tiles: zero-pad head_dim (and the scale rows'
    # page dim) up to them.  A no-op for head_dim 128 pools; the padded
    # lanes add exact zeros to QK and come back as output lanes dropped
    # below.
    q, k_pages, v_pages = map(_pad_lanes, (q, k_pages, v_pages))
    dp = q.shape[-1]
    if quant:
        k_scales, v_scales = _pad_lanes(k_scales), _pad_lanes(v_scales)

    # (B, H, qs, D) → (B, KH, g, qs, D): group rows of one KV head together
    qg = q.reshape(b, kh, g, qs, dp)

    # the index map takes the scalar refs as trailing varargs, so both
    # launch arities (with and without new_lens) share one definition
    def q_index(sb, i, jb, *_refs):
        return (sb, 0, 0, i, 0)

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, softcap=softcap,
        sched=sched, batch=b, out_dtype=out_dtype, quant=quant,
        has_new_lens=new_lens is not None)
    pools = [k_pages, v_pages] + ([k_scales, v_scales] if quant else [])
    scratch = [pltpu.VMEM((2, ppb) + pool.shape[1:], pool.dtype)
               for pool in pools]
    scratch += [
        pltpu.SemaphoreType.DMA((len(pools), 2)),
        pltpu.SMEM((1,), jnp.int32),          # slot of the current block
        pltpu.VMEM((kh, g * qc, dp), jnp.float32),
        pltpu.VMEM((kh, g * qc, 1), jnp.float32),
        pltpu.VMEM((kh, g * qc, 1), jnp.float32),
    ]
    if quant:
        scratch += [pltpu.VMEM((2, ppb) + k_pages.shape[1:], jnp.float32)] * 2
    scalars = [page_table.astype(jnp.int32), lengths.astype(jnp.int32)]
    if new_lens is not None:
        assert new_lens.shape == (b,), (new_lens.shape, b)
        scalars.append(new_lens.astype(jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, sched.num_q_blocks, sched.num_blocks),
        in_specs=[pl.BlockSpec((1, kh, g, qc, dp), q_index)]
        + [pl.BlockSpec(memory_space=pltpu.HBM)] * len(pools),
        out_specs=pl.BlockSpec((1, kh, g, qc, dp), q_index),
        scratch_shapes=scratch,
    )
    # ``name`` opens a named scope around the call, so under every caller
    # (prefill chunks, the decode tick) the compiled custom call, and the
    # profiler trace, name it ``paged_flash.N`` and not after an
    # enclosing remat ``checkpoint.N``; the other kernels do the same
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, qs, dp), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret, name="paged_flash",
    )(*scalars, qg, *pools)
    return out.reshape(b, h, qs, dp)[..., :d]
