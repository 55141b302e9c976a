"""Pallas TPU kernel: paged-KV flash attention (decode + chunked prefill).

Serving-side companion of ``kernel.py``'s prefill engine, extending the
same schedule vocabulary to the serving cache: instead of a rectangular
``(B, KH, T, D)`` KV tensor, KV lives in a head-major **page pool** ``(P,
KH, page, D)`` addressed through a per-sequence **page table** — and the
KV sweep walks only the pages a sequence actually occupies.  Head-major
makes each streamed KV block ``(page, D)`` in its last two dims, the
(sublane, lane) tiling Mosaic requires; ``page`` must be a whole number
of the pool dtype's sublane tiles (``serving/cache.CacheConfig``):

  * **Page-table index map** — the page table and the per-sequence
    context lengths ride in scalar-prefetch memory
    (``pltpu.PrefetchScalarGridSpec``), so the KV BlockSpec index map can
    compute, per grid step, the *physical* page id
    ``page_table[b, min(j_lo + jj, j_hi)]`` before the DMA is issued.
    Fully out-of-range steps revisit ``j_hi`` (the clamped walk of
    ``kernel.py`` — unchanged block index, copy elided) and are
    compute-guarded with ``pl.when``.
  * **Length-aware sweep** — the grid's KV extent is the *static* page
    budget ``max_steps`` (page-table width, pruned by the sliding
    window), but the per-sequence bounds ``[j_lo, j_hi]`` are *dynamic*,
    read from ``lengths``: a 300-token sequence in a 4k-page-table batch
    streams ceil(300/page) pages, not 4k/page.
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
  * **GQA-native grouping** — the leading grid dim is ``B · KH``: each
    KV head's page stream is fetched **once** and consumed by all ``g =
    H // KH`` query heads of its group, laid out as rows of one
    ``(g · q_chunk, D)`` q block (the decode analogue of the prefill
    kernel's index-map broadcast).
  * **In-kernel masking** — causality against the per-row position
    ``base + i·q_chunk + (row mod q_chunk)`` (``base = ctx - q_len``)
    and the window bound are fused broadcasted-iota compares, exactly the
    prefill kernel's machinery; the partially-filled last page is masked
    by the same compare (and the page's undefined V tail is zeroed
    before the PV product).  Partial q chunks are native: out-of-range
    rows produce row-local garbage that Pallas drops at the
    out-of-range output store.
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

Grid (n, i, jj): n = B·KH flat KV-head index, i the q block, jj the
schedule-relative page step, innermost; VMEM scratch carries (acc f32
(g·q_chunk, D), m, l) across jj and re-initializes per (n, i).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tiling import ceil_div

NEG_INF = -2.3819763e38

__all__ = ["FlashDecodeSchedule", "flash_decode_schedule",
           "paged_decode_kernel", "pages_touched"]


@dataclasses.dataclass(frozen=True)
class FlashDecodeSchedule:
    """Static plan for one paged attention launch.

    ``max_steps`` is the launched KV-grid extent (pages per q block the
    sweep *budgets* for); the pages actually streamed are the dynamic
    per-(sequence, block) ``[j_lo, j_hi]`` ranges — ``pages_touched``
    counts them for a concrete batch of lengths.  ``max_steps <
    max_pages`` whenever the sliding window prunes the walk.  ``q_len``
    is the total new rows per sequence, processed as ``num_q_blocks``
    blocks of ``q_chunk`` rows (one block for plain decode).
    """

    page_size: int
    max_pages: int
    q_len: int
    window: int | None
    max_steps: int
    q_chunk: int = 1
    num_q_blocks: int = 1


def flash_decode_schedule(max_pages: int, page_size: int, *,
                          q_len: int = 1,
                          window: int | None = None,
                          q_chunk: int | None = None) -> FlashDecodeSchedule:
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

    The launched KV extent is ``max_pages`` for global layers; a window
    bounds each q block's visible token span to ``q_chunk + window - 1``
    and with it the page span to ``ceil(span / page_size) + 1`` (the +1
    covers an unaligned window straddling one extra page boundary).
    """
    assert max_pages >= 1 and page_size >= 1 and q_len >= 1
    q_chunk = min(q_chunk or q_len, q_len)
    num_q_blocks = ceil_div(q_len, q_chunk)
    max_steps = max_pages
    if window is not None:
        span = q_chunk + window - 1
        max_steps = min(max_pages, ceil_div(span, page_size) + 1)
    return FlashDecodeSchedule(page_size=page_size, max_pages=max_pages,
                               q_len=q_len, window=window,
                               max_steps=max_steps, q_chunk=q_chunk,
                               num_q_blocks=num_q_blocks)


def _page_bounds(ctx, i, *, q_len, q_chunk, page_size, window,
                 _min=jnp.minimum, _max=jnp.maximum):
    """Inclusive [j_lo, j_hi] logical-page range visible to q block ``i``
    of a context of ``ctx`` tokens (the step's ``q_len`` rows occupy
    positions ``ctx - q_len .. ctx - 1``; block ``i`` holds rows
    ``i*q_chunk .. (i+1)*q_chunk - 1`` of those).

    Traced int32 in the index maps / kernel body; Python ints (with
    ``min``/``max`` passed in) in ``pages_touched``.
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


def pages_touched(lengths, sched: FlashDecodeSchedule) -> int:
    """KV pages streamed for one step over a batch of context lengths
    (post-write, i.e. including the step's new tokens) — the analytic
    benchmark counter (cf. ``FlashSchedule.blocks_touched``).  Sums over
    the q blocks: a chunked prefill streams early pages once per later
    block, exactly as the launched walk does."""
    total = 0
    for ctx in lengths:
        for i in range(sched.num_q_blocks):
            j_lo, j_hi = _page_bounds(int(ctx), i, q_len=sched.q_len,
                                      q_chunk=sched.q_chunk,
                                      page_size=sched.page_size,
                                      window=sched.window, _min=min,
                                      _max=max)
            total += j_hi - j_lo + 1
    return total


def _head_scales(rows, h):
    """Row ``h`` of a (KH, ps) block of scale rows as a (ps, 1) column,
    one scale per page slot.  The row is picked by a masked sum (exact:
    one value plus zeros), which needs no dynamic sublane slice."""
    sel = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0) == h
    row = jnp.sum(jnp.where(sel, rows, 0.0), axis=0, keepdims=True)
    return row.reshape(rows.shape[1], 1)


def _decode_kernel(pt_ref, len_ref, *rest, scale, window, softcap,
                   sched: FlashDecodeSchedule, kh, out_dtype, quant: bool,
                   has_new_lens: bool = False):
    if has_new_lens:
        # verify mode: third scalar-prefetch operand — per-sequence live
        # new-row counts (rows past them are fully masked)
        nl_ref, rest = rest[0], rest[1:]
    else:
        nl_ref = None
    q_ref, k_ref, v_ref, *rest = rest
    if quant:
        # the int8 layout streams two extra per-page operands: the
        # (1, KH, ps) scale rows riding the same clamped page walk
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    n = pl.program_id(0)
    i = pl.program_id(1)
    jj = pl.program_id(2)
    b = n // kh
    ps, qc = sched.page_size, sched.q_chunk
    ctx = len_ref[b]
    j_lo, j_hi = _page_bounds(ctx, i, q_len=sched.q_len, q_chunk=qc,
                              page_size=ps, window=window)
    j = jnp.minimum(j_lo + jj, j_hi)        # must match the KV index map

    @pl.when(jj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j_lo + jj <= j_hi)
    def _compute():
        g = q_ref.shape[2]
        q = q_ref[0, 0].reshape(g * qc, q_ref.shape[-1])    # (g·qc, D)
        k = k_ref[0, 0]                     # (ps, D)
        v = v_ref[0, 0]                     # (ps, D)
        if quant:
            # fused dequant: values·scale in f32, right off the DMA — the
            # fp page never exists in HBM (only this VMEM tile does)
            k = k.astype(jnp.float32) * _head_scales(ks_ref[0], n % kh)
            v = v.astype(jnp.float32) * _head_scales(vs_ref[0], n % kh)
            q = q.astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)

        # rows are the query group laid out (g, qc) flattened: row r is
        # query token i*qc + r % qc at position ctx - q_len + i*qc + r % qc
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = j * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if has_new_lens:
            # verify mode: the live new-row count is dynamic per sequence
            # (ctx = committed + new_lens[b]); rows at or past it belong
            # to no token and are masked outright
            row_idx = i * qc + row % qc
            q_pos = ctx - nl_ref[b] + row_idx
            allowed = (k_pos <= q_pos) & (row_idx < nl_ref[b])
        else:
            q_pos = ctx - sched.q_len + i * qc + row % qc
            allowed = k_pos <= q_pos        # causal + page tail in one
        if window is not None:
            allowed &= k_pos > q_pos - window
        s = jnp.where(allowed, s, NEG_INF)
        # zero the last page's uncommitted V tail (0 · NaN would poison PV)
        vrow = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(j * ps + vrow < ctx, v, 0)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # rows with no visible KV yet have m_new == NEG_INF → exp(0): re-mask
        p = jnp.where(allowed, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(jj == pl.num_programs(2) - 1)
    def _epilogue():
        g = o_ref.shape[2]
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-37)
        o_ref[0, 0] = o.reshape(g, qc, o_ref.shape[-1]).astype(out_dtype)


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
    be a multiple of KH; each KV head's page stream is fetched once per
    (b, kv-head, q-block) grid cell and consumed by its whole query
    group.  ``q_chunk`` bounds the rows resident per block (default: all
    of q_len in one block — right for decode-sized steps); the page
    table and lengths travel via scalar prefetch so the KV index map
    resolves physical pages before each DMA.

    ``k_scales``/``v_scales`` (P, KH, page) f32 select the quantized
    layout (``kv_quant="int8"``): the pools hold int8 rows and the scale
    pools stream alongside them through the *same* clamped page walk —
    one (1, KH, ps) block of scale rows per KV page block, of which the
    kernel uses its own head's row — with dequantization
    (``values.astype(f32) * scale``) fused into the kernel body ahead of
    the QK/PV contractions.  The fp pages never materialize in HBM; the
    per-step KV bytes drop to ``1 + 4/D`` per element vs 2 for bf16.

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
                                  q_chunk=q_chunk)
    qc = sched.q_chunk

    # (B, H, qs, D) → (B, KH, g, qs, D): group rows of one KV head together
    qg = q.reshape(b, kh, g, qs, d)

    bounds = functools.partial(_page_bounds, q_len=qs, q_chunk=qc,
                               page_size=ps, window=window)

    # verify mode streams new_lens as a third scalar-prefetch operand; the
    # index maps take the scalar refs as trailing varargs so both launch
    # arities share one definition (page bounds read only the lengths —
    # the static-q_len superset is exact under masking, see docstring)
    def q_index(n, i, jj, *_refs):
        return (n // kh, n % kh, 0, i, 0)

    def kv_index(n, i, jj, pt_ref, len_ref, *_refs):
        sb = n // kh
        j_lo, j_hi = bounds(len_ref[sb], i)
        # clamped sparse walk: trailing steps revisit j_hi (copy elided)
        return (pt_ref[sb, jnp.minimum(j_lo + jj, j_hi)], n % kh, 0, 0)

    def scale_index(n, i, jj, pt_ref, len_ref, *_refs):
        # the scale rows of exactly the page the KV walk fetches (all KH
        # of them: a (1, ps) block would break the (8, 128) tiling rule)
        sb = n // kh
        j_lo, j_hi = bounds(len_ref[sb], i)
        return (pt_ref[sb, jnp.minimum(j_lo + jj, j_hi)], 0, 0)

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, softcap=softcap,
        sched=sched, kh=kh, out_dtype=out_dtype, quant=quant,
        has_new_lens=new_lens is not None)
    in_specs = [
        pl.BlockSpec((1, 1, g, qc, d), q_index),
        pl.BlockSpec((1, 1, ps, d), kv_index),
        pl.BlockSpec((1, 1, ps, d), kv_index),
    ]
    operands = [qg, k_pages, v_pages]
    if quant:
        in_specs += [pl.BlockSpec((1, kh, ps), scale_index),
                     pl.BlockSpec((1, kh, ps), scale_index)]
        operands += [k_scales, v_scales]
    scalars = [page_table.astype(jnp.int32), lengths.astype(jnp.int32)]
    if new_lens is not None:
        assert new_lens.shape == (b,), (new_lens.shape, b)
        scalars.append(new_lens.astype(jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b * kh, sched.num_q_blocks, sched.max_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, qc, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((g * qc, d), jnp.float32),
            pltpu.VMEM((g * qc, 1), jnp.float32),
            pltpu.VMEM((g * qc, 1), jnp.float32),
        ],
    )
    # ``name`` opens a named scope around the call, so under every caller
    # (prefill chunks, the decode tick) the compiled custom call, and the
    # profiler trace, name it ``paged_flash.N`` and not after an
    # enclosing remat ``checkpoint.N``; the other kernels do the same
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, qs, d), out_dtype),
        interpret=interpret, name="paged_flash",
    )(*scalars, *operands)
    return out.reshape(b, h, qs, d)
