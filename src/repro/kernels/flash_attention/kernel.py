"""Pallas TPU kernel: window-aware block-sparse flash attention.

Beyond-paper companion kernel: the paper accelerates the Q/K/V projection
GEMMs; this kernel accelerates the attention that consumes them with the
same design vocabulary — two-level tiling (HBM→VMEM blocks feeding the
MXU), persistent per-row state (running max/sum/accumulator live in VMEM
scratch across the KV sweep, exactly the update_A persistence idea applied
to softmax statistics), a fused epilogue (the 1/l normalization), and a
*schedule* chosen from the mask structure, mirroring the GEMM dispatcher's
schedule-aware plans:

  * **Block-sparse KV sweep** — ``flash_schedule`` derives, per q block,
    the inclusive KV-block range ``[j_lo, j_hi]`` actually visible under
    the causal/sliding-window masks.  The KV grid dimension is sized to
    the *maximum* range (``max_kv_steps``, ≪ the dense T/kc for windowed
    layers) and the BlockSpec index map walks ``j_lo + jj`` clamped at
    ``j_hi`` — so fully-masked KV blocks are never streamed from HBM
    (clamped trailing steps revisit the last real block, which the
    pipeline elides as an unchanged block index), not merely
    compute-guarded with ``pl.when``.
  * **In-kernel masking** — causal and sliding-window (gemma2-style local
    layers) masks are fused broadcasted-iota comparisons on the score
    block; no (S, T) bias tensor ever exists.
  * **GQA-native KV** — q is (B, H, S, D), k/v stay (B, KH, T, D); the KV
    index map broadcasts head ``n % h`` to KV head ``(n % h) // g``, so
    grouped KV is *addressed* g× rather than materialized g× in HBM.
  * **Native partial chunks** — S/T need not be chunk multiples: ceil
    grids + iota masks (exactly the GEMM kernels' partial-tile policy).
    Out-of-range KV columns are masked to NEG_INF *and* the undefined
    fill in the partial V block is zeroed (0 · NaN would otherwise poison
    the PV product); out-of-range q rows only ever produce row-local
    garbage that Pallas drops at the out-of-range output store.

Grid (n, i, jj): n = B·H flat head index, jj the *schedule-relative* KV
step, innermost; VMEM scratch carries (acc f32 (qc, D), m (qc, 1),
l (qc, 1)) across jj.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tiling import ceil_div, round_up

NEG_INF = -2.3819763e38

__all__ = ["FlashSchedule", "flash_schedule", "flash_attention_kernel",
           "NEG_INF"]


@dataclasses.dataclass(frozen=True)
class FlashSchedule:
    """Static block schedule for one (S, T, chunk, mask-structure) problem.

    ``max_kv_steps`` is the launched KV-grid extent per q block;
    ``blocks_touched`` counts KV blocks actually streamed from HBM across
    all q blocks (the block-sparse sweep skips fully-masked blocks) versus
    the ``blocks_dense = num_q_blocks * num_kv_blocks`` rectangular sweep.
    """

    s_len: int
    t_len: int
    q_chunk: int
    kv_chunk: int
    causal: bool
    window: int | None
    num_q_blocks: int
    num_kv_blocks: int
    max_kv_steps: int
    blocks_touched: int
    blocks_dense: int


def _kv_block_bounds(i, *, q_chunk, kv_chunk, num_kv, causal, window,
                     _min=jnp.minimum, _max=jnp.maximum):
    """Inclusive [j_lo, j_hi] KV-block range visible to q block ``i``.

    Pure int arithmetic (non-negative before the floor division).  Used on
    traced int32 (index maps / kernel body) and — with Python ``min``/
    ``max`` passed in — on Python ints (schedule planning, which must stay
    concrete even when the caller is itself being traced).
    """
    j_lo = 0
    if window is not None:
        # lowest k visible to the block's first row i*qc: k > i*qc - window
        first_k = _max(i * q_chunk - (window - 1), 0)
        j_lo = _min(first_k // kv_chunk, num_kv - 1)
    j_hi = num_kv - 1
    if causal:
        # highest k visible to the block's last row: k <= (i+1)*qc - 1
        j_hi = _min(((i + 1) * q_chunk - 1) // kv_chunk, num_kv - 1)
    return j_lo, j_hi


def flash_schedule(s_len: int, t_len: int, *, q_chunk: int, kv_chunk: int,
                   causal: bool = True,
                   window: int | None = None) -> FlashSchedule:
    """Plan the block-sparse KV sweep for an (S, T) attention problem.

    All-static: chunk sizes are clamped to the (8-aligned) sequence
    lengths, grids are ceil-divided (native partial chunks), and the
    returned ``max_kv_steps`` is the KV grid extent
    ``flash_attention_kernel`` launches — ``blocks_touched`` vs
    ``blocks_dense`` is therefore an exact streamed-HBM counter, used by
    ``benchmarks/flash_attention.py`` and the schedule tests.  Decode
    over a paged cache plans with ``decode.flash_decode_schedule``
    instead (dynamic per-sequence lengths, static page budget).
    """
    q_chunk = min(q_chunk, round_up(s_len, 8))
    kv_chunk = min(kv_chunk, round_up(t_len, 8))
    num_q = ceil_div(s_len, q_chunk)
    num_kv = ceil_div(t_len, kv_chunk)
    max_steps, touched = 0, 0
    for i in range(num_q):
        j_lo, j_hi = _kv_block_bounds(i, q_chunk=q_chunk, kv_chunk=kv_chunk,
                                      num_kv=num_kv, causal=causal,
                                      window=window, _min=min, _max=max)
        steps = j_hi - j_lo + 1
        max_steps = max(max_steps, steps)
        touched += steps
    return FlashSchedule(
        s_len=s_len, t_len=t_len, q_chunk=q_chunk, kv_chunk=kv_chunk,
        causal=causal, window=window, num_q_blocks=num_q,
        num_kv_blocks=num_kv, max_kv_steps=max_steps,
        blocks_touched=touched, blocks_dense=num_q * num_kv)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale, causal, window, softcap, sched: FlashSchedule,
                  out_dtype):
    i = pl.program_id(1)
    jj = pl.program_id(2)
    qc, kc = sched.q_chunk, sched.kv_chunk
    j_lo, j_hi = _kv_block_bounds(i, q_chunk=qc, kv_chunk=kc,
                                  num_kv=sched.num_kv_blocks,
                                  causal=causal, window=window)
    j = jnp.minimum(j_lo + jj, j_hi)        # must match the KV index map
    partial_t = sched.t_len % kc != 0
    masked = causal or window is not None or partial_t

    @pl.when(jj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j_lo + jj <= j_hi)
    def _compute():
        q = q_ref[0, 0]                                # (qc, D)
        k = k_ref[0, 0]                                # (kc, D)
        v = v_ref[0, 0]                                # (kc, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)

        allowed = None
        if masked:
            q_pos = i * qc + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = j * kc + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            allowed = jnp.full(s.shape, True)
            if causal:
                allowed &= q_pos >= k_pos
            if window is not None:
                allowed &= k_pos > q_pos - window
            if partial_t:
                allowed &= k_pos < sched.t_len
            s = jnp.where(allowed, s, NEG_INF)
        if partial_t:
            # zero the undefined fill of the edge V block: the masked p is
            # exactly 0 there, but 0 · NaN would still poison the PV dot
            row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(j * kc + row < sched.t_len, v, 0)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if allowed is not None:
            # a row with no visible KV in its first streamed block has
            # m_new == NEG_INF, so exp(s - m_new) == exp(0) — re-mask it
            p = jnp.where(allowed, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(jj == pl.num_programs(2) - 1)
    def _epilogue():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-37)).astype(out_dtype)


def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           scale: float, causal: bool = True,
                           window: int | None = None,
                           softcap: float | None = None,
                           q_chunk: int = 256, kv_chunk: int = 256,
                           out_dtype=None, interpret: bool = False):
    """q (B, H, S, D); k, v (B, KH, T, D) with H a multiple of KH.

    GQA KV heads are broadcast across the H // KH query groups by the KV
    BlockSpec index map (never materialized); S and T may be arbitrary
    (native partial chunks); ``window`` enables in-kernel sliding-window
    masking with a block-sparse KV sweep.
    """
    b, h, s_len, d = q.shape
    kh, t_len = k.shape[1], k.shape[2]
    assert h % kh == 0, (h, kh)
    assert k.shape == v.shape == (b, kh, t_len, d), (q.shape, k.shape,
                                                     v.shape)
    g = h // kh
    out_dtype = out_dtype or q.dtype
    sched = flash_schedule(s_len, t_len, q_chunk=q_chunk, kv_chunk=kv_chunk,
                           causal=causal, window=window)
    qc, kc = sched.q_chunk, sched.kv_chunk
    bounds = functools.partial(_kv_block_bounds, q_chunk=qc, kv_chunk=kc,
                               num_kv=sched.num_kv_blocks, causal=causal,
                               window=window)

    def q_index(n, i, jj):
        return (n // h, n % h, i, 0)

    def kv_index(n, i, jj):
        j_lo, j_hi = bounds(i)
        # clamped sparse walk: trailing steps revisit j_hi (copy elided)
        return (n // h, (n % h) // g, jnp.minimum(j_lo + jj, j_hi), 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, sched=sched, out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=(b * h, sched.num_q_blocks, sched.max_kv_steps),
        in_specs=[
            pl.BlockSpec((1, 1, qc, d), q_index),
            pl.BlockSpec((1, 1, kc, d), kv_index),
            pl.BlockSpec((1, 1, kc, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, qc, d), q_index),
        out_shape=jax.ShapeDtypeStruct((b, h, s_len, d), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((qc, d), jnp.float32),
            pltpu.VMEM((qc, 1), jnp.float32),
            pltpu.VMEM((qc, 1), jnp.float32),
        ],
        interpret=interpret, name="flash_prefill",
    )(q, k, v)
