"""Pure-jnp oracles for the flash-attention kernels: dense softmax
attention (prefill) and its paged-decode counterpart.

GQA-native like the kernels: q (B, H, S, D) against k/v (B, KH, T, D)
with KV broadcast across the H // KH query groups by reshape — no
materialized ``jnp.repeat``.  Supports the kernels' full mask structure
(causal, sliding window) so every schedule has a dense oracle;
``paged_attention_ref`` gathers the page pool back into a dense cache and
applies the decode masks, making it the reference for the paged
flash-decode kernel (``decode.py``).
"""
from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -2.3819763e38


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  scale: float, causal: bool = True,
                  window: int | None = None,
                  softcap: float | None = None) -> jnp.ndarray:
    """q (B, H, S, D); k, v (B, KH, T, D) → (B, H, S, D).  f32 softmax."""
    b, h, s_len, d = q.shape
    kh, t_len = k.shape[1], k.shape[2]
    g = h // kh
    qg = q.reshape(b, kh, g, s_len, d)
    s = jnp.einsum("bkgsd,bktd->bkgst", qg, k,
                   preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    if causal or window is not None:
        sq = jnp.arange(s_len)[:, None]
        tk = jnp.arange(t_len)[None, :]
        mask = jnp.full((s_len, t_len), True)
        if causal:
            mask &= tk <= sq
        if window is not None:
            mask &= tk > sq - window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    # normalize like the kernel (0 output for all-masked rows, not uniform)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    if causal or window is not None:
        p = jnp.where(mask[None, None, None], p, 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-37)
    o = jnp.einsum("bkgst,bktd->bkgsd", (p / l).astype(v.dtype), v)
    return o.reshape(b, h, s_len, d)


def paged_gather(pages: jnp.ndarray, page_table: jnp.ndarray) -> jnp.ndarray:
    """Materialize a paged pool back into a dense per-sequence cache.

    pages (P, KH, page, D) head-major; page_table (B, max_pages) int32 →
    (B, max_pages·page, KH, D) — logical token order per sequence.
    """
    b, max_pages = page_table.shape
    _, kh, page, d = pages.shape
    return pages[page_table].swapaxes(2, 3).reshape(b, max_pages * page,
                                                    kh, d)


def paged_gather_scales(scales: jnp.ndarray,
                        page_table: jnp.ndarray) -> jnp.ndarray:
    """Gather the per-(kv-head, page-slot) scale rows of a quantized pool.

    scales (P, KH, page) f32; page_table (B, max_pages) int32 →
    (B, max_pages·page, KH) — token order matching ``paged_gather``.
    """
    b, max_pages = page_table.shape
    _, kh, page = scales.shape
    return scales[page_table].swapaxes(2, 3).reshape(b, max_pages * page,
                                                     kh)


def dequantize_gathered(values: jnp.ndarray,
                        scales: jnp.ndarray) -> jnp.ndarray:
    """(B, T, KH, D) int8 values × (B, T, KH) scales → f32, the exact
    dequant the paged decode kernel fuses in-kernel (values·scale, f32)."""
    return values.astype(jnp.float32) * scales[..., None]


def paged_attention_ref(q: jnp.ndarray, k_pages: jnp.ndarray,
                        v_pages: jnp.ndarray, page_table: jnp.ndarray,
                        lengths: jnp.ndarray, *, scale: float,
                        window: int | None = None,
                        softcap: float | None = None,
                        k_scales: jnp.ndarray | None = None,
                        v_scales: jnp.ndarray | None = None,
                        new_lens: jnp.ndarray | None = None) -> jnp.ndarray:
    """Dense decode / chunked-prefill oracle over a paged cache.

    q (B, H, q_len, D); pools (P, KH, page, D); lengths (B,) int32 is the
    per-sequence context *including* the q_len new tokens → (B, H, q_len,
    D).  Row r of sequence b sits at position ``lengths[b] - q_len + r``;
    causality, the sliding window, and the uncommitted cache tail are all
    enforced against that position (f32 softmax, kernel-matching 0-output
    normalization for fully-masked rows).  q_len may be a whole prompt
    chunk — this is the oracle for every q-block schedule the paged
    kernel launches (``q_chunk`` only changes the kernel's blocking,
    never the math).

    ``k_scales``/``v_scales`` (P, KH, page) f32 make this the quantized
    oracle: the int8 pools are gathered and dequantized row-wise
    (``values.astype(f32) * scale``) — the bitwise-specified dequant the
    kernel fuses into its page walk.

    ``new_lens`` (B,) int32 is the verify-mode oracle (speculative
    decode): row ``r`` of sequence ``b`` is live iff ``r <
    new_lens[b]`` at position ``lengths[b] - new_lens[b] + r``; dead
    rows are fully masked (0 output, matching the kernel).
    """
    b, h, qs, d = q.shape
    kh = k_pages.shape[1]
    g = h // kh
    k = paged_gather(k_pages, page_table)           # (B, T, KH, D)
    v = paged_gather(v_pages, page_table)
    if k_scales is not None:
        k = dequantize_gathered(k, paged_gather_scales(k_scales, page_table))
    if v_scales is not None:
        v = dequantize_gathered(v, paged_gather_scales(v_scales, page_table))
    t_len = k.shape[1]
    qg = q.reshape(b, kh, g, qs, d)
    s = jnp.einsum("bkgsd,btkd->bkgst", qg, k,
                   preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    nn = jnp.full_like(lengths, qs) if new_lens is None else new_lens
    q_pos = (lengths[:, None] - nn[:, None]
             + jnp.arange(qs)[None, :])             # (B, qs)
    k_pos = jnp.arange(t_len)
    mask = k_pos[None, None, :] <= q_pos[:, :, None]        # (B, qs, T)
    if new_lens is not None:
        # verify mode: rows past the live new-token count belong to no
        # token — mask them outright (0-output convention)
        mask &= (jnp.arange(qs)[None, :, None] < nn[:, None, None])
    if window is not None:
        mask &= k_pos[None, None, :] > q_pos[:, :, None] - window
    mask = mask[:, None, None]                      # (B, 1, 1, qs, T)
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-37)
    o = jnp.einsum("bkgst,btkd->bkgsd", (p / l).astype(v.dtype), v)
    return o.reshape(b, h, qs, d)
