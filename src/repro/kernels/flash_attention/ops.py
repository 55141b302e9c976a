"""Jit'd wrapper for the flash-attention kernel with GQA + dispatch.

GQA is *native*: k/v keep their true KV head count end to end — the
wrapper only transposes (B, T, KH, D) → the kernel's (B, KH, T, D)
layout, and the kernel's BlockSpec index maps broadcast each KV head
across its query group, so the KV tensor is never repeated
group-count× in HBM.
"""
from __future__ import annotations

import jax

from repro.kernels.flash_attention import ref as _ref
from repro.kernels.flash_attention.decode import paged_decode_kernel
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.tiled_matmul.ops import kernel_mode

__all__ = ["flash_attention", "paged_decode_attention"]


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    scale: float | None = None, causal: bool = True,
                    window: int | None = None,
                    softcap: float | None = None,
                    q_chunk: int = 256, kv_chunk: int = 256,
                    mode: str | None = None) -> jax.Array:
    """Multi-head attention, (B, S, H, D) q with (B, T, KH, D) kv (GQA).

    Returns (B, S, H, D) in q's dtype (f32 softmax inside).  KV heads are
    broadcast across query groups inside the kernel (index-map broadcast,
    no HBM repeat).  ``window`` applies a sliding-window mask
    (k > q - window) with a block-sparse KV sweep; S/T may be arbitrary
    (native partial chunks).  Lowers to the ``flash_schedule``-planned
    Pallas kernel under ``pallas``/``pallas_interpret`` and to the dense
    oracle ``ref.attention_ref`` under ``ref`` (mode defaults to
    ``kernel_mode()``); decode steps over a paged cache use
    ``paged_decode_attention`` instead.
    """
    mode = mode or kernel_mode()
    b, s, h, d = q.shape
    kh = k.shape[2]
    assert h % kh == 0, (h, kh)
    scale = scale if scale is not None else d ** -0.5

    qh = q.transpose(0, 2, 1, 3)            # (b, h, s, d)
    kh_ = k.transpose(0, 2, 1, 3)           # (b, kh, t, d)
    vh_ = v.transpose(0, 2, 1, 3)

    if mode == "ref":
        o = _ref.attention_ref(qh, kh_, vh_, scale=scale, causal=causal,
                               window=window, softcap=softcap)
    else:
        o = flash_attention_kernel(
            qh, kh_, vh_, scale=scale, causal=causal, window=window,
            softcap=softcap, q_chunk=q_chunk, kv_chunk=kv_chunk,
            interpret=(mode == "pallas_interpret"))
    return o.transpose(0, 2, 1, 3)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array,
                           lengths: jax.Array, *,
                           scale: float | None = None,
                           window: int | None = None,
                           softcap: float | None = None,
                           q_chunk: int | None = None,
                           k_scales: jax.Array | None = None,
                           v_scales: jax.Array | None = None,
                           new_lens: jax.Array | None = None,
                           mode: str | None = None) -> jax.Array:
    """Attention over a paged KV cache (always causal).

    q (B, q_len, H, D) — the step's new queries (q_len = 1 for plain
    decode, a whole prompt chunk for chunked paged prefill);
    k_pages/v_pages (P, KH, page, D) one layer's head-major page pool;
    page_table (B, max_pages) int32; lengths (B,) int32 per-sequence context
    *including* the new tokens (their K/V already committed).  Returns
    (B, q_len, H, D).  ``q_chunk`` bounds the q rows resident per kernel
    block (multi-query-row steps; ignored by the dense oracle).

    ``k_scales``/``v_scales`` (P, KH, page) f32 select the quantized
    ``kv_quant="int8"`` layout: int8 pools with per-row absmax scales,
    dequantized in-kernel (or inside the gather for the ref oracle) with
    the bitwise-identical ``values.astype(f32) * scale``.

    ``new_lens`` (B,) int32 selects the n-token verify mode
    (speculative decode — ``docs/DESIGN.md`` §8): per-sequence live
    new-token counts; rows at or past them are fully masked and
    ``lengths`` counts committed + live tokens only.  ``None`` is the
    bitwise-identical plain launch.

    Lowers to the paged flash kernel (``decode.py``) under
    ``pallas``/``pallas_interpret`` — a length-aware page walk that
    streams each sequence's occupied pages, every KV head at once, in
    blocks of pages — and to
    the dense gather oracle ``ref.paged_attention_ref`` under ``ref``.
    """
    mode = mode or kernel_mode()
    b, qs, h, d = q.shape
    kh = k_pages.shape[1]
    assert h % kh == 0, (h, kh)
    scale = scale if scale is not None else d ** -0.5

    qh = q.transpose(0, 2, 1, 3)            # (B, H, qs, D)
    if mode == "ref":
        o = _ref.paged_attention_ref(qh, k_pages, v_pages, page_table,
                                     lengths, scale=scale, window=window,
                                     softcap=softcap, k_scales=k_scales,
                                     v_scales=v_scales, new_lens=new_lens)
    else:
        o = paged_decode_kernel(qh, k_pages, v_pages, page_table, lengths,
                                scale=scale, window=window, softcap=softcap,
                                q_chunk=q_chunk, k_scales=k_scales,
                                v_scales=v_scales, new_lens=new_lens,
                                interpret=(mode == "pallas_interpret"))
    return o.transpose(0, 2, 1, 3)
