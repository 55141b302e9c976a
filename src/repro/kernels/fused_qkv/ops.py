"""Jit'd wrapper for the fused QKV projection (update_A analogue).

Plan selection routes through the schedule-aware GEMM dispatcher
(``core.dispatch.select_fused_plan``) keyed on the full fused shape
(M, K, Nq, Nkv) — the (Nq, Nkv) output split is part of the tune key because
GQA changes the K/V sweep and with it the winning schedule.  The dispatcher
returns blocks *and* a ``Schedule``: ``panel`` keeps the activation panel
resident across the whole contraction (the paper's ``update_A``), ``k_split``
streams K slabs through carried accumulators.  Both schedules share one
kernel launch path and are bitwise identical to the reference; partial tiles
are handled natively (no host-side ``jnp.pad``), the same policy as
``tiled_matmul``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.dispatch import Schedule, select_fused_plan
from repro.core.quantization import QTensor
from repro.kernels.fused_qkv import ref as _ref
from repro.kernels.fused_qkv.kernel import fused_qkv_kernel
from repro.kernels.tiled_matmul.ops import kernel_mode, whole_on_each_device

__all__ = ["fused_qkv"]


def fused_qkv(a: QTensor, wq: QTensor, wk: QTensor, wv: QTensor, *,
              block_m: int | None = None, block_n: int | None = None,
              block_k: int | None = None,
              out_dtype=jnp.bfloat16, mode: str | None = None):
    """(q, k, v) = dequant(A_q @ [Wq|Wk|Wv]) with A loaded once.

    a: (M, K) QTensor, per-row scale.  w*: (K, N*) QTensors, per-col scales.
    ``block_k``: None lets the dispatcher pick the schedule; an explicit
    value < K forces the K-split schedule (tests/benchmarks).
    """
    mode = mode or kernel_mode()
    m, k = a.values.shape
    nq, nkv = wq.values.shape[1], wk.values.shape[1]
    a_scale = jnp.broadcast_to(a.scale.astype(jnp.float32), (m, 1))
    sq = jnp.broadcast_to(wq.scale.astype(jnp.float32), (1, nq))
    sk = jnp.broadcast_to(wk.scale.astype(jnp.float32), (1, nkv))
    sv = jnp.broadcast_to(wv.scale.astype(jnp.float32), (1, nkv))
    if mode == "ref":
        return _ref.fused_qkv_ref(a.values, a_scale, wq.values, sq,
                                  wk.values, sk, wv.values, sv,
                                  out_dtype=out_dtype)

    interpret = mode == "pallas_interpret"
    if block_m is None or block_n is None:
        plan = select_fused_plan(m, k, nq, nkv, out_dtype=out_dtype,
                                 interpret=interpret)
        block_m = block_m or plan.block_m
        block_n = block_n or plan.block_n
        if block_k is None and plan.schedule is Schedule.K_SPLIT:
            block_k = plan.block_k
    kernel = functools.partial(fused_qkv_kernel, block_m=block_m,
                               block_n=block_n, block_k=block_k,
                               out_dtype=out_dtype, interpret=interpret)
    return whole_on_each_device(kernel, a.values, a_scale, wq.values, sq,
                                wk.values, sk, wv.values, sv)
