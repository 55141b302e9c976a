"""Pallas TPU kernel: fused per-row activation quantization.

The paper's FPGAQuantizedLinear quantizes input activations on the host CPU
before DMA-ing them to the fabric (§6.2).  On TPU that host round-trip is the
analogue of an HBM round-trip in fp32; this kernel fuses
absmax → scale → round → clip → int8 in one VMEM pass so the fp32 activation
is read once and only int8 (+ one f32 scale per row) is written back —
quartering the bytes moved for the GEMM input (the paper's bandwidth story,
applied to the quantization step itself).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tiling import VMEM_LIMIT_BYTES, VMEM_PLAN_BUDGET

def quant_act_block_m(k: int, itemsize: int) -> int:
    """Rows per block: the most of 256, 128, … 8 whose footprint fits the
    planner's VMEM budget (a whole row must be resident for its absmax,
    so wide rows — a 11008-wide FFN activation — take fewer of them)."""
    # per element: the input and the int8 output double-buffered, plus two
    # f32 temporaries (the upcast row and x / scale)
    row_bytes = k * (2 * itemsize + 2 * 1 + 2 * 4)
    for bm in (256, 128, 64, 32, 16, 8):
        if bm * row_bytes <= VMEM_PLAN_BUDGET:
            return bm
    return 8


def _quant_act_kernel(x_ref, q_ref, s_ref, *, qmax):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(absmax <= 1e-12, 1.0, absmax / qmax)
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def quant_act_kernel(x: jax.Array, *, block_m: int = 256, qmax: int = 127,
                     interpret: bool = False):
    """x: (M, K) float, M % block_m == 0 → (int8 (M,K), f32 (M,1)).

    Rows are independent, so the grid splits M only; each invocation sees the
    full row (K) — the reduction axis must be in-block for a one-pass absmax.
    """
    m, k = x.shape
    assert m % block_m == 0, (m, block_m)
    return pl.pallas_call(
        functools.partial(_quant_act_kernel, qmax=qmax),
        grid=(m // block_m,),
        in_specs=[pl.BlockSpec((block_m, k), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((block_m, k), lambda i: (i, 0)),
                   pl.BlockSpec((block_m, 1), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((m, k), jnp.int8),
                   jax.ShapeDtypeStruct((m, 1), jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret, name="quant_act",
    )(x)
