"""Pallas TPU kernel: two-level tiled int8 GEMM with fused dequant epilogue.

This is the TPU-native adaptation of the paper's accelerator (DESIGN.md §2):

  FPGA (paper)                         TPU (this kernel)
  ----------------------------------   ------------------------------------
  A persistent in BRAM                 A row-panel BlockSpec index_map is
                                       independent of the N grid index, so
                                       Pallas elides the HBM→VMEM copy while
                                       the kernel sweeps B column blocks —
                                       A stays resident, exactly `update_A`.
  B streamed in BLOCK_M=256 col blocks outer grid dimension `j` over N/bn
  32×32 unrolled MAC array, II=1       the 128×128 MXU, fed by
                                       dot_general(int8, int8 → int32)
  dequant epilogue in PL               fused f32 scale(+bias) epilogue on the
                                       final K step, written once per block
  partial tiles via boundary checks    native edge blocks (paper §5): ceil
                                       grids + in-kernel iota masking on the
                                       contraction dim — no host-side pad

Two grid schedules are provided:

  * ``k_steps == 1`` — "panel-resident" schedule (the paper's): grid
    (⌈M/bm⌉, ⌈N/bn⌉), the whole K reduction happens in one kernel invocation
    with the A panel (bm, K) held in VMEM across the full sweep of B blocks.
  * ``k_steps > 1`` — K-split schedule for large K: grid (⌈M/bm⌉, ⌈N/bn⌉,
    ⌈K/bk⌉) with an int32 VMEM accumulator initialised at k==0 and flushed
    through the dequant epilogue at k==k_steps-1 (paper §8 "double-buffered
    streaming").

Partial-tile semantics (paper §5 "Handling partial tiles"): shapes need NOT
be block multiples.  Pallas materialises out-of-range input blocks with
undefined fill (NaN / int-min in interpret mode) and *drops* out-of-range
output stores, so garbage in edge M-rows / N-cols never reaches the logical
output.  The one place undefined fill would corrupt valid results is the
contraction dim in the K-split schedule — an out-of-range K slab accumulates
into valid (i, j) outputs — so the kernel zeroes A's out-of-range K columns
with a broadcasted-iota mask (int8 zero annihilates whatever B holds there,
keeping the int32 accumulation bit-exact vs the reference).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tiling import VMEM_LIMIT_BYTES, ceil_div

# the scoped-VMEM limit the dispatcher's plans are budgeted against
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)

_INT8_DOT = functools.partial(
    jax.lax.dot_general,
    dimension_numbers=(((1,), (0,)), ((), ())),
    preferred_element_type=jnp.int32)


def _epilogue(acc, sa, sb, bias, out_dtype):
    """Dequantize int32 accumulator → out_dtype.  Must match ref.py exactly."""
    out = acc.astype(jnp.float32) * (sa.astype(jnp.float32)
                                     * sb.astype(jnp.float32))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(out_dtype)


def _matmul_kernel_panel(a_ref, b_ref, sa_ref, sb_ref, *rest, out_dtype):
    """Panel-resident schedule: one invocation covers the full K reduction.

    The A block spans the entire (unpadded) K, so no contraction masking is
    needed; M/N edge garbage lands only in dropped out-of-range stores.
    """
    if len(rest) == 2:
        bias_ref, o_ref = rest
        bias = bias_ref[...]
    else:
        (o_ref,) = rest
        bias = None
    acc = _INT8_DOT(a_ref[...], b_ref[...])
    o_ref[...] = _epilogue(acc, sa_ref[...], sb_ref[...], bias, out_dtype)


def _matmul_kernel_ksplit(a_ref, b_ref, sa_ref, sb_ref, *rest,
                          out_dtype, k_dim, block_k):
    """K-split schedule with an int32 VMEM accumulator.

    ``k_dim`` is the *logical* K; when it is not a block_k multiple the final
    K step masks A's out-of-range columns to zero (iota mask) so the
    undefined fill Pallas reads past the array edge cannot pollute the
    accumulator for valid output positions.
    """
    if len(rest) == 3:
        bias_ref, o_ref, acc_ref = rest
        bias = bias_ref[...]
    else:
        o_ref, acc_ref = rest
        bias = None

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    if k_dim % block_k:
        valid_k = k_dim - pl.program_id(2) * block_k   # > block_k off-edge
        col = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
        a = jnp.where(col < valid_k, a, 0)
    acc_ref[...] += _INT8_DOT(a, b_ref[...])

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = _epilogue(acc_ref[...], sa_ref[...], sb_ref[...], bias,
                               out_dtype)


def tiled_matmul_kernel(a_values: jax.Array, a_scale: jax.Array,
                        b_values: jax.Array, b_scale: jax.Array,
                        bias: jax.Array | None = None, *,
                        block_m: int = 256, block_n: int = 256,
                        block_k: int | None = None,
                        out_dtype=jnp.bfloat16,
                        interpret: bool = False) -> jax.Array:
    """Raw pallas_call wrapper.  Shapes may be arbitrary — edge blocks are
    handled natively (ceil grid + in-kernel contraction masking); the output
    is the exact logical (M, N).

    a_values (M, K) int8, a_scale (M, 1) f32
    b_values (K, N) int8, b_scale (1, N) f32
    bias     (1, N) f32 or None
    """
    m, k = a_values.shape
    k2, n = b_values.shape
    assert k == k2, (a_values.shape, b_values.shape)
    assert a_scale.shape == (m, 1) and b_scale.shape == (1, n)

    k_steps = 1 if block_k is None else ceil_div(k, block_k)
    has_bias = bias is not None
    out_shape = jax.ShapeDtypeStruct((m, n), out_dtype)

    if k_steps == 1:
        # Paper schedule: A panel persistent across the B-block sweep.
        grid = (ceil_div(m, block_m), ceil_div(n, block_n))
        in_specs = [
            pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),   # A: j-invariant
            pl.BlockSpec((k, block_n), lambda i, j: (0, j)),   # B: streamed
            pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),   # row scales
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),   # col scales
        ]
        operands = [a_values, b_values, a_scale, b_scale]
        if has_bias:
            in_specs.append(pl.BlockSpec((1, block_n), lambda i, j: (0, j)))
            operands.append(bias.reshape(1, n))
        kernel = functools.partial(_matmul_kernel_panel, out_dtype=out_dtype)
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
            out_shape=out_shape,
            compiler_params=_COMPILER_PARAMS,
            interpret=interpret, name="int8_gemm_panel",
        )(*operands)

    grid = (ceil_div(m, block_m), ceil_div(n, block_n), k_steps)
    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((block_m, 1), lambda i, j, kk: (i, 0)),
        pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
    ]
    operands = [a_values, b_values, a_scale, b_scale]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)))
        operands.append(bias.reshape(1, n))
    kernel = functools.partial(_matmul_kernel_ksplit, out_dtype=out_dtype,
                               k_dim=k, block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret, name="int8_gemm_ksplit",
    )(*operands)
