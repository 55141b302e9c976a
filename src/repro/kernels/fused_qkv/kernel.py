"""Pallas TPU kernel: fused Q/K/V projection — panel-resident and K-split.

This is the direct TPU analogue of the paper's ``update_A`` control flag
(§4.2): "the host can choose to reuse the last loaded A matrix for subsequent
calls — useful when processing multiple B batches with the same weights".
The paper amortizes the DDR→BRAM load of A across the three Q/K/V weight
matrices; here one ``pallas_call`` holds an activation panel in VMEM while
streaming Wq, Wk, Wv column blocks past it and writing three outputs.  A is
fetched from HBM once per row panel instead of three times.

Two contraction schedules share the launch path (``Schedule`` in
``core.dispatch`` picks between them):

  * ``panel`` (``block_k is None`` / ``block_k >= K``) — the paper's
    schedule: grid (⌈M/bm⌉, ⌈Nq/bn⌉), the A panel (bm, K) spans the full
    contraction and its BlockSpec index_map is invariant in the N-sweep grid
    axis, so Pallas elides re-copies across the Wq/Wk/Wv block sweep.
  * ``k_split`` (``block_k < K``) — for K too large to hold a full panel
    (paper §8 "double-buffered streaming"): grid (⌈M/bm⌉, ⌈Nq/bn⌉, ⌈K/bk⌉)
    with three int32 VMEM accumulators (one per output) initialised at k==0
    and flushed through the shared dequant epilogue at the final K step.

GQA support: Nk = Nv may be smaller than Nq (fewer KV heads).  The grid is
sized for Q's column blocks; K/V compute+stores are guarded with ``pl.when``
and their index maps clamped, so trailing grid steps only compute Q.

Partial tiles (paper §5): shapes need NOT be block multiples.  Grids are
ceil-divided; edge-block garbage (Pallas's undefined out-of-range fill) only
ever lands in out-of-range M-rows / N-cols whose stores Pallas drops.  The
one place undefined fill would corrupt valid results is the contraction dim
in the K-split schedule — an out-of-range K slab accumulates into valid
(i, j) outputs — so that schedule zeroes A's out-of-range K columns with a
broadcasted-iota mask (int8 zero annihilates whatever the weight slab holds
there, keeping the int32 accumulation bit-exact vs the reference, the same
native-partial-tile discipline as ``tiled_matmul``).
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


def _dequant(acc, sa, sb, out_dtype):
    return (acc.astype(jnp.float32)
            * (sa.astype(jnp.float32) * sb.astype(jnp.float32))
            ).astype(out_dtype)


def _fused_qkv_kernel(a_ref, wq_ref, wk_ref, wv_ref,
                      sa_ref, sq_ref, sk_ref, sv_ref,
                      q_ref, k_ref, v_ref, *, nkv_blocks, out_dtype):
    a = a_ref[...]            # (bm, K) int8 — persistent across the j sweep
    sa = sa_ref[...]
    q_ref[...] = _dequant(_INT8_DOT(a, wq_ref[...]), sa, sq_ref[...],
                          out_dtype)

    @pl.when(pl.program_id(1) < nkv_blocks)
    def _kv():
        k_ref[...] = _dequant(_INT8_DOT(a, wk_ref[...]), sa, sk_ref[...],
                              out_dtype)
        v_ref[...] = _dequant(_INT8_DOT(a, wv_ref[...]), sa, sv_ref[...],
                              out_dtype)


def _fused_qkv_kernel_ksplit(a_ref, wq_ref, wk_ref, wv_ref,
                             sa_ref, sq_ref, sk_ref, sv_ref,
                             q_ref, k_ref, v_ref,
                             accq_ref, acck_ref, accv_ref, *,
                             nkv_blocks, out_dtype, k_dim, block_k):
    """K-split schedule: three int32 accumulators carried across grid steps.

    ``k_dim`` is the *logical* K; when it is not a block_k multiple the final
    K step masks A's out-of-range columns to zero (iota mask) so the
    undefined fill Pallas reads past the array edge cannot pollute the
    accumulators for valid output positions.
    """
    kk = pl.program_id(2)
    is_kv = pl.program_id(1) < nkv_blocks

    @pl.when(kk == 0)
    def _init():
        accq_ref[...] = jnp.zeros_like(accq_ref)
        acck_ref[...] = jnp.zeros_like(acck_ref)
        accv_ref[...] = jnp.zeros_like(accv_ref)

    a = a_ref[...]
    if k_dim % block_k:
        valid_k = k_dim - kk * block_k         # > block_k off the K edge
        col = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
        a = jnp.where(col < valid_k, a, 0)
    accq_ref[...] += _INT8_DOT(a, wq_ref[...])

    @pl.when(is_kv)
    def _kv():
        acck_ref[...] += _INT8_DOT(a, wk_ref[...])
        accv_ref[...] += _INT8_DOT(a, wv_ref[...])

    last = kk == pl.num_programs(2) - 1

    @pl.when(last)
    def _flush_q():
        q_ref[...] = _dequant(accq_ref[...], sa_ref[...], sq_ref[...],
                              out_dtype)

    @pl.when(jnp.logical_and(last, is_kv))
    def _flush_kv():
        k_ref[...] = _dequant(acck_ref[...], sa_ref[...], sk_ref[...],
                              out_dtype)
        v_ref[...] = _dequant(accv_ref[...], sa_ref[...], sv_ref[...],
                              out_dtype)


def fused_qkv_kernel(a_values, a_scale, wq, sq, wk, sk, wv, sv, *,
                     block_m: int = 256, block_n: int = 256,
                     block_k: int | None = None,
                     out_dtype=jnp.bfloat16, interpret: bool = False):
    """One launch path for both schedules.  Shapes may be arbitrary — edge
    blocks are handled natively.

    a_values (M, K) int8; a_scale (M, 1) f32
    wq (K, Nq), wk/wv (K, Nkv) int8; sq (1, Nq), sk/sv (1, Nkv) f32
    block_k None (or >= K) selects the panel-resident schedule; block_k < K
    selects the K-split schedule.
    Returns (q (M, Nq), k (M, Nkv), v (M, Nkv)) in out_dtype.
    """
    m, k = a_values.shape
    nq = wq.shape[1]
    nkv = wk.shape[1]
    assert wv.shape[1] == nkv
    nq_blocks = ceil_div(nq, block_n)
    nkv_blocks = ceil_div(nkv, block_n)
    assert nkv_blocks <= nq_blocks, "Q must have >= as many column blocks"

    clamp = nkv_blocks - 1
    ksplit = block_k is not None and block_k < k

    if not ksplit:
        def kv_map(i, j):
            return (0, jnp.minimum(j, clamp))

        def kv_out_map(i, j):
            return (i, jnp.minimum(j, clamp))

        grid = (ceil_div(m, block_m), nq_blocks)
        kernel = functools.partial(_fused_qkv_kernel, nkv_blocks=nkv_blocks,
                                   out_dtype=out_dtype)
        in_specs = [
            pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),  # A persistent
            pl.BlockSpec((k, block_n), lambda i, j: (0, j)),  # Wq streamed
            pl.BlockSpec((k, block_n), kv_map),               # Wk streamed
            pl.BlockSpec((k, block_n), kv_map),               # Wv streamed
            pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), kv_map),
            pl.BlockSpec((1, block_n), kv_map),
        ]
        out_specs = (
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((block_m, block_n), kv_out_map),
            pl.BlockSpec((block_m, block_n), kv_out_map),
        )
        scratch_shapes = ()
    else:
        def kv_w_map(i, j, kk):
            return (kk, jnp.minimum(j, clamp))

        def kv_s_map(i, j, kk):
            return (0, jnp.minimum(j, clamp))

        def kv_out_map(i, j, kk):
            return (i, jnp.minimum(j, clamp))

        # kk is the innermost grid axis: each (i, j) output block sees its
        # full K sweep back-to-back, so the accumulators carry correctly.
        grid = (ceil_div(m, block_m), nq_blocks, ceil_div(k, block_k))
        kernel = functools.partial(_fused_qkv_kernel_ksplit,
                                   nkv_blocks=nkv_blocks, out_dtype=out_dtype,
                                   k_dim=k, block_k=block_k)
        in_specs = [
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_k, block_n), kv_w_map),
            pl.BlockSpec((block_k, block_n), kv_w_map),
            pl.BlockSpec((block_m, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, block_n), kv_s_map),
            pl.BlockSpec((1, block_n), kv_s_map),
        ]
        out_specs = (
            pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
            pl.BlockSpec((block_m, block_n), kv_out_map),
            pl.BlockSpec((block_m, block_n), kv_out_map),
        )
        scratch_shapes = tuple(
            pltpu.VMEM((block_m, block_n), jnp.int32) for _ in range(3))

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=(
            jax.ShapeDtypeStruct((m, nq), out_dtype),
            jax.ShapeDtypeStruct((m, nkv), out_dtype),
            jax.ShapeDtypeStruct((m, nkv), out_dtype),
        ),
        scratch_shapes=scratch_shapes,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(a_values, wq, wk, wv, a_scale, sq, sk, sv)
