"""Public jit'd wrapper for the tiled int8 GEMM.

Handles: plan selection via the GEMM dispatcher (``core.dispatch`` — tuned
plans when the autotuner cache has one, analytic model otherwise), native
partial tiles (paper §5: edge blocks masked in-kernel, NO host-side
``jnp.pad`` of operands on the Pallas path), and backend dispatch:

  REPRO_KERNELS=ref                -> pure-jnp oracle (default off-TPU: the
                                      multi-pod dry-run compiles this path)
  REPRO_KERNELS=pallas_interpret   -> Pallas kernel, interpret mode (tests)
  REPRO_KERNELS=pallas             -> compiled Pallas kernel (default on TPU)

Both paths share the same dequant-epilogue math, so results are bitwise
identical; tests assert this across shape/dtype sweeps.

``partial="pad"`` retains the seed's zero-pad-to-block-multiples policy
(exact in int8) purely so ``benchmarks/partial_tile.py`` can measure what
the pad/slice copies cost versus the native path.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import os
import warnings

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.dispatch import select_plan
from repro.core.quantization import QTensor, quantize
from repro.core.tiling import MXU_DIM, round_up
from repro.kernels.tiled_matmul import ref as _ref
from repro.kernels.tiled_matmul.kernel import tiled_matmul_kernel

__all__ = ["tiled_matmul", "quantized_matmul", "kernel_mode",
           "KERNEL_MODES", "replicated_operands", "whole_on_each_device"]

KERNEL_MODES = ("ref", "pallas_interpret", "pallas")


def kernel_mode() -> str:
    """The kernel backend ops dispatch to when no ``mode`` is passed.

    ``REPRO_KERNELS`` names it explicitly (the tests pick ``ref`` or
    ``pallas_interpret``); unset, a TPU backend runs the compiled Pallas
    kernels and any other backend the pure-jnp oracles.  An unknown name
    raises, and an explicit non-``pallas`` mode on a TPU warns: it would
    keep the compiled kernels off the chip.
    """
    mode = os.environ.get("REPRO_KERNELS", "")
    on_tpu = jax.default_backend() == "tpu"
    if not mode:
        return "pallas" if on_tpu else "ref"
    if mode not in KERNEL_MODES:
        raise ValueError(f"REPRO_KERNELS must be one of {KERNEL_MODES}, "
                         f"got {mode!r}")
    if on_tpu and mode != "pallas":
        warnings.warn(f"REPRO_KERNELS={mode} on a TPU backend: the compiled "
                      "kernels will not run", stacklevel=2)
    return mode


# the mesh whose devices each hold every kernel operand whole (set only by
# a caller that knows it: mesh-sharded serving replicates the params)
_REPLICATED_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "repro_kernels_replicated_mesh", default=None)


@contextlib.contextmanager
def replicated_operands(mesh):
    """Declare that, inside, every Pallas kernel operand is replicated
    over ``mesh``: params held whole on each device and activations
    unsharded, as mesh-sharded serving holds them.  The kernel calls then
    run whole on each device (``whole_on_each_device``).  A mesh that
    shards params (training) must not be declared here."""
    token = _REPLICATED_MESH.set(mesh)
    try:
        yield
    finally:
        _REPLICATED_MESH.reset(token)


def whole_on_each_device(fn, *args):
    """``fn(*args)`` — a Pallas call — on each device of the mesh that
    ``replicated_operands`` declared.

    GSPMD cannot partition a Mosaic kernel, so there the call runs under
    ``shard_map`` with replicated operands and result: every device
    computes the whole product, with no collective.  Outside such a
    declaration it is a plain call.
    """
    mesh = _REPLICATED_MESH.get()
    if mesh is None or mesh.size == 1:
        return fn(*args)
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(*args)


def tiled_matmul(a: QTensor, b: QTensor, bias: jax.Array | None = None, *,
                 block_m: int | None = None, block_n: int | None = None,
                 block_k: int | None = None,
                 out_dtype=jnp.bfloat16,
                 mode: str | None = None,
                 partial: str = "native") -> jax.Array:
    """C = dequant(A_q @ B_q) + bias for quantized operands.

    ``a``: QTensor (M, K) with per-row (M,1) / per-tensor scale.
    ``b``: QTensor (K, N) with per-col (1,N) / per-tensor scale.
    ``partial``: "native" (edge blocks in-kernel) or "pad" (legacy zero-pad,
    kept for the partial-tile benchmark).
    """
    assert partial in ("native", "pad"), partial
    mode = mode or kernel_mode()
    m, k = a.values.shape
    _, n = b.values.shape
    a_scale = jnp.broadcast_to(a.scale.astype(jnp.float32), (m, 1))
    b_scale = jnp.broadcast_to(b.scale.astype(jnp.float32), (1, n))

    if mode == "ref":
        return _ref.tiled_matmul_ref(a.values, a_scale, b.values, b_scale,
                                     bias, out_dtype)

    interpret = mode == "pallas_interpret"
    if block_m is None or block_n is None:
        plan = select_plan(m, k, n, out_dtype=out_dtype, interpret=interpret)
        block_m = block_m or plan.block_m
        block_n = block_n or plan.block_n
        if block_k is None and plan.k_steps > 1:
            block_k = plan.block_k

    if partial == "pad":
        return _tiled_matmul_padded(
            a.values, a_scale, b.values, b_scale, bias, block_m=block_m,
            block_n=block_n, block_k=block_k, out_dtype=out_dtype,
            interpret=interpret)

    bi = bias.reshape(1, n).astype(jnp.float32) if bias is not None else None
    kernel = functools.partial(tiled_matmul_kernel, block_m=block_m,
                               block_n=block_n, block_k=block_k,
                               out_dtype=out_dtype, interpret=interpret)
    return whole_on_each_device(kernel, a.values, a_scale, b.values,
                                b_scale, bi)


def _tiled_matmul_padded(av, a_scale, bv, b_scale, bias, *, block_m, block_n,
                         block_k, out_dtype, interpret):
    """Legacy policy: zero-pad operands to block multiples, slice the result.

    Exact in int8, but moves every operand through an HBM pad copy and the
    output through a slice copy — ``benchmarks/partial_tile.py`` quantifies
    the delta against the native path.
    """
    m, k = av.shape
    _, n = bv.shape
    mp = round_up(m, block_m)
    np_ = round_up(n, block_n)
    kp = round_up(k, block_k) if block_k else round_up(k, MXU_DIM)
    av = jnp.pad(av, ((0, mp - m), (0, kp - k)))
    bv = jnp.pad(bv, ((0, kp - k), (0, np_ - n)))
    sa = jnp.pad(a_scale, ((0, mp - m), (0, 0)), constant_values=1.0)
    sb = jnp.pad(b_scale, ((0, 0), (0, np_ - n)), constant_values=1.0)
    bi = (jnp.pad(bias.reshape(1, -1).astype(jnp.float32),
                  ((0, 0), (0, np_ - n)))
          if bias is not None else None)
    out = tiled_matmul_kernel(av, sa, bv, sb, bi,
                              block_m=block_m, block_n=block_n,
                              block_k=block_k, out_dtype=out_dtype,
                              interpret=interpret)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("out_dtype", "mode",
                                             "act_bits"))
def quantized_matmul(x: jax.Array, w: QTensor,
                     bias: jax.Array | None = None, *,
                     out_dtype=jnp.bfloat16, mode: str | None = None,
                     act_bits: int = 8) -> jax.Array:
    """Dynamic-activation-quant GEMM: quantize x per-row then tiled_matmul.

    This is the FPGAQuantizedLinear inner loop (paper §6.2): quantize input
    activations to int8, offload the int8 GEMM, dequantize + bias.  Plan
    selection routes through the GEMM dispatcher at trace time.
    """
    xq = quantize(x, channel_axes=(0,), bits=act_bits)
    return tiled_matmul(xq, w, bias, out_dtype=out_dtype, mode=mode)
