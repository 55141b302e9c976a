"""Jit'd wrapper for fused activation quantization with backend dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quantization import QTensor
from repro.core.tiling import round_up
from repro.kernels.quant_act import ref as _ref
from repro.kernels.quant_act.kernel import quant_act_block_m, quant_act_kernel
from repro.kernels.tiled_matmul.ops import kernel_mode, whole_on_each_device

__all__ = ["quant_act"]


def quant_act(x: jax.Array, *, block_m: int | None = None,
              mode: str | None = None) -> QTensor:
    """Per-row int8 quantization of a 2-D activation matrix.  ``block_m``
    defaults to the most rows whose block fits the VMEM budget."""
    mode = mode or kernel_mode()
    m, k = x.shape
    if mode == "ref":
        values, scale = _ref.quant_act_ref(x)
        return QTensor(values=values, scale=scale, bits=8)
    block_m = block_m or quant_act_block_m(k, x.dtype.itemsize)
    block_m = min(block_m, m) if m % block_m else block_m
    mp = round_up(m, block_m)
    xp = jnp.pad(x, ((0, mp - m), (0, 0)))
    values, scale = whole_on_each_device(
        functools.partial(quant_act_kernel, block_m=block_m,
                          interpret=(mode == "pallas_interpret")), xp)
    return QTensor(values=values[:m], scale=scale[:m], bits=8)
