"""Compile guard: the main-path kernels compile for a TPU v5e.

The TPU compiler is installed beside JAX, and it compiles for a chip that
is described rather than attached (``topologies.get_topology_desc``).
That catches, with no chip, what interpret mode cannot: blocks that break
Mosaic's (sublane, lane) tiling rule and plans that overflow the scoped
VMEM.  Every kernel is compiled at qwen2.5-3B / paper widths with
``interpret=False`` and the plan the dispatcher picks.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test runner's workers all
import this file.  The persistent compilation cache is off around the
compiles (an entry compiled for a described chip cannot be read back).
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs import get_config
from repro.core.dispatch import select_plan
from repro.core.quantization import QTensor
from repro.core.tiling import VMEM_PLAN_BUDGET
from repro.kernels.flash_attention.ops import (flash_attention,
                                               paged_decode_attention)
from repro.kernels.fused_qkv.ops import fused_qkv
from repro.kernels.quant_act.ops import quant_act
from repro.kernels.tiled_matmul.ops import tiled_matmul

QWEN = get_config("qwen2_5_3b")
PAGE = 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip from argument shapes."""
    def place(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)
    args = jax.tree.map(place, shapes)
    return jax.jit(fn).lower(*args).compile()


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _qtensor(rows, cols, scale_axis):
    scale = (rows, 1) if scale_axis == 0 else (1, cols)
    return QTensor(_sds((rows, cols), jnp.int8), _sds(scale, jnp.float32))


@pytest.mark.parametrize("m,k,n", [
    (64, 768, 3072),                   # the paper's GEMM
    (256, QWEN.d_ff, QWEN.d_model),    # qwen2.5 down projection, one chunk
    (4, QWEN.d_ff, QWEN.d_model),      # ... one 4-slot decode step
])
def test_tiled_matmul_compiles(one_chip, m, k, n):
    plan = select_plan(m, k, n, out_dtype=jnp.bfloat16, interpret=False)
    assert plan.vmem_footprint <= VMEM_PLAN_BUDGET
    _compile(functools.partial(tiled_matmul, mode="pallas"), one_chip,
             _qtensor(m, k, 0), _qtensor(k, n, 1), _sds((n,), jnp.float32))


@pytest.mark.parametrize("m", [8, 256])
def test_fused_qkv_compiles(one_chip, m):
    d, kv = QWEN.d_model, QWEN.kv_dim
    _compile(functools.partial(fused_qkv, mode="pallas",
                               out_dtype=jnp.float32), one_chip,
             _qtensor(m, d, 0), _qtensor(d, QWEN.q_dim, 1),
             _qtensor(d, kv, 1), _qtensor(d, kv, 1))


def test_quant_act_compiles(one_chip):
    _compile(functools.partial(quant_act, mode="pallas"), one_chip,
             _sds((256, QWEN.d_model), jnp.bfloat16))


def test_flash_prefill_compiles(one_chip):
    h, kh, hd = QWEN.n_heads, QWEN.n_kv_heads, QWEN.head_dim
    _compile(functools.partial(flash_attention, mode="pallas"), one_chip,
             _sds((1, 1024, h, hd), jnp.bfloat16),
             _sds((1, 1024, kh, hd), jnp.bfloat16),
             _sds((1, 1024, kh, hd), jnp.bfloat16))


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
@pytest.mark.parametrize("batch,rows,q_chunk", [(4, 1, None),
                                                (1, 256, 128)])
def test_paged_kernel_compiles(one_chip, kv_quant, batch, rows, q_chunk):
    h, kh, hd = QWEN.n_heads, QWEN.n_kv_heads, QWEN.head_dim
    max_pages, n_pages = 17, 69
    pool = _sds((n_pages, kh, PAGE, hd),
                jnp.int8 if kv_quant == "int8" else jnp.bfloat16)
    scales = (_sds((n_pages, kh, PAGE), jnp.float32),) * 2 \
        if kv_quant == "int8" else (None, None)

    def paged(q, kp, vp, table, lens, ks, vs):
        return paged_decode_attention(q, kp, vp, table, lens,
                                      q_chunk=q_chunk, k_scales=ks,
                                      v_scales=vs, mode="pallas")

    _compile(paged, one_chip, _sds((batch, rows, h, hd), jnp.bfloat16),
             pool, pool, _sds((batch, max_pages), jnp.int32),
             _sds((batch,), jnp.int32), *scales)


def test_gemm_compiles_under_serving_mesh(topo):
    """Mesh-sharded serving traces the GEMMs inside a 4-device sharding
    context; GSPMD cannot partition a Mosaic kernel, so the call must run
    whole on each device."""
    import numpy as np

    from repro.serving.engine import _mesh_context

    mesh = Mesh(np.asarray(topo.devices), ("model",))
    m, k, n = 256, QWEN.d_ff, QWEN.d_model

    def gemm(x, w):
        with _mesh_context(mesh):
            return tiled_matmul(quant_act(x, mode="pallas"), w,
                                mode="pallas")

    _compile(gemm, NamedSharding(mesh, PartitionSpec()),
             _sds((m, k), jnp.bfloat16), _qtensor(k, n, 1))


def test_kernels_replicate_only_under_serving(topo):
    """Only the serving mesh, which holds params whole on each device,
    runs the Pallas calls whole on each device; under any other sharding
    context (a training mesh shards params) they stay plain calls."""
    import numpy as np

    from repro.launch.sharding import activate_sharding
    from repro.serving.cache import SERVING_RULES
    from repro.serving.engine import _mesh_context

    mesh = Mesh(np.asarray(topo.devices), ("model",))
    args = (_sds((256, QWEN.d_model), jnp.bfloat16),
            _qtensor(QWEN.d_model, QWEN.kv_dim, 1))

    def traced(ctx):
        def gemm(x, w):
            with ctx:
                return tiled_matmul(quant_act(x, mode="pallas"), w,
                                    mode="pallas")
        return str(jax.make_jaxpr(gemm)(*args))

    assert "shard_map" not in traced(activate_sharding(mesh, SERVING_RULES))
    assert traced(_mesh_context(mesh)).count("shard_map") == 2
