"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantization import quantize
from repro.kernels.fused_qkv.ops import fused_qkv
from repro.kernels.quant_act.ops import quant_act
from repro.kernels.tiled_matmul.ops import tiled_matmul
from repro.kernels.tiled_matmul.ref import matmul_f32_oracle

RNG = np.random.default_rng(0)

# paper shapes (§6.2) + partial tiles + tall/wide
SHAPES = [
    (64, 768, 768),        # DistilBERT attention case (paper Table 2)
    (64, 768, 3072),       # FFN case (paper Table 2)
    (100, 300, 513),       # partial tiles in every dim
    (256, 512, 384),
    (1, 128, 128),         # degenerate M
    (128, 4096, 256),      # K-split path territory
]


def _mk(m, k, n, dtype=np.float32):
    a = RNG.normal(size=(m, k)).astype(dtype)
    b = (RNG.normal(size=(k, n)) * 0.05).astype(dtype)
    return jnp.asarray(a), jnp.asarray(b)


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_tiled_matmul_pallas_matches_ref(m, k, n):
    a, b = _mk(m, k, n)
    aq = quantize(a, channel_axes=(0,))
    bq = quantize(b, channel_axes=(1,))
    out_ref = tiled_matmul(aq, bq, out_dtype=jnp.float32, mode="ref")
    out_pal = tiled_matmul(aq, bq, out_dtype=jnp.float32,
                           mode="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(out_ref), np.asarray(out_pal))


@pytest.mark.parametrize("m,k,n", [(64, 768, 768), (100, 300, 513)])
def test_tiled_matmul_bias_epilogue(m, k, n):
    a, b = _mk(m, k, n)
    bias = jnp.asarray(RNG.normal(size=(n,)).astype(np.float32))
    aq = quantize(a, channel_axes=(0,))
    bq = quantize(b, channel_axes=(1,))
    out_ref = tiled_matmul(aq, bq, bias, out_dtype=jnp.float32, mode="ref")
    out_pal = tiled_matmul(aq, bq, bias, out_dtype=jnp.float32,
                           mode="pallas_interpret")
    # bias add may fuse differently (FMA): <= 1 ULP
    np.testing.assert_allclose(np.asarray(out_ref), np.asarray(out_pal),
                               atol=1e-6, rtol=0)


def test_tiled_matmul_ksplit_exact():
    a, b = _mk(128, 4096, 256)
    aq = quantize(a, channel_axes=(0,))
    bq = quantize(b, channel_axes=(1,))
    out_ref = tiled_matmul(aq, bq, out_dtype=jnp.float32, mode="ref")
    out_pal = tiled_matmul(aq, bq, block_m=128, block_n=128, block_k=1024,
                           out_dtype=jnp.float32, mode="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(out_ref), np.asarray(out_pal))


@pytest.mark.parametrize("out_dtype", [jnp.bfloat16, jnp.float32])
def test_tiled_matmul_out_dtypes(out_dtype):
    a, b = _mk(64, 768, 768)
    aq = quantize(a, channel_axes=(0,))
    bq = quantize(b, channel_axes=(1,))
    out_ref = tiled_matmul(aq, bq, out_dtype=out_dtype, mode="ref")
    out_pal = tiled_matmul(aq, bq, out_dtype=out_dtype,
                           mode="pallas_interpret")
    assert out_ref.dtype == out_dtype
    np.testing.assert_array_equal(
        np.asarray(out_ref, np.float32), np.asarray(out_pal, np.float32))


def test_quantized_matmul_accuracy_vs_f32():
    """Paper §6.2: int8 path within quantization error of fp32 (<1e-2)."""
    a, b = _mk(64, 768, 3072)
    aq = quantize(a, channel_axes=(0,))
    bq = quantize(b, channel_axes=(1,))
    out = tiled_matmul(aq, bq, out_dtype=jnp.float32, mode="ref")
    oracle = matmul_f32_oracle(a, b)
    rel = float(jnp.linalg.norm(out - oracle) / jnp.linalg.norm(oracle))
    assert rel < 2e-2, rel


@pytest.mark.parametrize("m,k", [(64, 768), (100, 300), (256, 1024), (1, 8)])
def test_quant_act_matches_ref(m, k):
    x = jnp.asarray(RNG.normal(size=(m, k)).astype(np.float32))
    r = quant_act(x, mode="ref")
    p = quant_act(x, mode="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(r.values), np.asarray(p.values))
    np.testing.assert_allclose(np.asarray(r.scale), np.asarray(p.scale),
                               atol=1e-8)


def test_quant_act_zero_rows():
    x = jnp.zeros((8, 64), jnp.float32)
    q = quant_act(x, mode="pallas_interpret")
    assert np.all(np.asarray(q.values) == 0)
    assert np.all(np.asarray(q.scale) == 1.0)


@pytest.mark.parametrize("m,nq,nkv", [(64, 1024, 256), (100, 768, 768),
                                      (128, 512, 128)])
def test_fused_qkv_matches_ref(m, nq, nkv):
    k_dim = 384
    a = jnp.asarray(RNG.normal(size=(m, k_dim)).astype(np.float32))
    aq = quantize(a, channel_axes=(0,))
    ws = [quantize(jnp.asarray((RNG.normal(size=(k_dim, n)) * 0.05)
                               .astype(np.float32)), channel_axes=(1,))
          for n in (nq, nkv, nkv)]
    ref = fused_qkv(aq, *ws, out_dtype=jnp.float32, mode="ref")
    pal = fused_qkv(aq, *ws, out_dtype=jnp.float32, mode="pallas_interpret")
    for r, p in zip(ref, pal):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(p))


def test_fused_qkv_shares_activation_quant():
    """The update_A analogue: one activation quantization for all three."""
    from repro.core.qkv_fusion import apply_fused_qkv
    from repro.core.quantized_linear import (apply_linear, init_linear,
                                             quantize_linear)
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    x = jax.random.normal(jax.random.PRNGKey(9), (4, 16, 96), jnp.float32)
    ps = [quantize_linear(init_linear(k_, 96, n))
          for k_, n in zip(ks, (128, 64, 64))]
    q, k, v = apply_fused_qkv(*ps, x, mode="w8a8", out_dtype=jnp.float32)
    q2 = apply_linear(ps[0], x, mode="w8a8", out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(q), np.asarray(q2), atol=1e-6)


def test_kernel_mode_selection(monkeypatch):
    """REPRO_KERNELS names the mode (unknown names raise); unset, a TPU
    runs the compiled kernels and anything else the jnp oracles; an
    explicit non-compiled mode on a TPU warns."""
    import warnings

    from repro.kernels.tiled_matmul.ops import kernel_mode
    monkeypatch.setenv("REPRO_KERNELS", "pallas_interpret")
    assert kernel_mode() == "pallas_interpret"
    monkeypatch.setenv("REPRO_KERNELS", "mosaic")
    with pytest.raises(ValueError, match="REPRO_KERNELS"):
        kernel_mode()
    monkeypatch.delenv("REPRO_KERNELS")
    assert kernel_mode() == "ref"                   # this CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert kernel_mode() == "pallas"
    monkeypatch.setenv("REPRO_KERNELS", "pallas_interpret")
    with pytest.warns(UserWarning, match="TPU"):
        assert kernel_mode() == "pallas_interpret"
