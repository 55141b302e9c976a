"""Property-based tests (hypothesis) for quantization + tiling invariants.

Runs under real hypothesis when installed (requirements-dev.txt / CI);
otherwise _hypothesis_compat substitutes a deterministic example sweep so
the module collects and the invariants still run everywhere.
"""
import jax.numpy as jnp
import numpy as np

from _hypothesis_compat import given, settings, st

from repro.core.quantization import (Calibrator, dequantize, fake_quantize,
                                     qmax_for_bits, quantize)
from repro.core.tiling import MXU_DIM, TilePlan, choose_plan, VMEM_PLAN_BUDGET

settings.register_profile("ci", max_examples=30, deadline=None)
settings.load_profile("ci")

finite_f32 = st.floats(-1e4, 1e4, allow_nan=False, allow_infinity=False,
                       width=32)


@given(st.lists(finite_f32, min_size=1, max_size=64),
       st.sampled_from([4, 8]))
def test_roundtrip_error_bound(vals, bits):
    """|dequant(quant(x)) - x| <= scale/2 elementwise (symmetric rounding)."""
    x = jnp.asarray(np.array(vals, np.float32).reshape(1, -1))
    q = quantize(x, channel_axes=(0,), bits=bits)
    err = np.abs(np.asarray(dequantize(q)) - np.asarray(x))
    bound = np.asarray(q.scale) / 2 + 1e-9
    assert np.all(err <= bound)


@given(st.lists(finite_f32, min_size=2, max_size=64).filter(
    lambda v: len(v) % 2 == 0))
def test_quantized_range(vals):
    x = jnp.asarray(np.array(vals, np.float32).reshape(2, -1))
    q = quantize(x, channel_axes=(0,))
    v = np.asarray(q.values)
    assert v.min() >= -127 and v.max() <= 127
    assert np.all(np.asarray(q.scale) > 0)


def test_zeros_quantize_to_zeros():
    q = quantize(jnp.zeros((4, 8)), channel_axes=(0,))
    assert np.all(np.asarray(q.values) == 0)
    assert np.all(np.asarray(q.scale) == 1.0)
    assert np.all(np.asarray(dequantize(q)) == 0.0)


@given(st.integers(2, 8))
def test_qmax(bits):
    assert qmax_for_bits(bits) == 2 ** (bits - 1) - 1


def test_per_channel_independence():
    """Scaling one channel never changes another channel's quantization."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(4, 16)).astype(np.float32)
    q1 = quantize(jnp.asarray(x), channel_axes=(0,))
    x2 = x.copy()
    x2[0] *= 100.0
    q2 = quantize(jnp.asarray(x2), channel_axes=(0,))
    np.testing.assert_array_equal(np.asarray(q1.values)[1:],
                                  np.asarray(q2.values)[1:])


def test_fake_quantize_straight_through():
    import jax
    x = jnp.asarray(np.linspace(-2, 2, 32, dtype=np.float32).reshape(1, -1))
    g = jax.grad(lambda v: jnp.sum(fake_quantize(v, channel_axes=(0,))))(x)
    np.testing.assert_array_equal(np.asarray(g), np.ones_like(np.asarray(g)))


def test_calibrator_fixed_scale():
    cal = Calibrator()
    rng = np.random.default_rng(0)
    for _ in range(5):
        cal.observe(jnp.asarray(rng.normal(size=(8, 8)).astype(np.float32)))
    s = cal.scale
    assert s > 0
    q = cal.quantize(jnp.asarray(rng.normal(size=(4, 4)).astype(np.float32)))
    assert abs(float(q.scale.reshape(())) - s) < 1e-7 * s


# ---------------------------------------------------------------------------
# Tiling-plan invariants (the paper's DSE, automated)
# ---------------------------------------------------------------------------
@given(st.integers(1, 4096), st.integers(1, 8192), st.integers(1, 8192))
def test_choose_plan_fits_and_covers(m, k, n):
    plan = choose_plan(m, k, n)
    assert plan.fits_vmem(VMEM_PLAN_BUDGET)
    # full coverage: blocks tile the (padded) problem
    assert plan.block_m % MXU_DIM == 0 or plan.block_m >= m
    assert -(-m // plan.block_m) * plan.block_m >= m
    assert -(-n // plan.block_n) * plan.block_n >= n
    assert plan.k_steps * plan.block_k >= k


def test_vmem_footprint_double_buffers_every_block():
    """The footprint is what Pallas allocates: every input and output
    block double-buffered (the j-invariant A panel too), each padded to
    the VMEM tiling, plus the int32 product and its f32 dequant — and the
    K-split accumulator.  qwen2.5's down projection as one 256-row chunk
    then plans inside the limit the kernel is compiled with."""
    from repro.core.tiling import VMEM_LIMIT_BYTES, vmem_tile_bytes
    m, k, n = 256, 11008, 2048
    out = vmem_tile_bytes(256, 512, 2)
    scales = vmem_tile_bytes(256, 1, 4) + 2 * vmem_tile_bytes(1, 512, 4)
    acc = vmem_tile_bytes(256, 512, 4)
    panel = TilePlan(m, k, n, block_m=256, block_n=512, block_k=k)
    a_panel = vmem_tile_bytes(256, k, 1)
    b_panel = vmem_tile_bytes(k, 512, 1)
    assert panel.vmem_footprint >= 2 * (a_panel + b_panel)
    assert panel.vmem_footprint == (2 * (a_panel + b_panel + out + scales)
                                    + 2 * acc)
    split = TilePlan(m, k, n, block_m=256, block_n=512, block_k=2048)
    assert split.vmem_footprint == 2 * (
        vmem_tile_bytes(256, 2048, 1) + vmem_tile_bytes(2048, 512, 1)
        + out + scales) + 3 * acc
    # an 8-row int8 block still occupies a whole 32-row sublane tile
    assert vmem_tile_bytes(8, 128, 1) == vmem_tile_bytes(32, 128, 1)
    plan = choose_plan(m, k, n)
    assert plan.vmem_footprint <= VMEM_PLAN_BUDGET < VMEM_LIMIT_BYTES


@given(st.integers(64, 1024), st.integers(64, 4096), st.integers(64, 4096))
def test_reuse_model_monotonic(m, k, n):
    """Bigger block_m (more A rows resident) never increases B traffic."""
    small = TilePlan(m, k, n, block_m=128, block_n=128, block_k=k)
    big = TilePlan(m, k, n, block_m=512, block_n=128, block_k=k)
    assert big.hbm_traffic <= small.hbm_traffic


def test_paper_shape_plan_is_panel_resident():
    """The DistilBERT shapes fit the persistent-A schedule (paper §4)."""
    for (m, k, n) in [(64, 768, 768), (64, 768, 3072)]:
        plan = choose_plan(m, k, n)
        assert plan.k_steps == 1          # A panel holds the full K
        assert plan.arithmetic_intensity > 100


# ---------------------------------------------------------------------------
# apply_linear(mode="w8") on-the-fly quantization: stack-aware scales
# ---------------------------------------------------------------------------
def test_w8_stacked_weights_parity():
    """Regression: on-the-fly w8 quantize of scan-stacked (L, K, N) master
    weights used channel_axes=(1,) — per-K-row scales reduced over the
    layer dim.  It must match quantize_linear's per-(layer, out-channel)
    scales and the per-layer application bitwise."""
    from repro.core.quantized_linear import apply_linear, quantize_linear

    rng = np.random.default_rng(11)
    L, M, K, N = 3, 4, 16, 8
    w = jnp.asarray(rng.normal(size=(L, K, N)).astype(np.float32))
    # make per-layer absmax genuinely different so wrong axes change scales
    w = w * jnp.asarray([0.1, 1.0, 10.0])[:, None, None]
    x = jnp.asarray(rng.normal(size=(L, M, K)).astype(np.float32))

    y_fly = apply_linear({"w": w}, x, mode="w8")
    y_offline = apply_linear(quantize_linear({"w": w}), x, mode="w8")
    y_per_layer = jnp.stack(
        [apply_linear({"w": w[layer]}, x[layer], mode="w8")
         for layer in range(L)])
    np.testing.assert_array_equal(np.asarray(y_fly), np.asarray(y_offline))
    np.testing.assert_array_equal(np.asarray(y_fly), np.asarray(y_per_layer))

    # stacked bias must align its layer axis to y's axis 0 even with an
    # extra batch dim (L == B is the silent-wrong trap)
    b = jnp.asarray(rng.normal(size=(L, N)).astype(np.float32))
    xb = jnp.asarray(rng.normal(size=(L, L, M, K)).astype(np.float32))
    y = apply_linear({"w": w, "b": b}, xb, mode="w8")
    per = jnp.stack([apply_linear({"w": w[layer], "b": b[layer]}, xb[layer],
                                  mode="w8") for layer in range(L)])
    np.testing.assert_array_equal(np.asarray(y), np.asarray(per))


def test_w8_single_layer_unchanged():
    from repro.core.quantized_linear import apply_linear, quantize_linear

    rng = np.random.default_rng(12)
    w = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(8,)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32))
    y_fly = apply_linear({"w": w, "b": b}, x, mode="w8")
    y_off = apply_linear(quantize_linear({"w": w, "b": b}), x, mode="w8")
    np.testing.assert_array_equal(np.asarray(y_fly), np.asarray(y_off))
