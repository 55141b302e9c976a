"""Quantized KV page pools (``kv_quant="int8"``): kernel parity, cache
invariants, CoW scale rows, serving-path guards.

Coverage layers:

  * **quantize_kv** — per-row absmax roundtrip bound, zero-row guard.
  * **Kernel vs quantized oracle** — the int8 paged flash-decode kernel
    (interpret mode) against the quantized ``paged_attention_ref`` and,
    *bitwise*, against the fp kernel run on pre-dequantized pools: the
    in-kernel dequant is exactly ``values.astype(f32) * scale``, so both
    kernels see identical fp operands.  The big
    {GQA} × {window} × {page size} × {mixed lengths} cross product is
    marked slow.
  * **Cache layout** — int8 pool + scale shapes/dtypes, page-byte ratio,
    SSM f32 state contract, logical sharding axes.
  * **Serving** — fork-then-decode bitwise parity (shared prefix vs
    disjoint copies — proves CoW copies the scale rows), the
    ``validate_decode_cache`` combo guards, fp-vs-int8 end-to-end greedy
    agreement, interpret-mode kernel through ``serve_step``, and the
    continuous-batching scheduler on an int8 pool.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.quantization import qmax_for_bits, quantize_kv
from repro.kernels.flash_attention.ops import paged_decode_attention
from repro.models.transformer import init_model
from repro.serving import allocator as alloc
from repro.serving.cache import (PAGE_STATE_KEYS, CacheConfig,
                                 cache_logical_axes, default_page_table,
                                 init_cache, page_nbytes)
from repro.serving.engine import (greedy_decode, prefill, serve_step,
                                  validate_decode_cache)
from repro.serving.scheduler import Scheduler

RNG = np.random.default_rng(7)
KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def _quant_pools(hist, page, table):
    """Quantize a dense (B, T, KH, D) history row-wise and scatter it
    into head-major (P, KH, page, D) int8 pools + (P, KH, page) f32
    scales."""
    b, t, kh, d = hist.shape
    mp = t // page
    q, s = quantize_kv(jnp.asarray(hist))
    q, s = np.asarray(q), np.asarray(s)
    pool = np.zeros((b * mp, kh, page, d), np.int8)
    scales = np.zeros((b * mp, kh, page), np.float32)
    for bb in range(b):
        for j in range(mp):
            rows = slice(j * page, (j + 1) * page)
            pool[int(table[bb, j])] = q[bb, rows].swapaxes(0, 1)
            scales[int(table[bb, j])] = s[bb, rows].T
    return jnp.asarray(pool), jnp.asarray(scales)


def _quant_case(b, t, h, kh, d, page, lens, *, window=None, cap=None):
    """int8 kernel (interpret) vs quantized ref oracle (allclose) and vs
    the fp kernel on pre-dequantized pools (bitwise)."""
    table = default_page_table(b, t // page, "striped")
    hist_k = RNG.normal(size=(b, t, kh, d)).astype(np.float32)
    hist_v = RNG.normal(size=(b, t, kh, d)).astype(np.float32)
    kp, ks = _quant_pools(hist_k, page, table)
    vp, vs = _quant_pools(hist_v, page, table)
    q = jnp.asarray(RNG.normal(size=(b, 1, h, d)).astype(np.float32))
    lens = jnp.asarray(lens, jnp.int32)

    out = paged_decode_attention(q, kp, vp, table, lens, window=window,
                                 softcap=cap, k_scales=ks, v_scales=vs,
                                 mode="pallas_interpret")
    ref = paged_decode_attention(q, kp, vp, table, lens, window=window,
                                 softcap=cap, k_scales=ks, v_scales=vs,
                                 mode="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-6, rtol=1e-5)
    # bitwise vs the fp kernel on pools dequantized up front: the fused
    # dequant must be exactly values * scale, no reassociation
    kf = kp.astype(jnp.float32) * ks[..., None]
    vf = vp.astype(jnp.float32) * vs[..., None]
    fp = paged_decode_attention(q, kf, vf, table, lens, window=window,
                                softcap=cap, mode="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(fp))


def _prefill_view(params, cache, cfg, b, prompt, start=0):
    """Prefill one row of a multi-slot paged cache through a batch-1
    view (the ``Scheduler._prefill_slot`` pattern); returns the first
    greedy token id."""
    suffix = np.asarray(prompt[start:], np.int32)
    view = dict(cache)
    view["page_table"] = cache["page_table"][b:b + 1]
    view["seq_lens"] = cache["seq_lens"][b:b + 1]
    nl, view = prefill(params, view, jnp.asarray(suffix[None]),
                       jnp.asarray([len(prompt)], jnp.int32), cfg,
                       start_pos=start)
    for key in PAGE_STATE_KEYS:
        if key in view:
            cache[key] = view[key]
    cache["seq_lens"] = cache["seq_lens"].at[b].set(view["seq_lens"][0])
    return int(jnp.argmax(nl[0]))


# ---------------------------------------------------------------------------
# quantize_kv
# ---------------------------------------------------------------------------
def test_quantize_kv_roundtrip():
    x = RNG.normal(size=(2, 5, 3, 16)).astype(np.float32)
    x[1, 2, 1] = 0.0                         # zero row: scale guard
    q, s = quantize_kv(jnp.asarray(x))
    assert q.dtype == jnp.int8 and q.shape == x.shape
    assert s.dtype == jnp.float32 and s.shape == x.shape[:-1]
    assert int(jnp.max(jnp.abs(q))) <= qmax_for_bits(8)
    deq = np.asarray(q, np.float32) * np.asarray(s)[..., None]
    # absmax rounding: error per element bounded by half a quant step
    err = np.abs(deq - x)
    assert np.all(err <= 0.5 * np.asarray(s)[..., None] + 1e-7)
    np.testing.assert_array_equal(deq[1, 2, 1], np.zeros(16))


# ---------------------------------------------------------------------------
# cache layout
# ---------------------------------------------------------------------------
def test_init_cache_int8_shapes_and_errors():
    cfg = get_smoke_config("qwen2_5_3b")
    cache = init_cache(cfg, 2, max_len=40,
                       config=CacheConfig(layout="paged", page_size=16,
                                          kv_quant="int8"))
    mp = 3
    assert cache["k_pages"].dtype == jnp.int8
    assert cache["v_pages"].dtype == jnp.int8
    assert cache["k_scales"].shape == (cfg.n_layers, 2 * mp,
                                       cfg.n_kv_heads, 16)
    assert cache["k_scales"].dtype == jnp.float32
    assert cache["v_scales"].shape == cache["k_scales"].shape
    with pytest.raises(ValueError, match="layout='paged'"):
        init_cache(cfg, 2, max_len=40, config=CacheConfig(kv_quant="int8"))
    with pytest.raises(ValueError, match="kv_quant"):
        init_cache(cfg, 2, max_len=40,
                   config=CacheConfig(layout="paged", kv_quant="int4"))


def test_page_nbytes_int8_ratio():
    cfg = get_smoke_config("qwen2_5_3b")
    fp = init_cache(cfg, 2, max_len=32, dtype=jnp.bfloat16,
                    config=CacheConfig(layout="paged", page_size=8))
    q = init_cache(cfg, 2, max_len=32, dtype=jnp.bfloat16,
                   config=CacheConfig(layout="paged", page_size=8,
                                      kv_quant="int8"))
    # per element: bf16 pages cost 2 bytes; int8 pages cost 1 + 4/head_dim
    # (the f32 scale amortized over its row) → ratio (1 + 4/hd) / 2
    hd = cfg.head_dim
    assert page_nbytes(q) * 2 * hd == page_nbytes(fp) * (hd + 4)
    assert page_nbytes(q) < page_nbytes(fp)


@pytest.mark.parametrize("arch", ["mamba2_370m", "zamba2_7b"])
def test_ssm_state_stays_f32(arch):
    """The cache contract: serving dtype applies to KV storage only —
    ``ssm_h`` and the ``conv_*`` tails accumulate across steps and stay
    f32 regardless of the requested dtype."""
    cfg = get_smoke_config(arch)
    for dtype in (jnp.bfloat16, jnp.float32):
        cache = init_cache(cfg, 2, max_len=16, dtype=dtype)
        for key in ("ssm_h", "conv_x", "conv_B", "conv_C"):
            assert cache[key].dtype == jnp.float32, (key, dtype)
        if "shared_k" in cache:              # hybrid: KV follows dtype
            assert cache["shared_k"].dtype == dtype


def test_cache_logical_axes_int8():
    cfg = get_smoke_config("qwen2_5_3b")
    axes = cache_logical_axes(cfg, layout="paged", kv_quant="int8")
    assert "k_scales" in axes and "v_scales" in axes
    # scales ride the same pool: identical axes minus the head_dim tail
    assert axes["k_scales"] == axes["k_pages"][:-1]
    assert axes["v_scales"] == axes["v_pages"][:-1]


# ---------------------------------------------------------------------------
# kernel vs quantized oracle
# ---------------------------------------------------------------------------
def test_int8_decode_matches_quant_ref():
    _quant_case(3, 128, 8, 2, 64, 16, [37, 5, 128])


def test_int8_decode_window_and_softcap():
    _quant_case(2, 128, 4, 1, 64, 16, [100, 23], window=20, cap=30.0)


def test_int8_decode_matches_fp_within_quant_error():
    """Accuracy sanity: the quantized path lands within the per-row
    absmax error envelope of the unquantized attention output."""
    b, t, h, kh, d, page = 2, 64, 4, 2, 32, 8
    table = default_page_table(b, t // page, "striped")
    hist_k = RNG.normal(size=(b, t, kh, d)).astype(np.float32)
    hist_v = RNG.normal(size=(b, t, kh, d)).astype(np.float32)
    kp, ks = _quant_pools(hist_k, page, table)
    vp, vs = _quant_pools(hist_v, page, table)
    q = jnp.asarray(RNG.normal(size=(b, 1, h, d)).astype(np.float32))
    lens = jnp.asarray([60, 33], jnp.int32)
    out_q = paged_decode_attention(q, kp, vp, table, lens, k_scales=ks,
                                   v_scales=vs, mode="ref")
    # fp pools through the same ref path
    from tests.test_paged_decode import _pools_from_history
    kf, vf = _pools_from_history(hist_k, hist_v, page, table)
    out_f = paged_decode_attention(q, kf, vf, table, lens, mode="ref")
    err = np.abs(np.asarray(out_q) - np.asarray(out_f))
    rel = err.max() / np.abs(np.asarray(out_f)).max()
    assert rel < 0.05, rel


@pytest.mark.slow
@pytest.mark.parametrize(
    "g,window,page,lens",
    list(itertools.product(
        [1, 4], [None, 48], [8, 16],
        [[64, 64], [37, 5], [128, 1], [96, 77]])))
def test_int8_decode_parity_sweep(g, window, page, lens):
    """{GQA} × {window} × {page size} × {mixed/non-multiple lens}."""
    h = 4
    _quant_case(2, 128, h, h // g, 64, page, lens, window=window)


# ---------------------------------------------------------------------------
# serving-path guards (unsupported combos fail loudly)
# ---------------------------------------------------------------------------
def test_unsupported_cache_combos_raise():
    cfg = get_smoke_config("qwen2_5_3b").replace(dtype="float32")
    cache = init_cache(cfg, 1, max_len=16,
                       config=CacheConfig(layout="paged", page_size=8,
                                          kv_quant="int8"))
    # int8 pages with the scale pools stripped: named combo, no garbage
    broken = {k: v for k, v in cache.items()
              if k not in ("k_scales", "v_scales")}
    with pytest.raises(NotImplementedError,
                       match=r"layout='paged', kv dtype int8, "
                             r"kv_quant=none"):
        validate_decode_cache(broken, cfg, "ref")
    # one scale pool missing
    half = {k: v for k, v in cache.items() if k != "v_scales"}
    with pytest.raises(NotImplementedError, match="BOTH"):
        validate_decode_cache(half, cfg, "ref")
    # scales present but fp pages
    mixed = dict(cache)
    mixed["k_pages"] = cache["k_pages"].astype(jnp.float32)
    mixed["v_pages"] = cache["v_pages"].astype(jnp.float32)
    with pytest.raises(NotImplementedError, match="not int8"):
        validate_decode_cache(mixed, cfg, "ref")
    # dense cache with integer KV: points at the paged int8 path
    dense = init_cache(cfg, 1, max_len=16, dtype=jnp.float32)
    dense["k"] = dense["k"].astype(jnp.int8)
    dense["v"] = dense["v"].astype(jnp.int8)
    with pytest.raises(NotImplementedError, match=r"layout='dense'"):
        validate_decode_cache(dense, cfg, "ref")


def test_greedy_decode_rejects_scaleless_int8():
    """The donated-cache scan entry itself refuses the combo — the error
    names kernel mode, layout, and quant state."""
    cfg = get_smoke_config("qwen2_5_3b").replace(quant_proj="none",
                                                 dtype="float32")
    params = init_model(KEY, cfg)
    cache = init_cache(cfg, 1, max_len=16, dtype=jnp.float32,
                       config=CacheConfig(layout="paged", page_size=8,
                                          kv_quant="int8"))
    broken = {k: v for k, v in cache.items()
              if k not in ("k_scales", "v_scales")}
    tok = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(NotImplementedError,
                       match=r"kernel_mode='ref'.*kv_quant=none"):
        greedy_decode(params, broken, tok, None, 2, cfg)


# ---------------------------------------------------------------------------
# allocator: CoW carries the scale rows
# ---------------------------------------------------------------------------
def test_fork_cow_copies_scale_rows():
    cfg = get_smoke_config("qwen2_5_3b")
    cache = init_cache(cfg, 2, max_len=32,
                       config=CacheConfig(layout="paged", page_size=8,
                                          alloc="dynamic", kv_quant="int8"))
    cache, ok = alloc.admit_sequence(cache, 0, 20)
    assert bool(ok)
    # stamp recognizable values on the parent's boundary page (page 1,
    # tokens 8..11 of a 12-token prefix)
    src = int(cache["page_table"][0, 1])
    cache["k_pages"] = cache["k_pages"].at[:, src].set(7)
    cache["k_scales"] = cache["k_scales"].at[:, src].set(0.5)
    cache["v_scales"] = cache["v_scales"].at[:, src].set(0.25)
    cache["seq_lens"] = cache["seq_lens"].at[0].set(12)
    cache, ok = alloc.fork_sequence(cache, 0, 1, 12, 20)
    assert bool(ok)
    dst = int(cache["page_table"][1, 1])
    assert dst != src                        # boundary page is private
    assert int(cache["page_table"][1, 0]) == int(cache["page_table"][0, 0])
    for key, want in (("k_pages", 7), ("k_scales", 0.5),
                      ("v_scales", 0.25)):
        np.testing.assert_array_equal(np.asarray(cache[key][:, dst]),
                                      np.asarray(cache[key][:, src]))
        assert float(cache[key][:, dst].max()) == want
    # child writes stay private: scales included
    cache["k_scales"] = cache["k_scales"].at[:, dst].set(9.0)
    assert float(cache["k_scales"][:, src].max()) == 0.5


def test_fork_then_decode_bitwise_int8():
    """Shared-prefix admission vs disjoint full copies over an int8
    pool: identical greedy tokens for parent and child.  Divergence
    would mean the boundary-page CoW dropped or staled the scale rows."""
    cfg = get_smoke_config("qwen2_5_3b").replace(quant_proj="none",
                                                 dtype="float32")
    params = init_model(KEY, cfg)
    prompt = np.asarray(RNG.integers(0, cfg.vocab_size, 14), np.int32)
    prefix, budget, steps = 10, 20, 4
    outs = {}
    for copy in (False, True):
        cache = init_cache(cfg, 2, max_len=24, dtype=jnp.float32,
                           config=CacheConfig(layout="paged", page_size=4,
                                              alloc="dynamic",
                                              kv_quant="int8"))
        cache, ok = alloc.admit_sequence(cache, 0, budget)
        assert bool(ok)
        t0 = _prefill_view(params, cache, cfg, 0, prompt)
        cache, ok = alloc.fork_sequence(cache, 0, 1, prefix, budget,
                                        copy=copy)
        assert bool(ok)
        t1 = _prefill_view(params, cache, cfg, 1, prompt, start=prefix)
        first = jnp.asarray([[t0], [t1]], jnp.int32)
        toks, _ = greedy_decode(params, cache, first, None, steps, cfg)
        outs[copy] = np.asarray(toks)
    np.testing.assert_array_equal(outs[False], outs[True])
    # the suffix re-prefill saw the same committed prefix: parent and
    # child rows decode the identical continuation of the same prompt
    np.testing.assert_array_equal(outs[False][0], outs[False][1])


# ---------------------------------------------------------------------------
# end to end
# ---------------------------------------------------------------------------
def test_paged_int8_engine_matches_fp():
    """fp32 vs int8 page pools through prefill → greedy_decode on a
    distilbert-class smoke model: ≥99% top-1 token agreement and small
    first-logits error."""
    cfg = get_smoke_config("distilbert_paper").replace(quant_proj="none",
                                                       dtype="float32")
    params = init_model(KEY, cfg)
    b, s_pad, steps = 4, 16, 8
    toks = jax.random.randint(jax.random.PRNGKey(3), (b, s_pad), 0,
                              cfg.vocab_size)
    lens = jnp.asarray([12, 5, 9, 16], jnp.int32)
    outs, logits = {}, {}
    for quant in ("none", "int8"):
        cache = init_cache(cfg, b, max_len=32, dtype=jnp.float32,
                           config=CacheConfig(layout="paged", page_size=8,
                                              alloc="striped",
                                              kv_quant=quant))
        nl, cache = prefill(params, cache, toks, lens, cfg)
        first = jnp.argmax(nl, -1)[:, None].astype(jnp.int32)
        out, _ = greedy_decode(params, cache, first, None, steps, cfg)
        outs[quant], logits[quant] = np.asarray(out), np.asarray(nl)
    agree = (outs["none"] == outs["int8"]).mean()
    assert agree >= 0.99, agree
    rel = (np.abs(logits["int8"] - logits["none"]).max()
           / np.abs(logits["none"]).max())
    assert rel < 0.01, rel


def test_serve_step_int8_interpret_matches_ref(monkeypatch):
    """The int8 dequant path lowers through the Pallas (interpret)
    flash-decode kernel end to end and matches the ref lowering."""
    cfg = get_smoke_config("qwen2_5_3b").replace(quant_proj="none",
                                                 dtype="float32")
    params = init_model(KEY, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                              cfg.vocab_size)
    lens = jnp.asarray([6, 4], jnp.int32)
    got = {}
    for mode in ("ref", "pallas_interpret"):
        monkeypatch.setenv("REPRO_KERNELS", mode)
        cache = init_cache(cfg, 2, max_len=16, dtype=jnp.float32,
                           config=CacheConfig(layout="paged", page_size=4,
                                              kv_quant="int8"))
        _, cache = prefill(params, cache, toks, lens, cfg)
        lg, _ = serve_step(params, cache, toks[:, :1], None, cfg)
        got[mode] = np.asarray(lg)
    np.testing.assert_allclose(got["ref"], got["pallas_interpret"],
                               atol=2e-4, rtol=2e-4)


def test_scheduler_int8_prefix_sharing_bitwise():
    """Continuous batching over an int8 pool: prefix sharing on vs off
    produces identical generations — aliased pages + CoW'd scale rows
    are indistinguishable from recomputed private pages."""
    cfg = get_smoke_config("qwen2_5_3b").replace(quant_proj="none",
                                                 dtype="float32")
    params = init_model(KEY, cfg)
    base = RNG.integers(0, cfg.vocab_size, 9).astype(np.int32)
    prompts = [base, np.concatenate([base[:6],
                                     [1, 2, 3]]).astype(np.int32),
               RNG.integers(0, cfg.vocab_size, 5).astype(np.int32)]
    results = {}
    for share in (True, False):
        sched = Scheduler(params, cfg, slots=2, max_len=32, bucket=4,
                          share_prefix=share,
                          config=CacheConfig(layout="paged", alloc="dynamic",
                                             page_size=4, pool_pages=16,
                                             kv_quant="int8"))
        for p in prompts:
            sched.submit(p, 4)
        results[share] = sched.run(max_ticks=64)
    assert set(results[True]) == set(results[False]) == {0, 1, 2}
    for rid in results[True]:
        np.testing.assert_array_equal(results[True][rid],
                                      results[False][rid])
        assert results[True][rid].size == 4
