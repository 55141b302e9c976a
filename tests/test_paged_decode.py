"""Paged-KV decode engine: kernel parity, cache invariants, serving loop.

Three layers of coverage:

  * **Kernel vs dense oracle** — the paged flash-decode Pallas kernel
    (interpret mode) against an independently-formulated dense reference
    (materialized GQA repeat + plain softmax over the gathered history),
    across {GQA group} × {sliding window} × {page size} ×
    {non-page-multiple lengths} × {mixed per-sequence lengths} — the big
    cross product is marked slow.
  * **Cache layout** — page-table invariants (disjoint pages, striped vs
    contiguous indistinguishable through the table), paged init shapes,
    logical sharding axes.
  * **Engine** — paged vs dense mixed-length batches produce identical
    greedy tokens; the ``lax.scan`` loop pins the legacy Python-loop
    behaviour; gemma2's traced local/global layers decode identically on
    both layouts; interpret-mode kernel end-to-end through ``serve_step``.
"""
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.tiling import VMEM_PLAN_BUDGET
from repro.kernels.flash_attention.decode import (MAX_PAGES_PER_BLOCK,
                                                 blocks_touched,
                                                 flash_decode_schedule,
                                                 grid_steps, pages_touched)
from repro.kernels.flash_attention.ops import paged_decode_attention
from repro.kernels.flash_attention.ref import paged_gather
from repro.models.transformer import init_model
from repro.serving.cache import (CacheConfig, default_page_table,
                                 init_cache)
from repro.serving.engine import greedy_decode, prefill, serve_step

RNG = np.random.default_rng(0)
KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def _pools_from_history(hist_k, hist_v, page, table):
    """Scatter a dense (B, T, KH, D) history into head-major
    (P, KH, page, D) pools."""
    b, t, kh, d = hist_k.shape
    mp = t // page
    kp = np.zeros((b * mp, kh, page, d), hist_k.dtype)
    vp = np.zeros_like(kp)
    for bb in range(b):
        for j in range(mp):
            rows = slice(j * page, (j + 1) * page)
            kp[int(table[bb, j])] = hist_k[bb, rows].swapaxes(0, 1)
            vp[int(table[bb, j])] = hist_v[bb, rows].swapaxes(0, 1)
    return jnp.asarray(kp), jnp.asarray(vp)


def _dense_decode_oracle(q, hist_k, hist_v, lens, *, window, cap, scale):
    """Independent formulation: materialized GQA repeat + full softmax.

    q (B, qs, H, D); hist (B, T, KH, D); lens (B,) context incl. q rows.
    """
    b, qs, h, d = q.shape
    kh = hist_k.shape[2]
    k = np.repeat(hist_k, h // kh, axis=2)          # (B, T, H, D)
    v = np.repeat(hist_v, h // kh, axis=2)
    t = k.shape[1]
    s = np.einsum("bshd,bthd->bhst", np.asarray(q, np.float32),
                  k.astype(np.float32)) * scale
    if cap is not None:
        s = cap * np.tanh(s / cap)
    q_pos = np.asarray(lens)[:, None] - qs + np.arange(qs)[None, :]
    mask = np.arange(t)[None, None, :] <= q_pos[:, :, None]   # (B, qs, T)
    if window is not None:
        mask &= np.arange(t)[None, None, :] > q_pos[:, :, None] - window
    s = np.where(mask[:, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhst,bthd->bshd", p, v.astype(np.float32))


def _case(b, t, h, kh, d, page, lens, *, window=None, cap=None, qs=1,
          alloc="striped"):
    table = default_page_table(b, t // page, alloc)
    hist_k = RNG.normal(size=(b, t, kh, d)).astype(np.float32)
    hist_v = RNG.normal(size=(b, t, kh, d)).astype(np.float32)
    kp, vp = _pools_from_history(hist_k, hist_v, page, table)
    q = jnp.asarray(RNG.normal(size=(b, qs, h, d)).astype(np.float32))
    lens = jnp.asarray(lens, jnp.int32)
    out = paged_decode_attention(q, kp, vp, table, lens, window=window,
                                 softcap=cap, mode="pallas_interpret")
    want = _dense_decode_oracle(q, hist_k, hist_v, lens, window=window,
                                cap=cap, scale=d ** -0.5)
    np.testing.assert_allclose(np.asarray(out), want, atol=5e-6, rtol=1e-5)
    # the pure-jnp paged oracle must agree too (it is the CPU lowering)
    ref = paged_decode_attention(q, kp, vp, table, lens, window=window,
                                 softcap=cap, mode="ref")
    np.testing.assert_allclose(np.asarray(ref), want, atol=5e-6, rtol=1e-5)


# ---------------------------------------------------------------------------
# kernel vs dense oracle
# ---------------------------------------------------------------------------
def test_paged_decode_matches_dense_mixed_lengths():
    # mixed, non-page-multiple lengths through a striped table
    _case(3, 128, 8, 2, 64, 16, [37, 5, 128])


def test_paged_decode_window_and_softcap():
    _case(2, 128, 4, 1, 64, 16, [100, 23], window=20, cap=30.0)


def test_paged_decode_multi_query_rows():
    # q_len > 1 (speculative-style step): rows at ctx-qs .. ctx-1
    _case(2, 64, 4, 2, 64, 8, [33, 17], qs=3)
    _case(2, 64, 4, 2, 64, 8, [33, 17], qs=3, window=12)


@pytest.mark.slow
@pytest.mark.parametrize(
    "g,window,page,lens,cap",
    list(itertools.product(
        [1, 4], [None, 48], [8, 16],
        [[64, 64], [37, 5], [128, 1], [96, 77]], [None, 30.0])))
def test_paged_decode_parity_sweep(g, window, page, lens, cap):
    """{GQA} × {window} × {page size} × {mixed/non-multiple lens} × {cap}."""
    h = 4
    _case(2, 128, h, h // g, 64, page, lens, window=window, cap=cap)


# Block edges of the page-block walk.  Page 8 over a 40-page table gives
# 16-page blocks (``pages_per_block`` is asserted below, so a different
# block size fails loudly instead of testing other edges); each case is
# (lens, extra keywords, the block size it needs).
EDGE_PAGE, EDGE_PAGES = 8, 40
BLOCK_EDGES = {
    # 100 → 13 pages of block 0; 200 → 25 pages, 9 into block 1
    "ends_mid_block": ([100, 200], {}, 16),
    # exactly one and exactly two whole blocks of pages
    "whole_blocks": ([128, 256], {}, 16),
    # 17 and 33 pages: the last block holds a single page
    "one_page_block": ([136, 264], {}, 16),
    # 20 tokens: blocks 1 and 2 lie wholly past j_hi
    "blocks_past_j_hi": ([20, 320], {}, 16),
    # length 0 on the scratch page beside a live row
    "idle_row": ([0, 77], {"idle": True}, 16),
    # a 200-token window: j_lo = page 12 (token 101, mid-page), the
    # 26-page walk spans a 16-page block and a partial one
    "window_j_lo_mid_page": ([300, 57], {"window": 200}, 16),
    # int8 pools with their scale rows, across both blocks and idle
    "int8_pools": ([200, 0, 128], {"quant": True, "idle": True}, 16),
    # verify mode: new_lens of 0 (every row dead) and the full count
    "verify_new_lens": ([130, 250], {"qs": 4, "new_lens": [0, 4]}, 16),
    # chunked prefill: 32 rows as two 16-row q blocks, each walking to
    # its own causal horizon
    "prefill_two_q_blocks": ([128, 301], {"qs": 32, "q_chunk": 16}, 16),
}


@pytest.mark.parametrize("edge", sorted(BLOCK_EDGES))
def test_paged_decode_block_edges(edge):
    """The kernel (interpret mode) against ``paged_attention_ref`` where
    the page-block walk turns: partial, whole and skipped blocks, idle
    rows, windows, int8 pools, verify rows and multi-q-block steps."""
    from repro.core.quantization import quantize_kv

    lens, kw, ppb = BLOCK_EDGES[edge]
    b, h, kh, d = len(lens), 4, 2, 64
    qs, q_chunk = kw.get("qs", 1), kw.get("q_chunk")
    window = kw.get("window")
    table = default_page_table(b, EDGE_PAGES, "striped")
    if kw.get("idle"):
        # idle rows point every page at the scratch page (id 0)
        table = jnp.where(jnp.asarray(lens)[:, None] == 0, 0, table)
    hist = RNG.normal(size=(2, b, EDGE_PAGES * EDGE_PAGE, kh, d))
    kp, vp = _pools_from_history(hist[0].astype(np.float32),
                                 hist[1].astype(np.float32), EDGE_PAGE,
                                 default_page_table(b, EDGE_PAGES,
                                                    "striped"))
    scales = {}
    if kw.get("quant"):
        (kp, ks), (vp, vs) = quantize_kv(kp), quantize_kv(vp)
        scales = {"k_scales": ks, "v_scales": vs}
    new_lens = kw.get("new_lens")
    if new_lens is not None:
        scales["new_lens"] = jnp.asarray(new_lens, jnp.int32)
    q = jnp.asarray(RNG.normal(size=(b, qs, h, d)).astype(np.float32))
    lens = jnp.asarray(lens, jnp.int32)

    sched = flash_decode_schedule(
        EDGE_PAGES, EDGE_PAGE, q_len=qs, window=window, q_chunk=q_chunk,
        group=h // kh, kv_heads=kh, head_dim=d, kv_dtype=kp.dtype)
    assert sched.pages_per_block == ppb, sched
    out = paged_decode_attention(q, kp, vp, table, lens, window=window,
                                 q_chunk=q_chunk, mode="pallas_interpret",
                                 **scales)
    want = paged_decode_attention(q, kp, vp, table, lens, window=window,
                                  mode="ref", **scales)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=5e-6, rtol=1e-5)
    if kw.get("idle"):
        np.testing.assert_array_equal(np.asarray(out)[np.asarray(lens) == 0],
                                      0.0)


def test_paged_gather_roundtrip():
    table = default_page_table(2, 4, "striped")
    hist = RNG.normal(size=(2, 32, 2, 8)).astype(np.float32)
    kp, _ = _pools_from_history(hist, hist, 8, table)
    np.testing.assert_array_equal(np.asarray(paged_gather(kp, table)), hist)


def test_allocation_indistinguishable_through_table():
    """Striped and contiguous physical placements must give identical
    results — the kernel only ever addresses pages through the table."""
    b, t, h, kh, d, page = 2, 64, 4, 2, 64, 8
    hist_k = RNG.normal(size=(b, t, kh, d)).astype(np.float32)
    hist_v = RNG.normal(size=(b, t, kh, d)).astype(np.float32)
    q = jnp.asarray(RNG.normal(size=(b, 1, h, d)).astype(np.float32))
    lens = jnp.asarray([50, 21], jnp.int32)
    outs = []
    for alloc in ("contiguous", "striped"):
        table = default_page_table(b, t // page, alloc)
        kp, vp = _pools_from_history(hist_k, hist_v, page, table)
        outs.append(np.asarray(paged_decode_attention(
            q, kp, vp, table, lens, mode="pallas_interpret")))
    np.testing.assert_array_equal(outs[0], outs[1])


# ---------------------------------------------------------------------------
# schedule: static page budget + analytic pages-touched counters
# ---------------------------------------------------------------------------
def test_decode_schedule_window_prunes_page_budget():
    sc = flash_decode_schedule(64, 16, q_len=1, window=20)
    assert sc.max_steps == 3                  # ceil(20/16)+1 ≪ 64
    assert flash_decode_schedule(64, 16).max_steps == 64
    # budget never exceeds the table width
    assert flash_decode_schedule(2, 16, window=4096).max_steps == 2


def test_decode_pages_touched_counters():
    sc = flash_decode_schedule(8, 16, q_len=1, window=None)
    # ceil(37/16)=3, ceil(5/16)=1, ceil(128/16)=8
    assert pages_touched([37, 5, 128], sc) == 3 + 1 + 8
    scw = flash_decode_schedule(8, 16, q_len=1, window=20)
    # windowed: at most ceil((1+19)/16)+1 = 3 pages per sequence
    assert pages_touched([37, 5, 128], scw) == 2 + 1 + 2


def test_decode_grid_steps_and_blocks_touched():
    """Launched grid steps against the page blocks that stream, by hand
    and at the served geometry."""
    sc = flash_decode_schedule(40, 8, group=2, kv_heads=2, head_dim=64)
    assert (sc.pages_per_block, sc.num_blocks) == (16, 3)   # 16+16+8
    assert grid_steps(sc, 3) == 3 * 1 * 3
    # 100 → 13 pages (1 block), 128 → 16 (1), 0 → page 0 (1),
    # 200 → 25 (2), 320 → 40 (3)
    assert blocks_touched([100, 128, 0], sc) == 1 + 1 + 1
    assert blocks_touched([200, 320], sc) == 2 + 3
    # a 2x16-row prefill chunk: each q block walks to its own horizon
    scp = flash_decode_schedule(40, 8, q_len=32, q_chunk=16, group=2,
                                kv_heads=2, head_dim=64)
    assert grid_steps(scp, 2) == 2 * 2 * scp.num_blocks
    # ctx 129: block 0 ends at token 112 (page 14), block 1 at 128
    # (page 16, the second block); ctx 40: pages 0-2 and 0-4
    assert blocks_touched([129, 40], scp) == (1 + 2) + (1 + 1)

    # qwen2.5-3B served: 64 rows, an 80-page table of 64-token pages,
    # 2 KV heads x 8 query heads x 128, bf16.  Before: a (B·KH, 1, 80)
    # grid of one (page, D) tile a step, 10,240 steps a layer
    cell = flash_decode_schedule(80, 64, group=8, kv_heads=2, head_dim=128)
    assert 8 <= cell.pages_per_block <= 16
    launched = grid_steps(cell, 64)
    assert launched <= 640 and 64 * 2 * 80 >= 16 * launched
    # ~44 live rows of ~950 tokens (15 pages) and 20 idle rows
    lens = [950] * 44 + [0] * 20
    assert pages_touched(lens, cell) == 44 * 15 + 20
    assert blocks_touched(lens, cell) == 44 + 20


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
def test_decode_block_fits_vmem(kv_dtype):
    """The page-block size is fitted to the VMEM budget: a decode step's
    8 q rows a head get a large block, a 128-row prefill q block (1,024
    rows a head) a smaller one, and both fit."""
    plan = functools.partial(flash_decode_schedule, 80, 64, group=8,
                             kv_heads=2, head_dim=128, kv_dtype=kv_dtype)
    decode, prefill = plan(), plan(q_len=256, q_chunk=128)
    for sc in (decode, prefill):
        assert sc.vmem_bytes <= VMEM_PLAN_BUDGET, sc
    assert decode.pages_per_block == MAX_PAGES_PER_BLOCK
    assert 1 <= prefill.pages_per_block < decode.pages_per_block


# ---------------------------------------------------------------------------
# cache layout invariants
# ---------------------------------------------------------------------------
def test_page_table_allocations_are_disjoint_and_complete():
    for alloc in ("contiguous", "striped"):
        table = np.asarray(default_page_table(3, 5, alloc))
        assert table.shape == (3, 5)
        assert len(set(table.flatten().tolist())) == 15
        assert table.min() == 0 and table.max() == 14


def test_init_cache_paged_shapes():
    cfg = get_smoke_config("qwen2_5_3b")
    cache = init_cache(cfg, 2, max_len=40,
                       config=CacheConfig(layout="paged", page_size=16))
    mp = 3                                    # ceil(40/16)
    assert cache["k_pages"].shape == (cfg.n_layers, 2 * mp, cfg.n_kv_heads,
                                      16, cfg.head_dim)
    assert cache["v_pages"].shape == cache["k_pages"].shape
    assert cache["page_table"].shape == (2, mp)
    assert cache["page_table"].dtype == jnp.int32
    assert cache["seq_lens"].shape == (2,)
    with pytest.raises(ValueError):
        init_cache(get_smoke_config("mamba2_370m"), 2, max_len=40,
                   config=CacheConfig(layout="paged"))


def test_cache_config_page_tiling(monkeypatch):
    """On a TPU a page must be whole sublane tiles of the pool dtype — 16
    rows for bf16, 32 for the int8 pools — and init_cache refuses any
    other before a kernel sees it; off the TPU any page size runs."""
    def cc(page, quant="none"):
        return CacheConfig(layout="paged", page_size=page, kv_quant=quant)

    cc(16).check_page_tiling(jnp.bfloat16, "tpu")
    cc(64, "int8").check_page_tiling(jnp.bfloat16, "tpu")
    cc(4).check_page_tiling(jnp.float32, "cpu")
    for page, quant, dtype, rows in ((8, "none", jnp.bfloat16, 16),
                                     (16, "int8", jnp.bfloat16, 32),
                                     (4, "none", jnp.float32, 8)):
        with pytest.raises(ValueError, match=f"multiple of {rows}"):
            cc(page, quant).check_page_tiling(dtype, "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="multiple of 16"):
        init_cache(get_smoke_config("qwen2_5_3b"), 2, max_len=32,
                   dtype=jnp.bfloat16, config=cc(8))


def test_cache_logical_axes_paged():
    from repro.serving.cache import cache_logical_axes
    cfg = get_smoke_config("qwen2_5_3b")
    axes = cache_logical_axes(cfg, layout="paged")
    assert set(axes) == {"k_pages", "v_pages", "page_table", "seq_lens"}
    assert len(axes["k_pages"]) == 5
    assert axes["seq_lens"] == ("batch",)
    # seq-split policy maps onto the page-pool dim
    axes_seq = cache_logical_axes(cfg, kv_shard="seq", layout="paged")
    assert axes_seq["k_pages"][1] == "kv_pages"
    axes_h = cache_logical_axes(cfg, kv_shard="heads", layout="paged")
    assert axes_h["k_pages"][2] == "kv_heads"


# ---------------------------------------------------------------------------
# engine: prefill → decode handoff, batched scan loop
# ---------------------------------------------------------------------------
def _engine_setup(arch="qwen2_5_3b", b=3, s_pad=10):
    cfg = get_smoke_config(arch).replace(quant_proj="none", dtype="float32")
    params = init_model(KEY, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s_pad), 0,
                              cfg.vocab_size)
    lens = jnp.asarray([s_pad, 4, 7][:b], jnp.int32)
    return cfg, params, toks, lens


def test_paged_engine_matches_dense_mixed_lengths():
    """Same mixed-length batch, both layouts: identical greedy tokens and
    matching prefill logits."""
    cfg, params, toks, lens = _engine_setup()
    b = toks.shape[0]
    outs, logits = [], []
    for layout, page in (("dense", None), ("paged", 4)):
        cc = (CacheConfig() if page is None else
              CacheConfig(layout="paged", page_size=page, alloc="striped"))
        cache = init_cache(cfg, b, max_len=20, dtype=jnp.float32, config=cc)
        nl, cache = prefill(params, cache, toks, lens, cfg)
        first = jnp.argmax(nl, -1)[:, None].astype(jnp.int32)
        start = lens if page is None else None
        out, cache = greedy_decode(params, cache, first, start, 4, cfg)
        outs.append(np.asarray(out))
        logits.append(np.asarray(nl))
        if page is not None:
            assert int(cache["seq_lens"][0]) == int(lens[0]) + 4
    np.testing.assert_allclose(logits[0], logits[1], atol=2e-4, rtol=2e-4)
    np.testing.assert_array_equal(outs[0], outs[1])


@pytest.mark.slow
def test_paged_engine_matches_per_sequence_loop():
    """The batched mixed-length paged path against B independent dense
    single-sequence decodes — the strictest end-to-end oracle."""
    cfg, params, toks, lens = _engine_setup(b=2, s_pad=8)
    cache = init_cache(cfg, 2, max_len=16, dtype=jnp.float32,
                       config=CacheConfig(layout="paged", page_size=4,
                                          alloc="striped"))
    nl, cache = prefill(params, cache, toks, lens, cfg)
    first = jnp.argmax(nl, -1)[:, None].astype(jnp.int32)
    out, _ = greedy_decode(params, cache, first, None, 3, cfg)

    for i in range(2):
        li = int(lens[i])
        cd = init_cache(cfg, 1, max_len=16, dtype=jnp.float32)
        for t in range(li):
            lg, cd = serve_step(params, cd, toks[i:i + 1, t:t + 1],
                                jnp.asarray(t, jnp.int32), cfg)
        cur = jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)
        seq = [int(cur[0, 0])]
        for j in range(3):
            lg, cd = serve_step(params, cd, cur,
                                jnp.asarray(li + j, jnp.int32), cfg)
            cur = jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)
            seq.append(int(cur[0, 0]))
        np.testing.assert_array_equal(np.asarray(out)[i], np.asarray(seq))


def test_scan_greedy_pins_python_loop():
    """The lax.scan serving loop reproduces the legacy step-by-step loop
    (dense layout, batch-synchronous positions)."""
    cfg, params, toks, _ = _engine_setup(b=2, s_pad=1)
    cache = init_cache(cfg, 2, max_len=12, dtype=jnp.float32)
    first = toks[:, :1]
    out, _ = greedy_decode(params, cache, first, 0, 4, cfg)

    cache = init_cache(cfg, 2, max_len=12, dtype=jnp.float32)
    tok, seq = first, [first]
    for t in range(4):
        lg, cache = serve_step(params, cache, tok,
                               jnp.asarray(t, jnp.int32), cfg)
        tok = jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)
        seq.append(tok)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(jnp.concatenate(seq, axis=1)))


def test_gemma2_local_global_paged_decode():
    """Sliding-window local layers (traced per-layer flag) + softcap on the
    paged path: per-step logits match the dense layout."""
    cfg, params, toks, lens = _engine_setup(arch="gemma2_27b", b=2, s_pad=6)
    cd = init_cache(cfg, 2, max_len=16, dtype=jnp.float32)
    cp = init_cache(cfg, 2, max_len=16, dtype=jnp.float32,
                    config=CacheConfig(layout="paged", page_size=4,
                                       alloc="striped"))
    nld, cd = prefill(params, cd, toks, lens, cfg)
    nlp, cp = prefill(params, cp, toks, lens, cfg)
    np.testing.assert_allclose(np.asarray(nld), np.asarray(nlp),
                               atol=2e-4, rtol=2e-4)
    tok = jnp.argmax(nlp, -1)[:, None].astype(jnp.int32)
    pos = lens
    for _ in range(2):
        lgd, cd = serve_step(params, cd, tok, pos, cfg)
        lgp, cp = serve_step(params, cp, tok, None, cfg)
        np.testing.assert_allclose(np.asarray(lgd), np.asarray(lgp),
                                   atol=2e-4, rtol=2e-4)
        tok = jnp.argmax(lgp[:, -1], -1)[:, None].astype(jnp.int32)
        pos = pos + 1


def test_serve_step_interpret_kernel_end_to_end(monkeypatch):
    """attn_impl routing: with Pallas (interpret) kernels live, the paged
    decode step lowers through the flash-decode kernel and matches ref."""
    cfg, params, toks, lens = _engine_setup(b=2, s_pad=6)
    caches = {}
    for mode in ("ref", "pallas_interpret"):
        monkeypatch.setenv("REPRO_KERNELS", mode)
        cache = init_cache(cfg, 2, max_len=16, dtype=jnp.float32,
                           config=CacheConfig(layout="paged", page_size=4))
        _, cache = prefill(params, cache, toks, lens, cfg)
        lg, _ = serve_step(params, cache, toks[:, :1], None, cfg)
        caches[mode] = np.asarray(lg)
    np.testing.assert_allclose(caches["ref"], caches["pallas_interpret"],
                               atol=2e-4, rtol=2e-4)


def test_serve_step_pos_none_requires_paged():
    cfg, params, toks, _ = _engine_setup(b=2, s_pad=1)
    cache = init_cache(cfg, 2, max_len=8)
    with pytest.raises(ValueError):
        serve_step(params, cache, toks[:, :1], None, cfg)
