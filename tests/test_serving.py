"""Continuous-batching serving core: allocator, prefix sharing, chunked
prefill, scheduler.

Four layers of coverage:

  * **Allocator invariants** — a property sweep (hypothesis via
    ``tests/_hypothesis_compat.py``) drives random admit/free/fork op
    sequences against a host-side mirror: refcounts match the mirror,
    the free stack and the referenced set partition the pool, live rows
    only reference live pages.
  * **Opacity under dynamic allocation** — decode through an
    allocator-churned table is *bitwise* identical to a freshly
    initialized contiguous table (invariant 3 extended to the dynamic
    allocator), and prefix-shared pages decode bitwise-identically to
    disjoint copies of the same pages (the relaxed "disjoint writable
    sets" invariant is invisible to the read path).
  * **Chunked paged prefill** — ``prefill(..., chunk=…)`` matches the
    one-pass prefill's ``next_logits`` for prompts beyond
    ``PAGED_FLASH_MAX_Q``, through both the jnp oracle and the
    multi-query-row interpret kernel; the kernel's q-block schedule has
    its own parity sweep vs the dense oracle.
  * **Scheduler** — mixed-arrival traces produce, per request, exactly
    the tokens an isolated ``prefill → greedy_decode`` run produces
    (including prefix-shared admissions); pages visibly recycle;
    ``greedy_decode`` still hits the jit cache across calls.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st
from repro.configs import get_smoke_config
from repro.kernels.flash_attention.decode import (flash_decode_schedule,
                                                 pages_touched)
from repro.kernels.flash_attention.ops import paged_decode_attention
from repro.models.transformer import init_model
from repro.serving import allocator as al
from repro.serving.cache import (CacheConfig, cache_logical_axes,
                                 default_page_table, init_cache)
from repro.serving.engine import _greedy_run, greedy_decode, prefill
from repro.serving.scheduler import Scheduler

RNG = np.random.default_rng(0)
KEY = jax.random.PRNGKey(0)


def _dyn_cache(batch=3, max_len=64, page=8, pool=None, arch="qwen2_5_3b"):
    cfg = get_smoke_config(arch)
    return init_cache(cfg, batch, max_len=max_len,
                      config=CacheConfig(layout="paged", page_size=page,
                                         alloc="dynamic", pool_pages=pool))


def _flat_alloc(cache):
    """(ref, top, free) flattened over the shard dim — the single-shard
    tests below reason about the pool globally; ``free`` is only the live
    stack entries (global page ids), concatenated shard by shard."""
    tops = np.asarray(cache["alloc_top"])
    ref = np.asarray(cache["alloc_ref"]).reshape(-1)
    free = np.concatenate([np.asarray(cache["alloc_free"])[s, :int(t)]
                           for s, t in enumerate(tops)])
    return ref, int(tops.sum()), free


# ---------------------------------------------------------------------------
# allocator: free-list + refcount invariants
# ---------------------------------------------------------------------------
def _check_invariants(cache, mirror_refs):
    """cache allocator state vs a host mirror {page: refcount}."""
    n = cache["alloc_ref"].size
    ref, top, free = _flat_alloc(cache)
    # refcounts match the mirror exactly (scratch page pinned at >= 1)
    want = np.zeros(n, np.int32)
    want[al.SCRATCH_PAGE] = 1
    for p, c in mirror_refs.items():
        want[p] += c
    np.testing.assert_array_equal(ref, want)
    # free stack and referenced pages partition the pool
    assert len(set(free.tolist())) == top, "free stack holds duplicates"
    assert set(free.tolist()).isdisjoint(np.flatnonzero(ref).tolist())
    assert top + int((ref > 0).sum()) == n


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_allocator_property_sweep(seed):
    """Random admit/free/fork sequences preserve the refcount + free-list
    invariants, mirrored by an independent host-side accounting."""
    rng = np.random.default_rng(seed)
    batch, page, pool = 4, 8, 24
    cache = _dyn_cache(batch=batch, page=page, pool=pool)
    live: dict[int, list[int]] = {}       # slot -> pages it references
    mirror: dict[int, int] = {}           # page -> refcount
    for _ in range(12):
        op = rng.integers(0, 3)
        if op == 0:                        # admit a free slot
            free_slots = [b for b in range(batch) if b not in live]
            if not free_slots:
                continue
            b = int(rng.choice(free_slots))
            n_tok = int(rng.integers(1, 5 * page))
            cache, ok = al.admit_sequence(cache, b, n_tok)
            need = -(-n_tok // page)
            free_now = pool - 1 - len(mirror)      # minus reserved scratch
            assert bool(ok) == (need <= free_now)
            if bool(ok):
                row = np.asarray(cache["page_table"][b])[:need]
                live[b] = row.tolist()
                for p in row.tolist():
                    mirror[p] = mirror.get(p, 0) + 1
        elif op == 1 and live:             # free a live slot
            b = int(rng.choice(list(live)))
            cache = al.free_sequence(cache, b)
            for p in live.pop(b):
                mirror[p] -= 1
                if mirror[p] == 0:
                    del mirror[p]
        elif op == 2 and live:             # fork off a live slot
            free_slots = [b for b in range(batch) if b not in live]
            if not free_slots:
                continue
            parent = int(rng.choice(list(live)))
            child = int(rng.choice(free_slots))
            par_cap = len(live[parent]) * page
            prefix = int(rng.integers(1, par_cap + 1))
            total_tok = int(rng.integers(prefix, 6 * page))
            cache, ok = al.fork_sequence(cache, parent, child, prefix,
                                         total_tok)
            if bool(ok):
                total = -(-total_tok // page)
                row = np.asarray(cache["page_table"][child])[:total]
                live[child] = row.tolist()
                for p in row.tolist():
                    mirror[p] = mirror.get(p, 0) + 1
                # shared prefix pages really are the parent's
                full = prefix // page
                np.testing.assert_array_equal(
                    row[:full], np.asarray(live[parent])[:full])
        _check_invariants(cache, mirror)


def test_allocator_admission_control():
    """A request the free list cannot cover is rejected atomically."""
    cache = _dyn_cache(batch=3, page=8, pool=10)   # 9 usable pages
    cache, ok = al.admit_sequence(cache, 0, 40)    # 5 pages
    assert bool(ok) and al.pool_occupancy(cache) == (6, 10)
    snap = jax.tree.map(np.asarray, {k: cache[k] for k in al.ALLOC_KEYS})
    cache, ok = al.admit_sequence(cache, 1, 48)    # 6 pages > 4 free
    assert not bool(ok)
    for k in al.ALLOC_KEYS:
        np.testing.assert_array_equal(np.asarray(cache[k]), snap[k])
    cache, ok = al.admit_sequence(cache, 1, 30)    # 4 pages: exact fit
    assert bool(ok) and al.pool_occupancy(cache) == (10, 10)
    # retiring slot 0 makes room again
    cache = al.free_sequence(cache, 0)
    cache, ok = al.admit_sequence(cache, 2, 40)
    assert bool(ok)


def test_refcount_shared_page_survives_parent_free():
    cache = _dyn_cache(batch=3, page=8, pool=16)
    cache, _ = al.admit_sequence(cache, 0, 24)          # 3 pages
    cache, ok = al.fork_sequence(cache, 0, 1, 16, 32)   # share 2 full pages
    assert bool(ok)
    shared = np.asarray(cache["page_table"][0])[:2]
    np.testing.assert_array_equal(np.asarray(cache["page_table"][1])[:2],
                                  shared)
    ref, _, _ = _flat_alloc(cache)
    assert all(int(ref[p]) == 2 for p in shared)
    cache = al.free_sequence(cache, 0)
    # still referenced by the child: not recycled
    ref, _, free = _flat_alloc(cache)
    assert all(int(ref[p]) == 1 for p in shared)
    assert set(shared.tolist()).isdisjoint(free.tolist())
    cache = al.free_sequence(cache, 1)
    assert al.pool_occupancy(cache) == (1, 16)          # scratch only


# ---------------------------------------------------------------------------
# opacity: dynamic tables and shared pages are invisible to the read path
# ---------------------------------------------------------------------------
def _scatter_history(pools_shape, table_row, hist, page):
    """Scatter a (T, KH, D) history into a head-major (P, KH, page, D)
    pool along ``table_row``."""
    kp = np.zeros(pools_shape, hist.dtype)
    for j in range(hist.shape[0] // page):
        kp[int(table_row[j])] = hist[j * page:(j + 1) * page].swapaxes(0, 1)
    return kp


def test_dynamic_table_bitwise_matches_contiguous():
    """Decode through an allocator-churned page table is bitwise equal to
    a freshly initialized contiguous table (invariant 3, dynamically)."""
    t, kh, d, page = 64, 2, 64, 8
    cache = _dyn_cache(batch=3, max_len=t, page=page, pool=3 * t // page + 1)
    # churn: admit/free/admit so the surviving row is scrambled
    cache, _ = al.admit_sequence(cache, 0, 24)
    cache, _ = al.admit_sequence(cache, 1, 40)
    cache = al.free_sequence(cache, 0)
    cache, _ = al.admit_sequence(cache, 2, t)       # the row under test
    row = np.asarray(cache["page_table"][2])
    assert sorted(row[: t // page]) != row[: t // page].tolist()

    hist_k = RNG.normal(size=(t, kh, d)).astype(np.float32)
    hist_v = RNG.normal(size=(t, kh, d)).astype(np.float32)
    q = jnp.asarray(RNG.normal(size=(1, 1, 4, d)).astype(np.float32))
    lens = jnp.asarray([50], jnp.int32)
    pool_shape = (int(cache["k_pages"].shape[1]), kh, page, d)

    outs = []
    for table in (row[None], np.asarray(default_page_table(1, t // page))):
        kp = _scatter_history(pool_shape, table[0], hist_k, page)
        vp = _scatter_history(pool_shape, table[0], hist_v, page)
        outs.append(np.asarray(paged_decode_attention(
            q, jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table, jnp.int32), lens,
            mode="pallas_interpret")))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_prefix_shared_decode_bitwise_matches_disjoint():
    """Two sequences sharing a k-page prefix decode bitwise-identically
    to the same sequences with disjoint page copies (``fork_sequence``
    with ``copy=True`` is the disjoint twin)."""
    cfg = get_smoke_config("qwen2_5_3b").replace(quant_proj="none",
                                                 dtype="float32")
    params = init_model(KEY, cfg)
    page, prefix, total = 4, 10, 14
    prompt = np.asarray(RNG.integers(0, cfg.vocab_size, total), np.int32)
    alt_tail = np.asarray(RNG.integers(0, cfg.vocab_size, total - prefix),
                          np.int32)
    prompt2 = np.concatenate([prompt[:prefix], alt_tail])

    outs = []
    for copy in (False, True):
        cache = _dyn_cache(batch=2, max_len=32, page=page, pool=20)
        cache, ok = al.admit_sequence(cache, 0, total + 6)
        assert bool(ok)
        view = dict(cache)
        view["page_table"] = cache["page_table"][0:1]
        view["seq_lens"] = cache["seq_lens"][0:1]
        nl0, view = prefill(params, view, jnp.asarray(prompt[None]),
                            jnp.asarray([total]), cfg)
        cache["k_pages"], cache["v_pages"] = view["k_pages"], view["v_pages"]
        cache["seq_lens"] = cache["seq_lens"].at[0].set(view["seq_lens"][0])
        cache, ok = al.fork_sequence(cache, 0, 1, prefix, total + 6,
                                     copy=copy)
        assert bool(ok)
        if copy:    # truly disjoint: no physical page appears in both rows
            a = set(np.asarray(cache["page_table"][0]).tolist())
            b = set(np.asarray(cache["page_table"][1]).tolist())
            assert a & b <= {al.SCRATCH_PAGE}
        view = dict(cache)
        view["page_table"] = cache["page_table"][1:2]
        view["seq_lens"] = cache["seq_lens"][1:2]
        nl1, view = prefill(params, view, jnp.asarray(prompt2[None, prefix:]),
                            jnp.asarray([total]), cfg, start_pos=prefix)
        cache["k_pages"], cache["v_pages"] = view["k_pages"], view["v_pages"]
        cache["seq_lens"] = cache["seq_lens"].at[1].set(view["seq_lens"][0])

        first = jnp.argmax(jnp.concatenate([nl0, nl1]), -1
                           )[:, None].astype(jnp.int32)
        toks, cache = greedy_decode(params, cache, first, None, 4, cfg)
        outs.append((np.asarray(toks), np.asarray(cache["k_pages"]
                                                  [0, np.asarray(
                                                      cache["page_table"][1])])))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])   # tokens bitwise
    np.testing.assert_array_equal(outs[0][1], outs[1][1])   # child KV bitwise


# ---------------------------------------------------------------------------
# chunked paged prefill
# ---------------------------------------------------------------------------
def test_chunked_prefill_matches_one_pass():
    """Chunked prefill == one-pass next_logits for prompts beyond
    PAGED_FLASH_MAX_Q, and the subsequent decodes agree token-for-token."""
    from repro.models.attention import PAGED_FLASH_MAX_Q
    cfg = get_smoke_config("qwen2_5_3b").replace(quant_proj="none",
                                                 dtype="float32")
    params = init_model(KEY, cfg)
    b, s_pad = 3, 26
    assert s_pad > PAGED_FLASH_MAX_Q
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s_pad), 0,
                              cfg.vocab_size)
    lens = jnp.asarray([26, 11, 19], jnp.int32)
    results = {}
    for label, chunk in (("onepass", None), ("chunk7", 7), ("chunk8", 8)):
        cache = init_cache(cfg, b, max_len=40, dtype=jnp.float32,
                           config=CacheConfig(layout="paged", page_size=4,
                                              alloc="striped"))
        nl, cache = prefill(params, cache, toks, lens, cfg, chunk=chunk)
        first = jnp.argmax(nl, -1)[:, None].astype(jnp.int32)
        out, _ = greedy_decode(params, cache, first, None, 3, cfg)
        results[label] = (np.asarray(nl), np.asarray(out))
    for label in ("chunk7", "chunk8"):
        np.testing.assert_allclose(results["onepass"][0], results[label][0],
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_array_equal(results["onepass"][1],
                                      results[label][1])


def test_chunked_prefill_interpret_kernel(monkeypatch):
    """The multi-query-row paged kernel (q blocks over a prompt chunk)
    matches the jnp oracle end-to-end through prefill."""
    from repro.models import attention
    cfg = get_smoke_config("qwen2_5_3b").replace(quant_proj="none",
                                                 dtype="float32")
    params = init_model(KEY, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 26), 0,
                              cfg.vocab_size)
    lens = jnp.asarray([26, 13], jnp.int32)
    nls = {}
    for mode in ("ref", "pallas_interpret"):
        monkeypatch.setenv("REPRO_KERNELS", mode)
        # q_chunk 8 < chunk 13 forces a genuine multi-block grid
        monkeypatch.setattr(attention, "PAGED_PREFILL_CHUNK_Q", 8)
        cache = init_cache(cfg, 2, max_len=40, dtype=jnp.float32,
                           config=CacheConfig(layout="paged", page_size=4))
        nls[mode], _ = prefill(params, cache, toks, lens, cfg, chunk=13)
    np.testing.assert_allclose(np.asarray(nls["ref"]),
                               np.asarray(nls["pallas_interpret"]),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.slow
@pytest.mark.parametrize("qs,q_chunk,window,lens", [
    (32, 8, None, [64, 128]),
    (27, 8, None, [60, 128]),      # partial q chunk
    (32, 8, 24, [64, 100]),
    (32, 16, 24, [64, 100]),
])
def test_multi_q_block_kernel_parity(qs, q_chunk, window, lens):
    """q-block schedule sweep: kernel vs dense oracle at prefill widths."""
    b, t, h, kh, d, page = 2, 128, 4, 2, 64, 16
    table = default_page_table(b, t // page, "striped")
    hk = RNG.normal(size=(b, t, kh, d)).astype(np.float32)
    hv = RNG.normal(size=(b, t, kh, d)).astype(np.float32)
    pool = np.zeros((b * t // page, kh, page, d), np.float32)  # head-major
    kp, vp = pool.copy(), pool.copy()
    for bb in range(b):
        for j in range(t // page):
            rows = slice(j * page, (j + 1) * page)
            kp[int(table[bb, j])] = hk[bb, rows].swapaxes(0, 1)
            vp[int(table[bb, j])] = hv[bb, rows].swapaxes(0, 1)
    q = jnp.asarray(RNG.normal(size=(b, qs, h, d)).astype(np.float32))
    args = (q, jnp.asarray(kp), jnp.asarray(vp), table,
            jnp.asarray(lens, jnp.int32))
    out = paged_decode_attention(*args, window=window, q_chunk=q_chunk,
                                 mode="pallas_interpret")
    ref = paged_decode_attention(*args, window=window, mode="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-6, rtol=1e-5)


def test_decode_schedule_q_blocks_and_counters():
    sc = flash_decode_schedule(8, 16, q_len=32, q_chunk=8, window=20)
    assert sc.num_q_blocks == 4
    assert sc.max_steps == 3                  # ceil((8+19)/16)+1
    # block i of a 64-ctx prefill walks only pages under its own horizon
    sc_g = flash_decode_schedule(8, 16, q_len=64, q_chunk=16)
    # blocks end at ctx 16,32,48,64 → pages 1,2,3,4
    assert pages_touched([64], sc_g) == 1 + 2 + 3 + 4
    # decode special case unchanged
    assert flash_decode_schedule(64, 16, window=20).max_steps == 3
    assert pages_touched([37, 5, 128], flash_decode_schedule(8, 16)) == 12


# ---------------------------------------------------------------------------
# engine regressions
# ---------------------------------------------------------------------------
def test_prefill_capacity_hybrid_cache():
    """Regression: the capacity check must read shared_k for hybrid
    caches (an over-long prompt used to scatter past S_max silently)."""
    cfg = get_smoke_config("zamba2_7b").replace(quant_proj="none",
                                                dtype="float32")
    params = init_model(KEY, cfg)
    cache = init_cache(cfg, 2, max_len=8, dtype=jnp.float32)
    assert "shared_k" in cache and "k" not in cache
    toks = jax.random.randint(KEY, (2, 12), 0, cfg.vocab_size)
    with pytest.raises(ValueError, match="capacity"):
        prefill(params, cache, toks, jnp.asarray([12, 12]), cfg)
    # pure-SSM caches have no positional capacity to cap against
    from repro.serving.engine import cache_capacity
    mcfg = get_smoke_config("mamba2_370m")
    assert cache_capacity(init_cache(mcfg, 2, max_len=4)) is None
    assert cache_capacity(cache) == 8


def test_init_cache_dynamic_and_axes():
    cache = _dyn_cache(batch=2, max_len=40, page=16, pool=7)
    assert cache["k_pages"].shape[1] == 7
    assert np.asarray(cache["page_table"]).max() == al.SCRATCH_PAGE
    assert set(al.ALLOC_KEYS) <= set(cache)
    cfg = get_smoke_config("qwen2_5_3b")
    axes = cache_logical_axes(cfg, layout="paged", dynamic=True)
    assert axes["alloc_held"] == ("batch",)
    # free stacks / refcounts shard with the pool slabs they manage
    assert axes["alloc_free"] == ("kv_pages", None)
    assert axes["alloc_top"] == ("kv_pages",)
    # static tables cannot oversubscribe the pool
    with pytest.raises(ValueError, match="dynamic"):
        init_cache(cfg, 2, max_len=40,
                   config=CacheConfig(layout="paged", page_size=16,
                                      pool_pages=3))


# ---------------------------------------------------------------------------
# scheduler: continuous batching vs isolated serving
# ---------------------------------------------------------------------------
def _standalone(params, cfg, prompt, n_new):
    cache = init_cache(cfg, 1, max_len=64, dtype=jnp.float32,
                       config=CacheConfig(layout="paged", page_size=4,
                                          alloc="striped"))
    nl, cache = prefill(params, cache, jnp.asarray(prompt[None]),
                        jnp.asarray([len(prompt)], jnp.int32), cfg)
    first = jnp.argmax(nl, -1)[:, None].astype(jnp.int32)
    if n_new == 1:
        return np.asarray(first)[0]
    out, _ = greedy_decode(params, cache, first, None, n_new - 1, cfg)
    return np.asarray(out)[0]


@pytest.mark.slow
def test_scheduler_matches_isolated_requests():
    """Mixed-arrival continuous batching returns, per request, exactly
    the isolated prefill → greedy_decode tokens — with prefix-shared
    admissions in the mix and pages recycling through the pool."""
    cfg = get_smoke_config("qwen2_5_3b").replace(quant_proj="none",
                                                 dtype="float32")
    params = init_model(KEY, cfg)
    rng = np.random.default_rng(3)
    base = rng.integers(0, cfg.vocab_size, 13)
    prompts = [
        rng.integers(0, cfg.vocab_size, 9),
        base.copy(),
        np.concatenate([base[:11], rng.integers(0, cfg.vocab_size, 4)]),
        rng.integers(0, cfg.vocab_size, 5),
    ]
    budgets = [4, 5, 3, 4]
    sched = Scheduler(params, cfg, slots=3, max_len=64, bucket=4,
                      config=CacheConfig(layout="paged", alloc="dynamic",
                                         page_size=4, pool_pages=24))
    rids = [sched.submit(prompts[0], budgets[0]),
            sched.submit(prompts[1], budgets[1])]
    sched.step()                                  # arrivals mid-stream
    rids.append(sched.submit(prompts[2], budgets[2]))
    sched.step()
    rids.append(sched.submit(prompts[3], budgets[3]))
    out = sched.run(max_ticks=100)
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(
            out[rid], _standalone(params, cfg, prompts[i], budgets[i]))
    # every page recycled at drain: only the scratch page is held
    occ = sched.pool_occupancy()
    assert (occ.used, occ.total) == (1, 24)
    assert sum(u for u, _ in occ.per_shard) == occ.used
    assert max(sched.occupancy_log) > 1


def test_scheduler_admission_waits_for_pages():
    """With a pool sized for ~one request, the second request is admitted
    only after the first retires — and still decodes correctly."""
    cfg = get_smoke_config("qwen2_5_3b").replace(quant_proj="none",
                                                 dtype="float32")
    params = init_model(KEY, cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, 8) for _ in range(2)]
    sched = Scheduler(params, cfg, slots=2, max_len=32, bucket=4,
                      share_prefix=False,
                      config=CacheConfig(layout="paged", alloc="dynamic",
                                         page_size=4, pool_pages=5))
    r0 = sched.submit(prompts[0], 3)     # needs 3 pages of the 4 usable
    r1 = sched.submit(prompts[1], 3)
    sched.step()
    assert sched.n_active == 1 and len(sched.queue) == 1
    out = sched.run(max_ticks=50)
    np.testing.assert_array_equal(out[r0],
                                  _standalone(params, cfg, prompts[0], 3))
    np.testing.assert_array_equal(out[r1],
                                  _standalone(params, cfg, prompts[1], 3))


def test_scheduler_rejects_impossible_request():
    """A request that could never fit the per-sequence table is refused
    at submit time (mid-tick it would wedge the queue head forever)."""
    cfg = get_smoke_config("qwen2_5_3b").replace(quant_proj="none",
                                                 dtype="float32")
    params = init_model(KEY, cfg)
    sched = Scheduler(params, cfg, slots=2, max_len=32,
                      config=CacheConfig(layout="paged", alloc="dynamic",
                                         page_size=8))
    with pytest.raises(ValueError, match="pages"):
        sched.submit(np.arange(10, dtype=np.int32), max_new_tokens=40)
    assert not sched.queue


# ---------------------------------------------------------------------------
# sequence-state registry: SSM / hybrid / MoE families through one loop
# ---------------------------------------------------------------------------
def test_ssm_prefill_matches_stepwise():
    """Batched padded prefill-commit advances the SSM recurrence exactly
    like feeding the prompt token by token (the decode recurrence is the
    ground truth), and right-padding is invisible: mixed-length rows in
    one padded batch continue bitwise like isolated exact-width runs."""
    from repro.serving.engine import serve_step
    cfg = get_smoke_config("mamba2_370m").replace(dtype="float32")
    params = init_model(KEY, cfg)
    rng = np.random.default_rng(7)
    lens = [7, 13, 4]
    b, s_pad = len(lens), 16
    prompts = np.zeros((b, s_pad), np.int32)
    for i, n in enumerate(lens):
        prompts[i, :n] = rng.integers(3, cfg.vocab_size, n)

    # ground truth per row: batch-1, token-by-token, exact width
    refs = []
    for i, n in enumerate(lens):
        cache = init_cache(cfg, 1, 32, dtype=jnp.float32)
        for t in range(n):
            lg, cache = serve_step(params, cache,
                                   jnp.asarray(prompts[i:i + 1, t:t + 1]),
                                   jnp.full((1,), t, jnp.int32), cfg)
        toks = [int(jnp.argmax(lg[0, -1]))]
        for _ in range(3):
            lg, cache = serve_step(params, cache,
                                   jnp.asarray([[toks[-1]]], jnp.int32),
                                   cache["seq_lens"], cfg)
            toks.append(int(jnp.argmax(lg[0, -1])))
        refs.append(toks)

    # one padded batch through prefill-commit, then batched decode
    for chunk in (None, 8):     # chunked prefill-commit must agree too
        cache = init_cache(cfg, b, 32, dtype=jnp.float32)
        nl, cache = prefill(params, cache, jnp.asarray(prompts),
                            jnp.asarray(lens, np.int32), cfg, chunk=chunk)
        np.testing.assert_array_equal(np.asarray(cache["seq_lens"]), lens)
        first = jnp.argmax(nl, -1)[:, None].astype(jnp.int32)
        out, _ = greedy_decode(params, cache, first, None, 3, cfg)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(refs))


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["mamba2_370m", "zamba2_7b",
                                  "granite_moe_3b_a800m"])
def test_scheduler_cross_family_matches_isolated(arch):
    """The acceptance bar of the state registry: a mixed-arrival trace
    through the *same* admit → step → retire loop produces, per request,
    exactly the isolated prefill → greedy_decode tokens — for pure SSM
    (slot state), hybrid (slots + shared KV), and MoE (paged KV with
    S=1 expert dispatch)."""
    cfg = get_smoke_config(arch).replace(dtype="float32")
    params = init_model(KEY, cfg)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(3, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9, 3, 12)]
    budgets = [4, 6, 3, 5]

    sched = Scheduler(params, cfg, slots=2, max_len=64, bucket=8,
                      dtype=jnp.float32)
    rids = [sched.submit(prompts[0], budgets[0]),
            sched.submit(prompts[1], budgets[1])]
    sched.step()                                  # arrivals mid-stream
    rids.append(sched.submit(prompts[2], budgets[2]))
    rids.append(sched.submit(prompts[3], budgets[3]))
    out = sched.run(max_ticks=200)

    for rid, p, m in zip(rids, prompts, budgets):
        if cfg.family in ("ssm", "hybrid"):
            config = None
            cache = init_cache(cfg, 1, max_len=64, dtype=jnp.float32)
        else:
            config = CacheConfig(layout="paged", alloc="dynamic",
                                 page_size=16)
            cache = init_cache(cfg, 1, max_len=64, dtype=jnp.float32,
                               config=config)
            cache, ok = al.admit_sequence(cache, 0, p.size + m)
            assert bool(ok)
        padded = np.pad(p, (0, -p.size % 8))     # the scheduler's bucket
        nl, cache = prefill(params, cache, jnp.asarray(padded[None]),
                            jnp.asarray([p.size], jnp.int32), cfg,
                            config=config)
        first = jnp.argmax(nl, -1)[:, None].astype(jnp.int32)
        toks, _ = greedy_decode(params, cache, first, None, m - 1, cfg,
                                config=config)
        np.testing.assert_array_equal(out[rid], np.asarray(toks)[0])
    # request event log covers every request with one tick per token
    for rid, m in zip(rids, budgets):
        log = sched.request_log[rid]
        assert log["submitted"] <= log["admitted"]
        assert len(log["token_ticks"]) == m


def test_scheduler_slot_admission_ssm_and_hybrid():
    """Admission control for slot-state families, through the Scheduler:
    hybrid capacity is shared_k's S_max (token-worded rejection at
    submit); pure SSM has no positional bound (huge budgets admit);
    slot starvation queues requests until a retire frees a row."""
    cfg = get_smoke_config("zamba2_7b").replace(dtype="float32")
    params = init_model(KEY, cfg)
    sched = Scheduler(params, cfg, slots=2, max_len=32, bucket=8,
                      dtype=jnp.float32)
    with pytest.raises(ValueError, match="tokens"):
        sched.submit(np.arange(3, 13, dtype=np.int32), max_new_tokens=40)
    assert not sched.queue

    # pure SSM: a budget far past any attention cache's S_max is fine
    mcfg = get_smoke_config("mamba2_370m").replace(dtype="float32")
    mparams = init_model(KEY, mcfg)
    msched = Scheduler(mparams, mcfg, slots=2, max_len=32, bucket=8,
                       dtype=jnp.float32)
    rng = np.random.default_rng(2)
    rids = [msched.submit(rng.integers(3, mcfg.vocab_size, 4), 3)
            for _ in range(3)]
    msched.step()
    # starved slots: 2 live, the third queued until someone retires
    assert msched.n_active == 2 and len(msched.queue) == 1
    occ = msched.pool_occupancy()
    assert (occ.used, occ.total) == (2, 2)      # slot units, not pages
    out = msched.run(max_ticks=60)
    assert set(out) == set(rids)
    assert all(len(v) == 3 for v in out.values())
    assert msched.pool_occupancy().used == 0    # every slot recycled


def test_state_handler_free_clears_slot_state():
    """A retired SSM slot must not leak its recurrence into the next
    occupant: handler.free zeroes SLOT_STATE_KEYS and the length."""
    from repro.serving.state import SLOT_STATE_KEYS, state_handler
    cfg = get_smoke_config("zamba2_7b")
    cache = init_cache(cfg, 2, max_len=16)
    handler = state_handler(cfg)
    cache["ssm_h"] = cache["ssm_h"] + 1.0       # fake a used slot
    cache["conv_x"] = cache["conv_x"] + 1.0
    cache["seq_lens"] = jnp.asarray([5, 7], jnp.int32)
    cache = handler.free(cache, 0)
    for k in SLOT_STATE_KEYS:
        assert float(jnp.abs(cache[k][:, 0]).max()) == 0.0
    assert float(jnp.abs(cache["ssm_h"][:, 1]).min()) == 1.0   # row 1 intact
    np.testing.assert_array_equal(np.asarray(cache["seq_lens"]), [0, 7])


def test_greedy_decode_hits_jit_cache():
    """The scheduler refactor must not cost greedy_decode its jit cache:
    a second identically-shaped call adds no new trace."""
    cfg = get_smoke_config("qwen2_5_3b").replace(quant_proj="none",
                                                 dtype="float32")
    params = init_model(KEY, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                              cfg.vocab_size)
    lens = jnp.asarray([6, 4], jnp.int32)

    def one_round():
        cache = init_cache(cfg, 2, max_len=16, dtype=jnp.float32,
                           config=CacheConfig(layout="paged", page_size=4))
        nl, cache = prefill(params, cache, toks, lens, cfg)
        first = jnp.argmax(nl, -1)[:, None].astype(jnp.int32)
        greedy_decode(params, cache, first, None, 2, cfg)

    one_round()
    size = _greedy_run._cache_size()
    one_round()
    assert _greedy_run._cache_size() == size, "greedy_decode re-traced"
