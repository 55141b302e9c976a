"""Mesh-sharded serving: CacheConfig API, per-shard allocator, parity.

Four layers of coverage:

  * **CacheConfig API** — the legacy ``init_cache`` / ``Scheduler``
    keyword spelling builds a bitwise-identical cache through a
    ``DeprecationWarning`` shim; passing both spellings is a
    ``TypeError``; the KV-partitioning policy resolver picks ``heads``
    exactly when the KV heads divide the model axis.
  * **Per-shard allocator** — round-robin placement lands page ``j`` on
    shard ``j mod S``; admission gates on the *global minimum* of
    per-shard headroom (a request the total free count covers is still
    refused when one shard cannot supply its share — and the refusal is
    atomic); the scratch reservation keeps shard 0 one page short, a
    permanent imbalance these tests lean on.
  * **Sharded parity** (slow, subprocess — fake devices need XLA_FLAGS
    before jax import) — the same mixed-arrival scheduler trace on mesh
    sizes 1 / 2 / 4 produces identical greedy tokens per request; mesh 2
    exercises the tensor-parallel ``heads`` policy, mesh 4 (with 2 KV
    heads) the split-KV ``pages`` policy with the partial-softmax
    combine.
  * **Partitioning is real** — pool leaves carry non-replicated
    ``NamedSharding``s matching ``cache_shardings``, and the compiled
    decode HLO contains no pool-sized all-gather (the shard_map'd page
    walk keeps every pool access shard-local; only O(heads) partial
    softmax reductions cross the mesh).
"""
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.serving import allocator as al
from repro.serving.cache import CacheConfig, init_cache


class FakeMesh:
    """Duck-typed mesh (shape mapping only) for policy-resolution tests."""

    def __init__(self, **axes):
        self.shape = axes


# ---------------------------------------------------------------------------
# CacheConfig: legacy shim + policy resolution
# ---------------------------------------------------------------------------
def test_init_cache_legacy_kwargs_bitwise_roundtrip():
    cfg = get_smoke_config("qwen2_5_3b")
    new = init_cache(cfg, 3, max_len=64,
                     config=CacheConfig(layout="paged", page_size=8,
                                        alloc="dynamic", pool_pages=24,
                                        kv_quant="int8"))
    with pytest.warns(DeprecationWarning, match="deprecated"):
        old = init_cache(cfg, 3, max_len=64, layout="paged", page_size=8,
                         alloc="dynamic", pool_pages=24, kv_quant="int8")
    assert set(old) == set(new)
    for k in new:
        assert old[k].dtype == new[k].dtype, k
        np.testing.assert_array_equal(np.asarray(old[k]), np.asarray(new[k]))


def test_init_cache_rejects_both_spellings():
    cfg = get_smoke_config("qwen2_5_3b")
    with pytest.raises(TypeError, match="not both"):
        init_cache(cfg, 2, max_len=32, config=CacheConfig(layout="paged"),
                   layout="paged")


def test_scheduler_legacy_kwargs_shim():
    from repro.models.transformer import init_model
    from repro.serving.scheduler import Scheduler
    import jax
    cfg = get_smoke_config("qwen2_5_3b").replace(quant_proj="none",
                                                 dtype="float32")
    params = init_model(jax.random.PRNGKey(0), cfg)
    with pytest.warns(DeprecationWarning, match="deprecated"):
        sched = Scheduler(params, cfg, slots=2, max_len=32, page_size=4,
                          pool_pages=16)
    assert sched.config == CacheConfig(layout="paged", alloc="dynamic",
                                       page_size=4, pool_pages=16)
    with pytest.raises(TypeError, match="not both"):
        Scheduler(params, cfg, slots=2, max_len=32, page_size=4,
                  config=CacheConfig(layout="paged", alloc="dynamic"))
    with pytest.raises(ValueError, match="alloc='dynamic'"):
        Scheduler(params, cfg, slots=2, max_len=32,
                  config=CacheConfig(layout="paged", alloc="striped"))


def test_resolved_kv_shard_policy():
    kh = 2
    assert CacheConfig().resolved_kv_shard(kh) is None
    m2 = CacheConfig(mesh=FakeMesh(model=2))
    m4 = CacheConfig(mesh=FakeMesh(model=4))
    assert m2.resolved_kv_shard(kh) == "heads"      # 2 % 2 == 0
    assert m4.resolved_kv_shard(kh) == "pages"      # 2 % 4 != 0
    # forcing heads past divisibility is an error, not a silent fallback
    with pytest.raises(ValueError, match="divisible"):
        CacheConfig(mesh=FakeMesh(model=4),
                    kv_shard="heads").resolved_kv_shard(kh)
    assert CacheConfig(mesh=FakeMesh(model=2),
                       kv_shard="seq").resolved_kv_shard(kh) == "pages"
    with pytest.raises(ValueError, match="kv_shard"):
        CacheConfig(mesh=FakeMesh(model=2),
                    kv_shard="zigzag").resolved_kv_shard(kh)
    # allocator shard count follows the pool partitioning, not the mesh
    assert m2.shards(kh) == 1                       # heads: flat free list
    assert CacheConfig(layout="paged",
                       mesh=FakeMesh(model=4)).shards(kh) == 4


def test_pool_rounds_up_to_shard_multiple():
    cfg = get_smoke_config("qwen2_5_3b")
    cache = init_cache(cfg, 2, max_len=64,
                       config=CacheConfig(layout="paged", page_size=8,
                                          alloc="dynamic", pool_pages=13,
                                          pool_shards=4))
    assert cache["k_pages"].shape[1] == 16          # 13 → 16
    assert cache["alloc_free"].shape == (4, 4)
    assert cache["alloc_top"].shape == (4,)


# ---------------------------------------------------------------------------
# per-shard allocator: round-robin striping + global-min admission
# ---------------------------------------------------------------------------
def _shard_cache(pool=16, shards=4, batch=3, page=8):
    cfg = get_smoke_config("qwen2_5_3b")
    return init_cache(cfg, batch, max_len=page * pool,
                      config=CacheConfig(layout="paged", page_size=page,
                                         alloc="dynamic", pool_pages=pool,
                                         pool_shards=shards))


def test_round_robin_placement_across_shards():
    cache = _shard_cache()
    per = 4
    cache, ok = al.admit_sequence(cache, 0, 8 * 8)   # 8 pages over 4 shards
    assert bool(ok)
    row = np.asarray(cache["page_table"][0])[:8]
    # page j of the request comes from shard j mod S (global id // per)
    np.testing.assert_array_equal(row // per, np.arange(8) % 4)
    assert len(set(row.tolist())) == 8
    # shard 0 starts one short (scratch): tops are [3,4,4,4] fresh,
    # [1,2,2,2] after the grab
    np.testing.assert_array_equal(np.asarray(cache["alloc_top"]),
                                  [1, 2, 2, 2])


def test_global_min_admission_under_imbalance():
    """7 pages free in total, but shard 0 cannot cover its round-robin
    share of a 5-page request: refused, atomically.  The same pool admits
    4 pages (1 per shard) immediately after — the rule is per-shard
    headroom, not the global count."""
    cache = _shard_cache()
    cache, ok = al.admit_sequence(cache, 0, 8 * 8)
    assert bool(ok)
    assert al.pool_occupancy(cache) == (9, 16)       # 8 + scratch
    snap = {k: np.asarray(cache[k]) for k in al.ALLOC_KEYS}
    # 5 pages → need [2,1,1,1]; shard 0 has 1 free: refuse despite 7 free
    state = al.allocator_state(cache)
    assert not bool(al.can_admit(state, 5))
    cache, ok = al.admit_sequence(cache, 1, 5 * 8)
    assert not bool(ok)
    for k in al.ALLOC_KEYS:                          # atomic refusal
        np.testing.assert_array_equal(np.asarray(cache[k]), snap[k])
    cache, ok = al.admit_sequence(cache, 1, 4 * 8)   # 1 per shard: fits
    assert bool(ok)
    np.testing.assert_array_equal(np.asarray(cache["alloc_top"]),
                                  [0, 1, 1, 1])
    assert al.shard_occupancy(cache) == ((4, 4), (3, 4), (3, 4), (3, 4))
    # freeing both rows restores the fresh per-shard stacks exactly
    cache = al.free_sequence(cache, 0)
    cache = al.free_sequence(cache, 1)
    np.testing.assert_array_equal(np.asarray(cache["alloc_top"]),
                                  [3, 4, 4, 4])
    assert al.pool_occupancy(cache) == (1, 16)       # scratch only


def test_single_shard_reduces_to_flat_allocator():
    """shards=1 is bit-for-bit the old flat free list: ascending stack,
    scratch pinned, same ids handed out."""
    flat = al.init_allocator(10, shards=1)
    np.testing.assert_array_equal(np.asarray(flat["free"][0, :9]),
                                  np.arange(1, 10))
    assert int(flat["top"][0]) == 9
    state, row, ok = al.alloc_pages(flat, 3, 6)
    assert bool(ok)
    np.testing.assert_array_equal(np.asarray(row),
                                  [9, 8, 7, 0, 0, 0])   # top-down, scratch


# ---------------------------------------------------------------------------
# sharded decode parity (subprocess: fake devices before jax import)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_sharded_serving_parity_and_partitioning(cpu_subprocess):
    """Mesh sizes 1 / 2 / 4 over the same mixed-arrival trace: identical
    greedy tokens per request; pool leaves actually partitioned; no
    pool-sized all-gather in the compiled decode."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["REPRO_KERNELS"] = "ref"
        import re
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_serving_mesh
        from repro.models.transformer import init_model
        from repro.serving.cache import CacheConfig, cache_shardings
        from repro.serving.engine import _greedy_run
        from repro.serving.scheduler import Scheduler

        cfg = get_smoke_config("qwen2_5_3b").replace(quant_proj="none",
                                                     dtype="float32")
        params = init_model(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(3)
        base = rng.integers(0, cfg.vocab_size, 13)
        prompts = [rng.integers(0, cfg.vocab_size, 9), base.copy(),
                   np.concatenate([base[:11],
                                   rng.integers(0, cfg.vocab_size, 4)]),
                   rng.integers(0, cfg.vocab_size, 5)]
        budgets = [4, 5, 3, 4]

        def run(msize):
            mesh = make_serving_mesh(msize) if msize > 1 else None
            cc = CacheConfig(layout="paged", alloc="dynamic", page_size=4,
                             pool_pages=24, mesh=mesh)
            sched = Scheduler(params, cfg, slots=3, max_len=64, bucket=4,
                              config=cc)
            rids = [sched.submit(prompts[0], budgets[0]),
                    sched.submit(prompts[1], budgets[1])]
            sched.step()
            rids.append(sched.submit(prompts[2], budgets[2]))
            sched.step()
            rids.append(sched.submit(prompts[3], budgets[3]))
            out = sched.run(max_ticks=100)
            return [out[r] for r in rids], sched

        ref, _ = run(1)
        for msize in (2, 4):
            got, sched = run(msize)
            policy = sched.config.resolved_kv_shard(cfg.n_kv_heads)
            assert policy == {2: "heads", 4: "pages"}[msize], policy
            for a, b in zip(got, ref):
                np.testing.assert_array_equal(a, b)

            # the pool is ACTUALLY partitioned, as cache_shardings says
            want = cache_shardings(cfg, sched.cache, sched.config)
            for key in ("k_pages", "v_pages"):
                sh = sched.cache[key].sharding
                assert not sh.is_fully_replicated, (msize, key)
                assert sh.is_equivalent_to(want[key],
                                           sched.cache[key].ndim), key
            dim = 2 if policy == "heads" else 1     # (L, P, KVH, page, hd)
            assert want["k_pages"].spec[dim] == "model", want["k_pages"]
            if policy == "pages":
                assert sched.cache["alloc_free"].shape[0] == msize
                assert not sched.cache[
                    "alloc_top"].sharding.is_fully_replicated

            # no pool-sized all-gather in the decode HLO: the page walk
            # must stay shard-local (partial-softmax terms that cross the
            # mesh are O(B*KVH*hd), far below one pool layer)
            cache = jax.tree.map(jnp.copy, sched.cache)
            tok = jnp.zeros((3, 1), jnp.int32)
            hlo = _greedy_run.lower(
                params, cache, tok, jnp.asarray(0, jnp.int32), None, cfg,
                1, True, "ref", sched.config.mesh).compile().as_text()
            pool_layer = int(np.prod(cache["k_pages"].shape[1:]))
            gathered = []
            for m in re.finditer(
                    r"(\\w+)\\[([\\d,]*)\\][^=]*= \\w*all-gather", hlo):
                dims = m.group(2)
                n = int(np.prod([int(d) for d in dims.split(",")])
                        ) if dims else 1
                if n >= pool_layer:
                    gathered.append(m.group(0))
            assert not gathered, gathered[:3]
            print(f"MESH{msize}_OK")
        print("SHARDED_SERVING_OK")
    """)
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=600, **cpu_subprocess)
    assert "SHARDED_SERVING_OK" in res.stdout, \
        res.stdout[-2000:] + res.stderr[-3000:]


@pytest.mark.slow
def test_sharded_prefix_sharing_and_int8(cpu_subprocess):
    """The sharded pool composes with the rest of the serving stack:
    prefix-shared admissions and int8 page pools both decode identically
    to their single-device runs on a 4-way pages-split mesh."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["REPRO_KERNELS"] = "ref"
        import jax, numpy as np
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_serving_mesh
        from repro.models.transformer import init_model
        from repro.serving.cache import CacheConfig
        from repro.serving.scheduler import Scheduler

        cfg = get_smoke_config("qwen2_5_3b").replace(quant_proj="none",
                                                     dtype="float32")
        params = init_model(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(5)
        base = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
        prompts = [base,
                   np.concatenate([base[:6], [1, 2, 3]]).astype(np.int32),
                   rng.integers(0, cfg.vocab_size, 5).astype(np.int32)]

        def run(msize, kv_quant):
            mesh = make_serving_mesh(msize) if msize > 1 else None
            sched = Scheduler(
                params, cfg, slots=2, max_len=32, bucket=4,
                config=CacheConfig(layout="paged", alloc="dynamic",
                                   page_size=4, pool_pages=16,
                                   kv_quant=kv_quant, mesh=mesh))
            for p in prompts:
                sched.submit(p, 4)
            return sched.run(max_ticks=64)

        for kv_quant in ("none", "int8"):
            ref, got = run(1, kv_quant), run(4, kv_quant)
            assert set(ref) == set(got) == {0, 1, 2}
            for rid in ref:
                np.testing.assert_array_equal(ref[rid], got[rid]), (
                    kv_quant, rid)
        print("SHARDED_COMPOSE_OK")
    """)
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=600, **cpu_subprocess)
    assert "SHARDED_COMPOSE_OK" in res.stdout, \
        res.stdout[-2000:] + res.stderr[-3000:]
