"""Decode-state (KV / SSM) cache: construction, ``CacheConfig``, sharding.

Two attention-cache layouts behind one ``init_cache`` API (see
``docs/DESIGN.md`` §1–2 for the full serving architecture):

**dense** (seed layout) — one rectangular buffer per tensor:
  attention archs:  k/v (L, B, S_max, KVH, hd)
  hybrid (zamba2):  ssm_h (L,B,H,P,N) f32, conv_* tails, plus
                    shared_k/v (A, B, S_max, KVH, hd) for the A application
                    sites of the parameter-shared block
  ssm (mamba2):     ssm state + conv tails only — O(1) in context length.
  Both SSM families also carry seq_lens (B,) int32 — per-slot committed
  tokens, same currency as the paged layout (serving/state.py keys slot
  admission and occupancy off it).

**paged** — fixed-size KV pages in a shared pool plus per-sequence page
tables (attention families only; the SSM state is already O(1)):
  k_pages/v_pages  (L, n_pages, KVH, page_size, hd) — head-major, so one
                   page of one head is a (page_size, hd) tile, the block
                   the paged flash kernel streams
  k_scales/v_scales(L, n_pages, KVH, page_size) f32 — ``kv_quant="int8"``
                   only: per-(kv-head, page-slot) symmetric absmax scales
                   for the int8 pools; they ride the *same* page table,
                   so everything that moves pages (CoW, prefix sharing)
                   moves their scale rows with them
  page_table       (B, max_pages) int32 — physical page id of logical page
                   j of sequence b; rows' *writable* page sets are disjoint
  seq_lens         (B,) int32 — tokens currently committed per sequence
  alloc_*          (``alloc="dynamic"`` only) shard-local free-list
                   allocator state — see ``serving/allocator.py``

Page-table invariants (``docs/DESIGN.md`` §2): entries are valid pool
indices; distinct sequences never *write* the same physical page (a
read-only shared prefix page may appear in several rows while its
refcount is tracked by the allocator); token position ``p`` of sequence
``b``, head ``h`` lives at ``(page_table[b, p // page_size], h,
p % page_size)``; only the first ``seq_lens[b]`` positions hold
committed data (later slots may hold prefill-padding garbage that decode
masks until it overwrites them).

All construction knobs live in the frozen ``CacheConfig`` dataclass —
layout/page/allocator choices plus the mesh and KV-sharding policy.  The
pre-PR-7 keyword sprawl (``init_cache(layout=, page_size=, alloc=,
pool_pages=, kv_quant=)``) survives as a thin shim that builds the same
``CacheConfig`` and emits a ``DeprecationWarning``.

Sharding (``docs/DESIGN.md`` §3): under ``CacheConfig(mesh=...)`` the
cache comes back already partitioned (``jax.device_put`` with
``NamedSharding`` per leaf).  KV heads go to ``model`` when they divide
its extent (tensor-parallel decode); otherwise the paged pool's **page
dim** (or the dense cache's sequence dim) takes ``model`` — split-KV
decoding with shard-local page walks and a partial-softmax combine
(``models/attention.py``).  The allocator state shards exactly like the
pool it manages.  ``cache_logical_axes`` encodes the per-array choice;
``cache_shardings`` resolves it to ``NamedSharding``s.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.core.tiling import ceil_div, sublane_rows
from repro.launch.sharding import DEFAULT_LOGICAL_RULES, tree_specs
from repro.models.config import ModelConfig

DEFAULT_PAGE_SIZE = 64

# every per-page array of the paged layout: whatever copies / forks /
# scatters physical pages must treat these together (scale rows travel
# with their int8 pages — docs/DESIGN.md §2)
PAGE_STATE_KEYS = ("k_pages", "v_pages", "k_scales", "v_scales")

# Serving restricts the paged pool's page dim to the `model` axis (the
# generic kv_pages chain also offers `data`/`pod`): the shard-local
# allocator and the shard_map'd split-KV decode both need ONE known axis
# to size their shards and run their collectives over.
SERVING_RULES: dict[str, tuple] = dict(DEFAULT_LOGICAL_RULES,
                                       kv_pages=("model",))


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    """Every knob of decode-cache construction in one frozen value.

    Layout knobs (pre-PR-7 ``init_cache`` keywords, same semantics):
      layout:     ``"dense"`` | ``"paged"``.
      page_size:  tokens per KV page (paged only).
      alloc:      ``"contiguous"`` / ``"striped"`` static tables, or
                  ``"dynamic"`` — embedded free-list allocator.
      pool_pages: physical pool size (paged; default
                  ``batch * ceil(max_len / page_size)``, rounded up to a
                  multiple of the pool shard count).
      kv_quant:   ``"none"`` | ``"int8"`` (int8 pools + f32 scale rows).

    Sharding knobs (new in PR 7):
      mesh:       a ``jax.sharding.Mesh`` (or None).  When set,
                  ``init_cache`` returns an already-partitioned pytree
                  and the serving engine activates the sharding context
                  (tensor-parallel / split-KV decode) around every
                  model call.
      kv_shard:   ``"auto"`` — KV heads to ``model`` when divisible,
                  else the page-pool (or dense seq) dim; ``"heads"`` /
                  ``"pages"`` (alias ``"seq"``) force one policy.
      pool_shards: override the allocator shard count without a mesh
                  (unit-testing the per-shard free lists); defaults to
                  the model-axis extent under the pages policy, else 1.
    """
    layout: str = "dense"
    page_size: int = DEFAULT_PAGE_SIZE
    alloc: str = "contiguous"
    pool_pages: int | None = None
    kv_quant: str = "none"
    mesh: Any = None
    kv_shard: str = "auto"
    pool_shards: int | None = None

    def model_size(self) -> int:
        """Extent of the mesh's ``model`` axis (1 without a mesh)."""
        if self.mesh is None:
            return 1
        return int(self.mesh.shape.get("model", 1))

    def resolved_kv_shard(self, n_kv_heads: int) -> str | None:
        """``"heads"`` | ``"pages"`` | None — the KV partitioning the
        decode path will actually run with (None = unsharded)."""
        m = self.model_size()
        if m <= 1:
            return None
        if self.kv_shard == "heads":
            if n_kv_heads % m:
                raise ValueError(
                    f"kv_shard='heads' needs n_kv_heads ({n_kv_heads}) "
                    f"divisible by the model axis ({m})")
            return "heads"
        if self.kv_shard in ("seq", "pages"):
            return "pages"
        if self.kv_shard != "auto":
            raise ValueError(f"unknown kv_shard {self.kv_shard!r}")
        return "heads" if n_kv_heads % m == 0 else "pages"

    def shards(self, n_kv_heads: int) -> int:
        """Pool/allocator shard count S: the model-axis extent when the
        page dim is the partitioned one, else 1 (heads-sharded pools
        replicate the page dim, so the free list stays flat)."""
        if self.pool_shards is not None:
            return self.pool_shards
        if (self.layout == "paged"
                and self.resolved_kv_shard(n_kv_heads) == "pages"):
            return self.model_size()
        return 1

    def check_page_tiling(self, kv_dtype, platform: str) -> None:
        """Raise unless ``page_size`` is a whole number of the pool
        dtype's sublane tiles (``tiling.sublane_rows``: 16 rows for bf16,
        32 for the int8 pools) when the cache lives on a TPU.  A page is
        the second-minor dim of every KV block the paged flash kernel
        streams, and the compiler refuses a partial-tile block, so catch
        it here rather than in the kernel.  Elsewhere (CPU, kernels
        interpreted) any page size runs."""
        if self.layout != "paged" or platform != "tpu":
            return
        dtype = jnp.dtype(jnp.int8 if self.kv_quant == "int8" else kv_dtype)
        rows = sublane_rows(dtype.itemsize)
        if self.page_size % rows:
            raise ValueError(
                f"page_size={self.page_size} is not a multiple of {rows}, "
                f"the TPU sublane tile of the {dtype.name} KV pool")

    def logical_axes(self, cfg: ModelConfig) -> dict:
        return cache_logical_axes(
            cfg, self.kv_shard, layout=self.layout,
            dynamic=(self.alloc == "dynamic"), kv_quant=self.kv_quant,
            model_size=self.model_size() if self.mesh is not None else None)


def n_shared_sites(cfg: ModelConfig) -> int:
    if cfg.family != "hybrid" or not cfg.shared_attn_every:
        return 0
    return cfg.n_layers // cfg.shared_attn_every


def default_page_table(batch: int, max_pages: int,
                       alloc: str = "contiguous") -> jnp.ndarray:
    """(B, max_pages) int32 page table over a ``batch * max_pages`` pool.

    ``alloc`` picks the physical placement (both satisfy the disjointness
    invariant; results must be identical — the kernel only ever addresses
    pages through the table):

      * ``"contiguous"`` — sequence ``b`` owns pages ``[b*max_pages,
        (b+1)*max_pages)`` in order (the dense layout, re-expressed).
      * ``"striped"`` — logical page ``j`` of sequence ``b`` is physical
        page ``j * batch + b``: consecutive logical pages of one sequence
        are scattered across the pool, exercising true indirection.

    The dynamic third option lives in ``serving/allocator.py``
    (``CacheConfig(alloc="dynamic")``): rows start unallocated and a
    free-list allocator assigns/recycles pages at admission/retirement.
    """
    b = jnp.arange(batch, dtype=jnp.int32)[:, None]
    j = jnp.arange(max_pages, dtype=jnp.int32)[None, :]
    if alloc == "contiguous":
        return b * max_pages + j
    if alloc == "striped":
        return j * batch + b
    raise ValueError(f"unknown page allocation {alloc!r}")


_LEGACY_KEYS = ("layout", "page_size", "alloc", "pool_pages", "kv_quant")


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, config: CacheConfig | None = None, *,
               layout: str | None = None, page_size: int | None = None,
               alloc: str | None = None, pool_pages: int | None = None,
               kv_quant: str | None = None) -> dict:
    """Zero-initialised decode cache for ``batch`` sequences of up to
    ``max_len`` tokens.

    Args:
      cfg: model config (family decides which state tensors exist).
      batch: number of concurrent sequences B.
      max_len: maximum context length S_max a sequence may reach.
      dtype: KV storage dtype (bf16 serving default; all SSM state —
        ``ssm_h`` and the ``conv_*`` tails — stays f32: the recurrence
        and the decode-time conv window accumulate across steps, so
        their state dtype is an accuracy contract, not a serving knob).
      config: a ``CacheConfig`` (layout / page / allocator / quant /
        mesh knobs — see its docstring).  Default: ``CacheConfig()``,
        the dense layout.
      layout, page_size, alloc, pool_pages, kv_quant: **deprecated** —
        the pre-PR-7 keyword spelling.  Still honored (a ``CacheConfig``
        is built from them, bitwise-identical result) but emits a
        ``DeprecationWarning``; mutually exclusive with ``config``.

    Returns a dict of arrays (shapes in the module docstring).  The paged
    dict additionally carries ``page_table`` (B, max_pages) int32 and
    ``seq_lens`` (B,) int32 — plus the ``alloc_*`` allocator arrays under
    ``alloc="dynamic"`` — so the whole decode state is one donatable
    pytree.  Under ``config.mesh`` every leaf comes back placed with its
    ``NamedSharding`` (``cache_shardings``): the pool is physically
    partitioned before the first prefill touches it.
    """
    legacy = {k: v for k, v in zip(
        _LEGACY_KEYS, (layout, page_size, alloc, pool_pages, kv_quant))
        if v is not None}
    if legacy:
        if config is not None:
            raise TypeError(
                "init_cache: pass either config=CacheConfig(...) or the "
                f"legacy keywords {sorted(legacy)}, not both")
        warnings.warn(
            f"init_cache keyword(s) {sorted(legacy)} are deprecated; pass "
            "config=CacheConfig(...) instead", DeprecationWarning,
            stacklevel=2)
        config = CacheConfig(**legacy)
    if config is None:
        config = CacheConfig()

    if config.layout not in ("dense", "paged"):
        raise ValueError(f"unknown cache layout {config.layout!r}")
    if config.kv_quant not in ("none", "int8"):
        raise ValueError(f"unknown kv_quant {config.kv_quant!r} "
                         "(expected 'none' or 'int8')")
    if config.kv_quant != "none" and config.layout != "paged":
        raise ValueError(
            f"kv_quant={config.kv_quant!r} requires layout='paged': the "
            "scale rows ride the page table, and the dense decode path "
            "has no fused dequant")
    cache: dict = {}
    if cfg.family in ("ssm", "hybrid"):
        if config.layout == "paged":
            raise ValueError(
                "paged layout applies to attention-family KV caches; "
                f"family {cfg.family!r} keeps its O(1) SSM state dense")
        l, h = cfg.n_layers, cfg.ssm_n_heads
        p, n = cfg.ssm_head_dim, cfg.ssm_state
        k = cfg.ssm_conv - 1
        cache["ssm_h"] = jnp.zeros((l, batch, h, p, n), jnp.float32)
        cache["conv_x"] = jnp.zeros((l, batch, k, cfg.d_inner), jnp.float32)
        cache["conv_B"] = jnp.zeros((l, batch, k, n), jnp.float32)
        cache["conv_C"] = jnp.zeros((l, batch, k, n), jnp.float32)
        sites = n_shared_sites(cfg)
        if sites:
            cache["shared_k"] = jnp.zeros(
                (sites, batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype)
            cache["shared_v"] = jnp.zeros_like(cache["shared_k"])
        # per-slot committed-token counts: the slot allocator and the
        # serving engine address SSM state by batch row exactly like the
        # paged path addresses pages — seq_lens is the shared currency
        cache["seq_lens"] = jnp.zeros((batch,), jnp.int32)
    elif config.layout == "paged":
        platform = (config.mesh.devices.flat[0].platform
                    if config.mesh is not None else jax.default_backend())
        config.check_page_tiling(dtype, platform)
        page_sz = config.page_size
        max_pages = ceil_div(max_len, page_sz)
        n_pages = (config.pool_pages if config.pool_pages is not None
                   else batch * max_pages)
        shards = config.shards(cfg.n_kv_heads)
        # the pool partitions page-dim-first under the pages policy: round
        # the pool up so every shard owns an equal slab
        n_pages = ceil_div(n_pages, shards) * shards
        pool_dtype = jnp.int8 if config.kv_quant == "int8" else dtype
        cache["k_pages"] = jnp.zeros(
            (cfg.n_layers, n_pages, cfg.n_kv_heads, page_sz, cfg.head_dim),
            pool_dtype)
        cache["v_pages"] = jnp.zeros_like(cache["k_pages"])
        if config.kv_quant == "int8":
            # zero scales dequantize the zero-initialised pool to exact
            # zeros — indistinguishable from the fp layout's fresh pages
            cache["k_scales"] = jnp.zeros(
                (cfg.n_layers, n_pages, cfg.n_kv_heads, page_sz),
                jnp.float32)
            cache["v_scales"] = jnp.zeros_like(cache["k_scales"])
        if config.alloc == "dynamic":
            from repro.serving.allocator import SCRATCH_PAGE, attach_allocator
            cache["page_table"] = jnp.full((batch, max_pages), SCRATCH_PAGE,
                                           jnp.int32)
            cache["seq_lens"] = jnp.zeros((batch,), jnp.int32)
            cache = attach_allocator(cache, n_pages, shards)
        else:
            if n_pages < batch * max_pages:
                raise ValueError(
                    f"static page tables need batch*max_pages = "
                    f"{batch * max_pages} pages; pool has {n_pages} "
                    f"(use alloc='dynamic' to oversubscribe)")
            cache["page_table"] = default_page_table(batch, max_pages,
                                                     config.alloc)
            cache["seq_lens"] = jnp.zeros((batch,), jnp.int32)
    else:
        cache["k"] = jnp.zeros(
            (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim),
            dtype)
        cache["v"] = jnp.zeros_like(cache["k"])
    if config.mesh is not None:
        shardings = cache_shardings(cfg, cache, config)
        cache = {k: jax.device_put(v, shardings[k])
                 for k, v in cache.items()}
    return cache


def pool_page_size(pool: jax.Array) -> int:
    """Tokens per page of a head-major KV value pool, stacked
    ``(L, P, KVH, page, hd)`` or one layer's ``(P, KVH, page, hd)``.
    Every page-size read of the paged layout goes through here."""
    return pool.shape[-2]


def invalidate_token_rows(cache: dict, tok_pos: jnp.ndarray,
                          inv: jnp.ndarray) -> dict:
    """Zero the page-state rows holding the given token positions.

    ``tok_pos`` (B, S) int32 logical token positions per sequence;
    ``inv`` (B, S) bool selects which of them to invalidate.  This is
    speculative rollback's page-state half (``docs/DESIGN.md`` §8):
    rejected draft tokens' K/V rows — and, per §2 invariant 5, their
    ``k_scales``/``v_scales`` rows, via ``PAGE_STATE_KEYS`` — are zeroed
    so nothing that later aliases the page (fork, prefix share) can
    observe stale speculative state.  Deselected entries and positions
    past the page table's reach redirect to the scratch page (harmless
    writes).  Pure jnp — safe inside jit; returns a new cache dict.
    """
    from repro.serving.allocator import SCRATCH_PAGE
    pt = cache["page_table"]
    page = pool_page_size(cache["k_pages"])
    width = pt.shape[1]
    inv = inv & (tok_pos < width * page)
    pidx = jnp.take_along_axis(
        pt, jnp.clip(tok_pos // page, 0, width - 1), axis=1)
    pidx = jnp.where(inv, pidx, SCRATCH_PAGE)
    slot = jnp.where(inv, tok_pos % page, 0)
    out = dict(cache)
    for key in PAGE_STATE_KEYS:
        if key in out:
            out[key] = out[key].at[:, pidx, :, slot].set(0)
    return out


def cache_shardings(cfg: ModelConfig, cache: dict,
                    config: CacheConfig) -> dict:
    """Per-leaf ``NamedSharding``s for a cache built with ``config``
    (requires ``config.mesh``).  ``init_cache`` places leaves with these;
    the scheduler re-pins after eager admission copy-backs; tests assert
    the pool is *actually* partitioned against them."""
    assert config.mesh is not None
    specs = tree_specs(cache, config.logical_axes(cfg), config.mesh,
                       SERVING_RULES)
    return {k: NamedSharding(config.mesh, specs[k]) for k in cache}


def page_nbytes(cache: dict) -> int:
    """HBM bytes one physical page occupies across all layers: K+V values
    plus, for the ``kv_quant="int8"`` layout, their scale rows.  This is
    the unit of the decode benchmarks' bytes/token accounting and of the
    allocator's admission math (a pool page is this many bytes whether
    the pool is bf16 or int8 — quantization shrinks the *unit*, so the
    same pool array serves ~2x the tokens per byte)."""
    n_pages = cache["k_pages"].shape[1]
    total = sum(cache[k].nbytes for k in PAGE_STATE_KEYS if k in cache)
    return total // n_pages


def cache_logical_axes(cfg: ModelConfig, kv_shard: str = "auto", *,
                       layout: str = "dense", dynamic: bool = False,
                       kv_quant: str = "none",
                       model_size: int | None = None) -> dict:
    """Logical axes per cache array (``docs/DESIGN.md`` §3).

    ``kv_shard``: ``auto | heads | seq | pages`` — ``seq``/``pages`` mean
    the dense cache's sequence dim, or the paged pool's page dim, goes to
    ``model``.  ``auto`` resolves against ``model_size`` when given (the
    serving path passes the actual mesh extent), else the 16-way
    reference-mesh heuristic.  ``dynamic`` adds the ``alloc_*`` allocator
    arrays — their leading shard dim takes ``kv_pages`` so the free
    stacks / refcounts live with the pool slabs they manage (replicated
    when the pool is heads-sharded or unsharded, i.e. one flat shard);
    ``alloc_held`` is per-sequence and follows batch.  ``kv_quant="int8"``
    adds the scale pools, sharded exactly like their int8 pages minus the
    trailing head_dim axis.
    """
    axes: dict = {}
    if cfg.family in ("ssm", "hybrid"):
        axes["ssm_h"] = (None, "batch", "ssm_heads", None, None)
        axes["conv_x"] = (None, "batch", None, "ssm_inner")
        axes["conv_B"] = (None, "batch", None, None)
        axes["conv_C"] = (None, "batch", None, None)
        axes["seq_lens"] = ("batch",)
        if n_shared_sites(cfg):
            kv = _kv_axes(cfg, kv_shard, model_size)
            axes["shared_k"] = kv
            axes["shared_v"] = kv
    elif layout == "paged":
        kv = _kv_axes(cfg, kv_shard, model_size)
        # (L, P, KVH, page, hd): the per-sequence dims B/S are gone — the
        # pool's page dim takes the kv_seq split, heads keep theirs
        paged = (None, "kv_pages" if kv[2] == "kv_seq" else None,
                 kv[3], None, None)
        axes["k_pages"] = paged
        axes["v_pages"] = paged
        if kv_quant == "int8":
            axes["k_scales"] = paged[:-1]          # (L, P, KVH, page)
            axes["v_scales"] = paged[:-1]
        axes["page_table"] = ("batch", None)
        axes["seq_lens"] = ("batch",)
        if dynamic:
            # (S, P/S) / (S,) / (S, P/S) / (B,)
            axes["alloc_free"] = ("kv_pages", None)
            axes["alloc_top"] = ("kv_pages",)
            axes["alloc_ref"] = ("kv_pages", None)
            axes["alloc_held"] = ("batch",)
    else:
        kv = _kv_axes(cfg, kv_shard, model_size)
        axes["k"] = kv
        axes["v"] = kv
    return axes


def _kv_axes(cfg: ModelConfig, kv_shard: str,
             model_size: int | None = None) -> tuple:
    # (L, B, S, KVH, hd)
    if kv_shard == "heads":
        return (None, "batch", None, "kv_heads", None)
    if kv_shard in ("seq", "pages"):
        return (None, "batch", "kv_seq", None, None)
    # auto: heads when they divide the model axis (the 16-way reference
    # mesh when no actual extent is supplied), else seq/pages split
    if cfg.n_kv_heads % (model_size or 16) == 0:
        return (None, "batch", None, "kv_heads", None)
    return (None, "batch", "kv_seq", None, None)
