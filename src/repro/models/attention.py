"""Attention: MHA/GQA/MQA with RoPE variants, sliding window, softcap,
QK-norm, cross-attention, KV cache, and blockwise (flash-style) execution.

The Q/K/V projections — the paper's target bottleneck — route through
``core.qkv_fusion.apply_fused_qkv`` (the persistent-A / update_A mechanism)
or ``core.quantized_linear.apply_linear`` under the config's ``quant_proj``
mode.  Long sequences use a double-chunked online-softmax attention
(never materializing S×T scores), required for the 32k prefill cells —
either the window-aware block-sparse Pallas flash engine
(``kernels/flash_attention``; ``cfg.attn_impl`` selects) or the pure-jnp
blockwise scan below.  Sequence lengths need not divide the chunk sizes
on either path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantized_linear import apply_linear, init_linear
from repro.core.qkv_fusion import apply_fused_qkv
from repro.launch.sharding import active_mesh, model_axis_size, shard
from repro.models.config import ModelConfig
from repro.models.layers import apply_norm, apply_rope, init_norm, softcap

Params = dict
NEG_INF = -2.3819763e38  # finite min-bf16-safe mask value

# decode steps up to this many new tokens run the paged flash kernel as a
# single q block (the whole (g·q_len, D) block + f32 accumulator in VMEM);
# longer cache-writing steps (chunked paged prefill) keep the same kernel
# but tile the rows into PAGED_PREFILL_CHUNK_Q-row q blocks, each walking
# only the pages its own causal horizon exposes
PAGED_FLASH_MAX_Q = 8
PAGED_PREFILL_CHUNK_Q = 128


def _flash_engine_live(cfg: ModelConfig) -> bool:
    """Does ``cfg.attn_impl`` select the Pallas flash engine right now?"""
    from repro.kernels.tiled_matmul.ops import kernel_mode
    return (cfg.attn_impl == "flash"
            or (cfg.attn_impl == "auto"
                and kernel_mode() in ("pallas", "pallas_interpret")))


def _run_windowed(fn, cfg: ModelConfig, is_local):
    """Invoke ``fn(window)`` under the layer's local/global flag.

    Static flags pick one schedule at trace time; a traced per-layer flag
    (the layer-stack scan) compiles both schedules once and selects at
    run time with ``lax.cond``.
    """
    if cfg.sliding_window is None:
        return fn(None)
    if isinstance(is_local, (bool, int)):
        return fn(cfg.sliding_window if is_local else None)
    return jax.lax.cond(jnp.asarray(is_local, bool),
                        lambda: fn(cfg.sliding_window),
                        lambda: fn(None))


def init_attention(key: jax.Array, cfg: ModelConfig, *,
                   cross: bool = False) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    p: Params = {
        "wq": init_linear(kq, cfg.d_model, cfg.q_dim, use_bias=cfg.qkv_bias),
        "wk": init_linear(kk, cfg.d_model, cfg.kv_dim, use_bias=cfg.qkv_bias),
        "wv": init_linear(kv, cfg.d_model, cfg.kv_dim, use_bias=cfg.qkv_bias),
        "wo": init_linear(ko, cfg.q_dim, cfg.d_model, use_bias=False,
                          scale=(cfg.q_dim ** -0.5) / max(cfg.n_layers, 1) ** 0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_norm(cfg, cfg.head_dim)
        p["k_norm"] = init_norm(cfg, cfg.head_dim)
    return p


def _split_heads(x: jax.Array, n: int, hd: int) -> jax.Array:
    return x.reshape(*x.shape[:-1], n, hd)


def _mask_bias(q_pos, k_pos, *, causal: bool, window, is_local) -> jax.Array:
    """(…, S, T) additive bias from position comparisons."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    allowed = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        allowed &= kp <= qp
    if window is not None:
        in_window = kp > qp - window
        use_local = jnp.asarray(is_local, bool)
        allowed &= in_window | ~use_local
    return jnp.where(allowed, 0.0, NEG_INF).astype(jnp.float32)


def _attend_dense(q, k, v, q_pos, k_pos, *, scale, cap, causal, window,
                  is_local):
    """q (B,S,K,G,hd); k,v (B,T,K,hd) → (B,S,K,G,hd).  Scores in f32.

    ``q_pos`` may be (S,) (batch-synchronous) or (B, S) (per-sequence
    decode positions — mixed-length batches); it is aligned to the
    (B,K,G,S,T) score block so the mask broadcasts per sequence.
    """
    if jnp.ndim(q_pos) == 2:
        q_pos = q_pos[:, None, None, :]        # (B,1,1,S) → bias (B,1,1,S,T)
    s = jnp.einsum("bskgh,btkh->bkgst", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = softcap(s, cap)
    s = s + _mask_bias(q_pos, k_pos, causal=causal, window=window,
                       is_local=is_local)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkh->bskgh", p.astype(v.dtype), v)
    return o


def _attend_blockwise(q, k, v, q_offset, *, scale, cap, causal, window,
                      is_local, q_chunk, kv_chunk):
    """Double-chunked online-softmax attention (flash-style, pure jnp).

    Never materializes more than (B,K,G,q_chunk,kv_chunk) scores; math is
    identical to softmax attention (tests assert vs the dense path).
    """
    b, s_len, kh, g, hd = q.shape
    t_len = k.shape[1]
    q_chunk = min(q_chunk, s_len)
    kv_chunk = min(kv_chunk, t_len)
    # partial chunks: pad to chunk multiples; padded KV columns are masked
    # below and padded q rows are sliced off the output
    s_pad = -s_len % q_chunk
    t_pad = -t_len % kv_chunk
    if s_pad:
        q = jnp.pad(q, ((0, 0), (0, s_pad), (0, 0), (0, 0), (0, 0)))
    if t_pad:
        k = jnp.pad(k, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
    nq, nk = (s_len + s_pad) // q_chunk, (t_len + t_pad) // kv_chunk

    q_r = q.reshape(b, nq, q_chunk, kh, g, hd).swapaxes(0, 1)
    k_r = k.reshape(b, nk, kv_chunk, kh, hd).swapaxes(0, 1)
    v_r = v.reshape(b, nk, kv_chunk, kh, hd).swapaxes(0, 1)

    def q_step(_, qi_qc):
        qi, qc = qi_qc
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        # checkpointed: without this the scan's backward saves every
        # (q_chunk × kv_chunk) score block — i.e. the full S×T attention
        # matrix — defeating the point of blockwise attention.  With it the
        # bwd recomputes scores per block (flash-attention-2 style).
        @jax.checkpoint
        def kv_step(carry, kj_kc_vc):
            acc, m, l = carry
            kj, kc, vc = kj_kc_vc
            k_pos = kj * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bskgh,btkh->bkgst", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            s = softcap(s, cap)
            s = s + _mask_bias(q_pos, k_pos, causal=causal, window=window,
                               is_local=is_local)
            if t_pad:
                s = s + jnp.where(k_pos < t_len, 0.0, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgst,btkh->bkgsh", p, vc.astype(jnp.float32))
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, kh, g, q_chunk, hd), jnp.float32)
        m0 = jnp.full((b, kh, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kh, g, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0), (jnp.arange(nk), k_r, v_r))
        o = acc / jnp.maximum(l, 1e-37)[..., None]
        return None, o.astype(q.dtype)      # (b,kh,g,qc,hd)

    _, o = jax.lax.scan(q_step, None, (jnp.arange(nq), q_r))
    # (nq,b,kh,g,qc,hd) → (b, s, kh, g, hd), padded q rows dropped
    o = o.transpose(1, 0, 4, 2, 3, 5).reshape(b, s_len + s_pad, kh, g, hd)
    return o[:, :s_len]


def _attend_paged(params, q, k, v, cfg: ModelConfig, *, cache, cache_pos,
                  page_table, is_local, scale, b, s, n_new=None):
    """Paged-cache decode step: scatter new kv into pages, attend, project.

    q (B,S,H,hd), k/v (B,S,K,hd) — already rope'd; cache (k_pages,
    v_pages) each head-major (P, K, page, hd), or (k_pages, v_pages,
    k_scales, v_scales) for the ``kv_quant="int8"`` layout (int8 pools +
    (P, K, page) f32 scale rows); cache_pos (B,) per-sequence lengths before the
    write.  Quantized layouts quantize each new row per (token, kv-head)
    (``core.quantization.quantize_kv``) and scatter values and scales
    through the same page-table indices — the read side dequantizes
    in-kernel (flash) or inside the gather (fallback), so fp pages never
    materialize.  Under ``attn_impl`` ∈ {auto (Pallas live), flash}
    every step routes through the paged flash kernel: decode-sized steps
    (S ≤ ``PAGED_FLASH_MAX_Q``) as one q block, longer cache-writing
    steps (chunked paged prefill) tiled into ``PAGED_PREFILL_CHUNK_Q``
    rows per block — no length ever falls back to the dense gather.
    ``attn_impl="jnp"`` (or no Pallas) gathers the pages into a dense
    cache and reuses the jnp decode path (the parity oracle).

    ``n_new`` (B,) int32 is the speculative verify mode (``docs/DESIGN.md``
    §8): of the step's S rows, only rows ``r < n_new[b]`` are live — their
    KV lands at positions ``cache_pos[b] + r`` and their outputs are real;
    dead rows scatter to the scratch page and read back 0.  Rows whose
    position would fall past the page table's reach (a near-full
    reservation verifying more tokens than its budget) also redirect to
    scratch, so a verify step can never corrupt a live page.

    Inside a sharding context with a >1 ``model`` axis the whole step —
    scatter *and* attend — runs under ``shard_map`` instead (the
    partitioned decode path, ``docs/DESIGN.md`` §3): KV heads partition
    over ``model`` when divisible (tensor parallel — each shard walks the
    full page table for its own heads; no softmax collective), otherwise
    the page-pool dim partitions and each shard walks only the pages it
    owns, combining via a cross-shard partial softmax
    (``_paged_attend_split``).  GSPMD never sees the pool indexed by the
    table, so it can never decide to all-gather it.
    """
    from repro.serving.cache import pool_page_size
    quant = len(cache) == 4
    ck, cv = cache[0], cache[1]
    page = pool_page_size(ck)
    tok_pos = cache_pos[:, None] + jnp.arange(s)[None, :]       # (B, S)
    if n_new is None:
        pidx = jnp.take_along_axis(page_table, tok_pos // page, axis=1)
        slot = tok_pos % page
    else:
        from repro.serving.allocator import SCRATCH_PAGE
        width = page_table.shape[1]
        live = ((jnp.arange(s)[None, :] < n_new[:, None])
                & (tok_pos < width * page))
        pidx = jnp.take_along_axis(
            page_table, jnp.clip(tok_pos // page, 0, width - 1), axis=1)
        pidx = jnp.where(live, pidx, SCRATCH_PAGE)
        slot = jnp.where(live, tok_pos % page, 0)

    mesh = active_mesh()
    msize = model_axis_size() or 1
    if mesh is not None and msize > 1:
        if n_new is not None:
            raise NotImplementedError(
                "speculative verify (n_new) is not supported on the "
                "sharded paged decode path — the scheduler degrades to "
                "1-token decode under a >1 model axis")
        by = "heads" if cfg.n_kv_heads % msize == 0 else "pages"
        if by == "pages" and ck.shape[0] % msize:
            raise ValueError(
                f"paged pool of {ck.shape[0]} pages cannot split over a "
                f"{msize}-way model axis; size the pool to a multiple "
                "(CacheConfig rounds pool_pages up automatically)")
        if quant:
            from repro.core.quantization import quantize_kv
            kq, k_sc = quantize_kv(k)
            vq, v_sc = quantize_kv(v)
            upds = (kq, vq, k_sc, v_sc)
        else:
            upds = (k, v)
        pools = _paged_scatter_sharded(mesh, by, tuple(cache), upds,
                                       pidx, slot)
        if by == "heads":
            o = _paged_attend_tp(q, tok_pos, page_table, cache_pos + s,
                                 pools, cfg, scale=scale,
                                 is_local=is_local, b=b, s=s, mesh=mesh)
        else:
            o = _paged_attend_split(q, tok_pos, page_table, pools, cfg,
                                    scale=scale, is_local=is_local,
                                    b=b, s=s, mesh=mesh)
        o = o.reshape(b, s, cfg.q_dim)
        y = apply_linear(params["wo"], o, mode=cfg.quant_proj)
        return y, pools
    if quant:
        from repro.core.quantization import quantize_kv
        cks, cvs = cache[2], cache[3]
        kq, k_sc = quantize_kv(k)             # (B,S,K,hd) int8, (B,S,K) f32
        vq, v_sc = quantize_kv(v)
        # (pidx, slot) index the pool's page and in-page dims around its
        # head dim: the (B, S) index dims lead, so (B,S,K,hd) rows fit
        ck = ck.at[pidx, :, slot].set(kq)
        cv = cv.at[pidx, :, slot].set(vq)
        cks = cks.at[pidx, :, slot].set(k_sc)
        cvs = cvs.at[pidx, :, slot].set(v_sc)
    else:
        cks = cvs = None
        ck = ck.at[pidx, :, slot].set(k.astype(ck.dtype))
        cv = cv.at[pidx, :, slot].set(v.astype(cv.dtype))
    lengths = cache_pos + (s if n_new is None else n_new)

    if _flash_engine_live(cfg):
        from repro.kernels.flash_attention.ops import paged_decode_attention
        q_chunk = None if s <= PAGED_FLASH_MAX_Q else PAGED_PREFILL_CHUNK_Q

        def _pdec(window):
            return paged_decode_attention(
                q, ck, cv, page_table, lengths, scale=scale, window=window,
                softcap=cfg.attn_logit_softcap, q_chunk=q_chunk,
                k_scales=cks, v_scales=cvs, new_lens=n_new)

        o = _run_windowed(_pdec, cfg, is_local)
    else:
        from repro.kernels.flash_attention.ref import (
            dequantize_gathered, paged_gather, paged_gather_scales)
        kh = cfg.n_kv_heads
        g = cfg.n_heads // kh
        kd = paged_gather(ck, page_table)                       # (B,T,K,hd)
        vd = paged_gather(cv, page_table)
        if quant:
            kd = dequantize_gathered(
                kd, paged_gather_scales(cks, page_table))
            vd = dequantize_gathered(
                vd, paged_gather_scales(cvs, page_table))
        o = _attend_dense(q.reshape(b, s, kh, g, cfg.head_dim), kd, vd,
                          tok_pos, jnp.arange(kd.shape[1]), scale=scale,
                          cap=cfg.attn_logit_softcap, causal=True,
                          window=cfg.sliding_window, is_local=is_local)
        if n_new is not None:
            # dead verify rows read back 0 (kernel/oracle convention)
            o = o * (jnp.arange(s)[None, :] < n_new[:, None]
                     )[..., None, None, None].astype(o.dtype)

    o = o.reshape(b, s, cfg.q_dim)
    y = apply_linear(params["wo"], o, mode=cfg.quant_proj)
    new_cache = (ck, cv, cks, cvs) if quant else (ck, cv)
    return y, new_cache


# ---------------------------------------------------------------------------
# Partitioned paged decode (docs/DESIGN.md §3).  Everything that touches
# the page pool runs under shard_map: each device holds only its pool
# shard and the program below IS the per-shard program — the pool is
# never an operand of a GSPMD-partitioned gather/scatter, so no sharding
# propagation choice can materialize (all-gather) it.
# ---------------------------------------------------------------------------
def _pool_specs(quant: bool, by: str) -> tuple:
    """shard_map PartitionSpecs for one layer's head-major (k_pages,
    v_pages[, k_scales, v_scales]): KV-head dim over ``model``
    (``by="heads"``) or page-pool dim over ``model`` (``by="pages"``)."""
    from jax.sharding import PartitionSpec as P
    if by == "heads":
        val, sc = P(None, "model", None, None), P(None, "model", None)
    else:
        val, sc = P("model", None, None, None), P("model", None, None)
    return (val, val, sc, sc) if quant else (val, val)


def _paged_scatter_sharded(mesh, by: str, pools: tuple, upds: tuple,
                           pidx: jax.Array, slot: jax.Array) -> tuple:
    """Scatter the step's new KV rows (+scale rows) into the partitioned
    pools.  ``by="heads"``: every shard owns all pages for a head slice —
    a plain local scatter of its update slice.  ``by="pages"``: indices
    are global page ids; each shard rebases them into its own slab and
    drops the writes it does not own (every page is owned by exactly one
    shard, so collectively the scatter lands exactly once)."""
    from jax.sharding import PartitionSpec as P
    quant = len(pools) == 4
    pool_specs = _pool_specs(quant, by)
    # updates are token-major (B, S, K[, hd]): heads split their dim 2
    upd = (P(None, None, "model", None), P(None, None, "model")) \
        if by == "heads" else (P(None, None, None, None), P(None, None, None))
    upd_specs = (upd[0], upd[0], upd[1], upd[1]) if quant else upd[:1] * 2

    def scat(pidx, slot, *ops):
        ps, us = ops[:len(pools)], ops[len(pools):]
        if by == "heads":
            return tuple(p.at[pidx, :, slot].set(u.astype(p.dtype))
                         for p, u in zip(ps, us))
        s_idx = jax.lax.axis_index("model")
        per = ps[0].shape[0]
        loc = pidx - s_idx * per
        tgt = jnp.where((loc >= 0) & (loc < per), loc, per)
        return tuple(p.at[tgt, :, slot].set(u.astype(p.dtype), mode="drop")
                     for p, u in zip(ps, us))

    rep2 = P(None, None)
    return jax.shard_map(scat, mesh=mesh,
                         in_specs=(rep2, rep2, *pool_specs, *upd_specs),
                         out_specs=pool_specs, check_vma=False)(
        pidx, slot, *pools, *upds)


def _paged_attend_tp(q, tok_pos, page_table, lengths, pools,
                     cfg: ModelConfig, *, scale, is_local, b, s, mesh):
    """Tensor-parallel paged attention: KV heads partition over ``model``
    (with their g-sized query groups riding along, so the q head dim
    partitions identically).  Each shard runs the *full* schedule —
    kernel page walk or gather oracle — over its head slice and the
    complete page table; softmax is per-head, so no combine is needed and
    per-head math is identical to the unsharded path.  This is the
    ``(B·KVH, q_blocks, steps)`` kernel grid partitioned over its KVH
    factor."""
    from jax.sharding import PartitionSpec as P
    quant = len(pools) == 4
    pool_specs = _pool_specs(quant, "heads")
    qspec = P(None, None, "model", None)
    kh, hd = cfg.n_kv_heads, cfg.head_dim
    g = cfg.n_heads // kh

    if _flash_engine_live(cfg):
        from repro.kernels.flash_attention.ops import paged_decode_attention
        q_chunk = None if s <= PAGED_FLASH_MAX_Q else PAGED_PREFILL_CHUNK_Q

        def _pdec(window):
            def local(q_l, pt, lens, *pl):
                cks_l, cvs_l = (pl[2], pl[3]) if quant else (None, None)
                return paged_decode_attention(
                    q_l, pl[0], pl[1], pt, lens, scale=scale,
                    window=window, softcap=cfg.attn_logit_softcap,
                    q_chunk=q_chunk, k_scales=cks_l, v_scales=cvs_l)

            return jax.shard_map(
                local, mesh=mesh,
                in_specs=(qspec, P(None, None), P(None), *pool_specs),
                out_specs=qspec, check_vma=False)(
                q, page_table, lengths, *pools)

        return _run_windowed(_pdec, cfg, is_local)

    def local(q_l, tokp, pt, loc_flag, *pl):
        from repro.kernels.flash_attention.ref import (
            dequantize_gathered, paged_gather, paged_gather_scales)
        kh_l = pl[0].shape[1]
        kd = paged_gather(pl[0], pt)
        vd = paged_gather(pl[1], pt)
        if quant:
            kd = dequantize_gathered(kd, paged_gather_scales(pl[2], pt))
            vd = dequantize_gathered(vd, paged_gather_scales(pl[3], pt))
        o = _attend_dense(q_l.reshape(b, s, kh_l, g, hd), kd, vd, tokp,
                          jnp.arange(kd.shape[1]), scale=scale,
                          cap=cfg.attn_logit_softcap, causal=True,
                          window=cfg.sliding_window, is_local=loc_flag)
        return o.reshape(b, s, kh_l * g, hd)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(qspec, P(None, None), P(None, None), P(), *pool_specs),
        out_specs=qspec, check_vma=False)(
        q, tok_pos, page_table, jnp.asarray(is_local, bool), *pools)


def _paged_attend_split(q, tok_pos, page_table, pools, cfg: ModelConfig,
                        *, scale, is_local, b, s, mesh):
    """Split-KV paged attention: the page-pool dim partitions over
    ``model`` (KV heads don't divide it).  Each shard walks only the
    table entries that name pages in its own slab — remote pages gather
    from slot 0 and are masked to NEG_INF, so the walk is shard-local by
    masking, with no index ever reaching outside the local slab.  The
    per-shard partial softmaxes combine exactly: a global row max via
    ``pmax``, then ``psum`` of the weights' normalizer and the weighted-V
    accumulator (flash-attention's two-pass identity across devices; q is
    replicated, so only (B,H,S)-sized partials cross the wire — never
    KV).  Runs the gather-oracle math locally whatever the kernel mode —
    a partial-output kernel epilogue is the remaining TPU work."""
    from jax.sharding import PartitionSpec as P

    from repro.serving.cache import pool_page_size
    quant = len(pools) == 4
    pool_specs = _pool_specs(quant, "pages")
    page = pool_page_size(pools[0])
    kh, hd = cfg.n_kv_heads, cfg.head_dim
    g = cfg.n_heads // kh

    def local(q_, tokp, pt, loc_flag, *pl):
        from repro.kernels.flash_attention.ref import (
            dequantize_gathered, paged_gather, paged_gather_scales)
        s_idx = jax.lax.axis_index("model")
        per = pl[0].shape[0]
        loc = pt - s_idx * per                   # rebase to local slab
        owned = (loc >= 0) & (loc < per)         # (B, max_pages)
        locc = jnp.where(owned, loc, 0)
        kd = paged_gather(pl[0], locc)           # (B, T, kh, hd)
        vd = paged_gather(pl[1], locc)
        if quant:
            kd = dequantize_gathered(kd, paged_gather_scales(pl[2], locc))
            vd = dequantize_gathered(vd, paged_gather_scales(pl[3], locc))
        t_len = kd.shape[1]
        own_tok = jnp.repeat(owned, page, axis=1)            # (B, T)
        sc = jnp.einsum("bskgh,btkh->bkgst", q_.reshape(b, s, kh, g, hd),
                        kd, preferred_element_type=jnp.float32) * scale
        sc = softcap(sc, cfg.attn_logit_softcap)
        sc = sc + _mask_bias(tokp[:, None, None, :], jnp.arange(t_len),
                             causal=True, window=cfg.sliding_window,
                             is_local=loc_flag)
        sc = jnp.where(own_tok[:, None, None, None, :], sc, NEG_INF)
        # partial softmax against the *global* row max (finite: the
        # causal diagonal was just written to a page some shard owns)
        m = jax.lax.pmax(jnp.max(sc, axis=-1), "model")      # (b,k,g,s)
        p = jnp.where(own_tok[:, None, None, None, :],
                      jnp.exp(sc - m[..., None]), 0.0)
        l = jax.lax.psum(jnp.sum(p, axis=-1), "model")
        acc = jax.lax.psum(
            jnp.einsum("bkgst,btkh->bkgsh", p, vd.astype(jnp.float32)),
            "model")
        o = (acc / jnp.maximum(l, 1e-37)[..., None]).astype(q_.dtype)
        return o.transpose(0, 3, 1, 2, 4).reshape(b, s, kh * g, hd)

    rep4 = P(None, None, None, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(rep4, P(None, None), P(None, None), P(), *pool_specs),
        out_specs=rep4, check_vma=False)(
        q, tok_pos, page_table, jnp.asarray(is_local, bool), *pools)


def apply_attention(params: Params, x: jax.Array, cfg: ModelConfig, *,
                    positions: jax.Array,
                    is_local=False,
                    causal: bool = True,
                    memory: jax.Array | None = None,
                    cache: tuple | None = None,
                    cache_pos: jax.Array | None = None,
                    page_table: jax.Array | None = None,
                    n_new: jax.Array | None = None):
    """Self- or cross-attention.

    x: (B, S, D).  memory: (B, T, D) for cross-attention (no cache, no rope).

    Decode mode (``cache`` given) supports both serving cache layouts:

      * dense — cache (k, v) each (B, S_max, K, hd); ``cache_pos`` is a
        scalar step index (batch-synchronous, seed behaviour) or a (B,)
        int32 vector of per-sequence write positions (mixed-length
        batches); new kv is written there and attention runs over the
        cache with per-sequence causal masking.
      * paged — ``page_table`` (B, max_pages) int32 is given and cache is
        (k_pages, v_pages) each (P, K, page, hd) — or (k_pages, v_pages,
        k_scales, v_scales) for the int8-quantized page layout;
        ``cache_pos`` (B,) holds per-sequence lengths *before* this step.
        New kv is scattered into each sequence's pages and attention
        routes through the paged flash-decode schedule
        (``kernels/flash_attention/decode.py``) when ``cfg.attn_impl``
        selects the flash engine, else through a dense gather fallback.
        ``n_new`` (B,) int32 selects the paged layout's speculative
        verify mode (see ``_attend_paged``); dense caches don't support
        it.

    Returns (y, new_cache or None).
    """
    assert n_new is None or page_table is not None, \
        "n_new (speculative verify) requires the paged cache layout"
    b, s, _ = x.shape
    kh, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    hd = cfg.head_dim
    scale = cfg.attn_scale if cfg.attn_scale is not None else hd ** -0.5
    kv_src = memory if memory is not None else x

    if memory is None and cfg.fuse_qkv:
        q, k, v = apply_fused_qkv(params["wq"], params["wk"], params["wv"],
                                  x, mode=cfg.quant_proj)
    else:
        q = apply_linear(params["wq"], x, mode=cfg.quant_proj)
        k = apply_linear(params["wk"], kv_src, mode=cfg.quant_proj)
        v = apply_linear(params["wv"], kv_src, mode=cfg.quant_proj)

    q = _split_heads(q, cfg.n_heads, hd)
    k = _split_heads(k, kh, hd)
    v = _split_heads(v, kh, hd)

    if cfg.qk_norm:
        q = apply_norm(params["q_norm"], q, cfg)
        k = apply_norm(params["k_norm"], k, cfg)

    if memory is None:                       # rope only on self-attention
        q = apply_rope(q, positions, cfg)
        k = apply_rope(k, positions, cfg)

    if cache is not None and page_table is not None:
        return _attend_paged(params, q, k, v, cfg, cache=cache,
                             cache_pos=cache_pos, page_table=page_table,
                             is_local=is_local, scale=scale, b=b, s=s,
                             n_new=n_new)

    new_cache = None
    if cache is not None:
        ck, cv = cache
        if jnp.ndim(cache_pos) == 0:
            # batch-synchronous write (seed behaviour): one shared position
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                              (0, cache_pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                              (0, cache_pos, 0, 0))
        else:
            # per-sequence write positions (mixed-length batches)
            bidx = jnp.arange(b)[:, None]
            tok_pos = cache_pos[:, None] + jnp.arange(s)[None, :]
            ck = ck.at[bidx, tok_pos].set(k.astype(ck.dtype))
            cv = cv.at[bidx, tok_pos].set(v.astype(cv.dtype))
        new_cache = (ck, cv)
        k, v = ck, cv
        k_pos = jnp.arange(k.shape[1])
        q_pos = positions
    else:
        k_pos = (positions if memory is None
                 else jnp.arange(kv_src.shape[1]))
        q_pos = positions

    # GQA execution layout: grouped (K sharded over `model`) when the KV-head
    # count divides the model axis; otherwise repeat KV up to the full head
    # count so attention compute still shards over heads (mistral: kv=8 on a
    # 16-way model axis).  The KV *cache* always stores the true kv_heads.
    # Decode exception: with the cache seq-split over `model`, the work is
    # already distributed over T — repeating KV would only multiply the
    # dominant KV-streaming bytes by the group size (12x for mistral), so
    # the grouped layout is kept (§Perf, mistral decode_32k).
    msize = model_axis_size()
    if (msize is None or kh % msize == 0 or g == 1
            or cache is not None):
        q = q.reshape(b, s, kh, g, hd)
    else:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        kh, g = cfg.n_heads, 1
        q = q.reshape(b, s, kh, g, hd)

    q = shard(q, "batch", None, "kv_heads", None, None)
    k = shard(k, "batch", "kv_seq" if cache is not None else None,
              "kv_heads", None)
    v = shard(v, "batch", "kv_seq" if cache is not None else None,
              "kv_heads", None)

    use_blockwise = (cache is None and memory is None
                     and s >= cfg.blockwise_attn_threshold)
    # The flash-attention Pallas engine replaces the jnp blockwise path for
    # the no-cache case — including gemma2-style local layers: the kernel
    # masks the sliding window in-kernel and its block-sparse schedule only
    # streams the KV blocks the window exposes (kernels/flash_attention).
    if use_blockwise and _flash_engine_live(cfg):
        from repro.kernels.flash_attention.ops import flash_attention
        qf = q.reshape(b, s, kh * g, hd)

        def _flash(window):
            return flash_attention(
                qf, k, v, scale=scale, causal=causal, window=window,
                softcap=cfg.attn_logit_softcap,
                q_chunk=cfg.attn_chunk_q, kv_chunk=cfg.attn_chunk_kv)

        o = _run_windowed(_flash, cfg, is_local).reshape(b, s, kh, g, hd)
    elif use_blockwise:
        o = _attend_blockwise(
            q, k, v, 0, scale=scale, cap=cfg.attn_logit_softcap,
            causal=causal, window=cfg.sliding_window, is_local=is_local,
            q_chunk=cfg.attn_chunk_q, kv_chunk=cfg.attn_chunk_kv)
    else:
        # decode masking: hide cache slots beyond the current position
        window = cfg.sliding_window if memory is None else None
        o = _attend_dense(q, k, v, q_pos, k_pos, scale=scale,
                          cap=cfg.attn_logit_softcap,
                          causal=causal and memory is None,
                          window=window, is_local=is_local)

    o = o.reshape(b, s, cfg.q_dim)
    y = apply_linear(params["wo"], o, mode=cfg.quant_proj)
    return y, new_cache
