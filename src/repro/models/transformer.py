"""Unified model assembly for all 10 assigned architectures.

One ``init_model`` / ``apply_model`` pair driven by ``ModelConfig``:

  * dense / moe / vlm LMs — pre-norm decoder blocks (optionally gemma2
    sandwich post-norms), scanned over layers (O(1) HLO in depth),
  * ssm — Mamba2 blocks,
  * hybrid (zamba2) — Mamba2 backbone with a parameter-shared attention
    block applied every ``shared_attn_every`` layers (distinct KV caches per
    application site),
  * encoder-decoder (seamless-m4t) — bidirectional encoder over precomputed
    frame embeddings + causal decoder with cross-attention.

Cache convention (decode) — see serving/cache.py + docs/DESIGN.md:
  dense:  {"k","v"}: (L, B, S_max, KVH, hd)     attention layers
  paged:  {"k_pages","v_pages"}: (L, P, KVH, page, hd) page pools,
          {"k_scales","v_scales"}: (L, P, KVH, page) f32 (kv_quant="int8"),
          {"page_table"}: (B, max_pages) int32, {"seq_lens"}: (B,) int32
  {"shared_k","shared_v"}: (A, B, S_max, KVH, hd)   zamba2 shared block
  {"ssm_h"}: (L, B, H, P, N) f32; {"conv_x","conv_B","conv_C"} conv tails;
          SSM serving caches also carry {"seq_lens"}: (B,) int32
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.launch.sharding import shard
from repro.models.attention import apply_attention, init_attention
from repro.models.config import ModelConfig
from repro.models.ffn import apply_ffn, init_ffn
from repro.models.layers import (apply_norm, embed_tokens, init_embedding,
                                 init_norm, sinusoidal_positions, unembed)
from repro.models.moe import apply_moe, init_moe
from repro.models.ssm import apply_mamba2, init_mamba2

Params = dict


# ===========================================================================
# Block init
# ===========================================================================
def _init_decoder_block(key: jax.Array, cfg: ModelConfig, *,
                        cross: bool = False) -> Params:
    ks = jax.random.split(key, 6)
    p: Params = {"norm_attn": init_norm(cfg),
                 "attn": init_attention(ks[0], cfg),
                 "norm_ffn": init_norm(cfg)}
    if cfg.is_moe:
        p["moe"] = init_moe(ks[1], cfg)
    else:
        p["ffn"] = init_ffn(ks[1], cfg)
    if cfg.post_block_norm:
        p["norm_attn_post"] = init_norm(cfg)
        p["norm_ffn_post"] = init_norm(cfg)
    if cross:
        p["norm_cross"] = init_norm(cfg)
        p["cross"] = init_attention(ks[2], cfg, cross=True)
    return p


def _init_ssm_block(key: jax.Array, cfg: ModelConfig) -> Params:
    return {"norm": init_norm(cfg), "mamba": init_mamba2(key, cfg)}


def init_model(key: jax.Array, cfg: ModelConfig) -> Params:
    cfg.validate()
    keys = jax.random.split(key, 8)
    params: Params = {"embed": init_embedding(keys[0], cfg),
                      "final_norm": init_norm(cfg)}
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "w": jax.random.normal(keys[1], (cfg.vocab_size, cfg.d_model),
                                   jnp.float32) * (cfg.d_model ** -0.5)}

    def stack(init_fn, key_, n):
        return jax.vmap(init_fn)(jax.random.split(key_, n))

    if cfg.family in ("ssm",):
        params["layers"] = stack(lambda k: _init_ssm_block(k, cfg),
                                 keys[2], cfg.n_layers)
    elif cfg.family == "hybrid":
        params["layers"] = stack(lambda k: _init_ssm_block(k, cfg),
                                 keys[2], cfg.n_layers)
        params["shared_attn"] = _init_decoder_block(keys[3], cfg)
    elif cfg.is_encoder_decoder:
        params["encoder"] = {
            "layers": stack(lambda k: _init_decoder_block(k, cfg),
                            keys[4], cfg.n_encoder_layers),
            "final_norm": init_norm(cfg),
        }
        params["layers"] = stack(
            lambda k: _init_decoder_block(k, cfg, cross=True),
            keys[2], cfg.n_layers)
    else:
        params["layers"] = stack(lambda k: _init_decoder_block(k, cfg),
                                 keys[2], cfg.n_layers)
    return params


# ===========================================================================
# Block apply
# ===========================================================================
def _decoder_block(p: Params, x, cfg: ModelConfig, *, positions, is_local,
                   causal, cache_kv, cache_pos, memory, page_table=None,
                   n_new=None):
    h = apply_norm(p["norm_attn"], x, cfg)
    a_out, new_kv = apply_attention(p["attn"], h, cfg, positions=positions,
                                    is_local=is_local, causal=causal,
                                    cache=cache_kv, cache_pos=cache_pos,
                                    page_table=page_table, n_new=n_new)
    # materialize the TP partial-sum reduction in bf16 BEFORE the (f32
    # internal) norm/residual — otherwise GSPMD hoists the all-reduce past
    # the upcast and moves 2× the bytes
    a_out = shard(a_out, "batch", "act_seq", None)
    if cfg.post_block_norm:
        a_out = apply_norm(p["norm_attn_post"], a_out, cfg)
    x = x + cfg.residual_multiplier * a_out.astype(x.dtype)

    if memory is not None:
        h = apply_norm(p["norm_cross"], x, cfg)
        c_out, _ = apply_attention(p["cross"], h, cfg, positions=positions,
                                   memory=memory)
        x = x + cfg.residual_multiplier * c_out.astype(x.dtype)

    h = apply_norm(p["norm_ffn"], x, cfg)
    if cfg.is_moe:
        f_out, aux = apply_moe(p["moe"], h, cfg)
    else:
        f_out, aux = apply_ffn(p["ffn"], h, cfg), {}
    f_out = shard(f_out, "batch", "act_seq", None)
    if cfg.post_block_norm:
        f_out = apply_norm(p["norm_ffn_post"], f_out, cfg)
    x = x + cfg.residual_multiplier * f_out.astype(x.dtype)
    return x, new_kv, aux


def _ssm_block(p: Params, x, cfg: ModelConfig, *, ssm_state, n_valid=None):
    h = apply_norm(p["norm"], x, cfg)
    y, new_state = apply_mamba2(p["mamba"], h, cfg, state=ssm_state,
                                n_valid=n_valid)
    y = shard(y, "batch", "act_seq", None)
    return x + cfg.residual_multiplier * y.astype(x.dtype), new_state


def _local_flags(cfg: ModelConfig) -> jax.Array:
    """(L,) bool — which layers use the sliding window (gemma2: even)."""
    idx = jnp.arange(cfg.n_layers)
    if cfg.layer_pattern == "local_global" and cfg.sliding_window:
        return idx % 2 == 0
    if cfg.sliding_window:
        return jnp.ones((cfg.n_layers,), bool)
    return jnp.zeros((cfg.n_layers,), bool)


# ===========================================================================
# Layer-stack scans (train/prefill vs decode)
# ===========================================================================
def _scan_decoder(params, x, cfg: ModelConfig, *, positions, causal,
                  cache, cache_pos, memory, n_valid=None):
    flags = _local_flags(cfg)
    decode = cache is not None
    paged = decode and "k_pages" in cache
    if n_valid is not None and not paged:
        raise NotImplementedError(
            "n_valid on the attention stack requires the paged cache "
            "layout (speculative verify, docs/DESIGN.md §8)")
    quant = paged and "k_scales" in cache
    page_table = cache["page_table"] if paged else None
    # per-layer page state threaded through the scan as xs (the quantized
    # layout adds its scale pools, which travel with their int8 pages)
    kv_keys = (("k_pages", "v_pages", "k_scales", "v_scales") if quant
               else ("k_pages", "v_pages") if paged
               else ("k", "v") if decode else ())

    def body(carry, xs):
        x, aux_sum = carry
        if decode:
            lp, flag = xs[0], xs[1]
            cache_kv = xs[2:]
        else:
            lp, flag = xs
            cache_kv = None
        x, new_kv, aux = _decoder_block(
            lp, x, cfg, positions=positions, is_local=flag, causal=causal,
            cache_kv=cache_kv, cache_pos=cache_pos, memory=memory,
            page_table=page_table, n_new=n_valid)
        aux_sum = aux_sum + aux.get("load_balance_loss", 0.0)
        # sequence-sharded residual between blocks: the checkpointed carry
        # is 1/|model| sized (no-op when seq doesn't divide, e.g. decode)
        x = shard(x, "batch", "act_seq", None)
        return (x, aux_sum), (new_kv if decode else None)

    if cfg.remat == "block":
        body = jax.checkpoint(body)

    xs = (params["layers"], flags) + tuple(cache[k] for k in kv_keys)
    (x, aux_sum), new_kvs = jax.lax.scan(body, (x, 0.0), xs)
    new_cache = None
    if paged:
        # layer-independent state (page table, allocator arrays, …) rides
        # along untouched; seq_lens is stamped by apply_model (it knows
        # how many tokens were committed)
        new_cache = {k: v for k, v in cache.items() if k not in kv_keys}
        new_cache.update(zip(kv_keys, new_kvs))
    elif decode:
        new_cache = dict(zip(kv_keys, new_kvs))
    return x, aux_sum, new_cache


def _scan_ssm(params, x, cfg: ModelConfig, *, cache, shared_ctx,
              n_valid=None):
    """SSM / hybrid stack.  ``shared_ctx`` (hybrid only): dict with
    positions, cache_pos, shared attn caches.  With a cache and S > 1 the
    blocks run in prefill-commit mode (state advanced by each row's
    ``n_valid`` committed tokens — see ``apply_mamba2``)."""
    decode = cache is not None
    every = cfg.shared_attn_every
    hybrid = cfg.family == "hybrid" and every > 0
    idx = jnp.arange(cfg.n_layers)
    attn_here = (idx % every) == (every - 1) if hybrid else \
        jnp.zeros((cfg.n_layers,), bool)
    # index of each application site (prefix count), for cache addressing
    app_index = jnp.cumsum(attn_here.astype(jnp.int32)) - 1

    sp = params.get("shared_attn")

    def maybe_shared_attn(x, flag, app_i, carry_caches):
        if not hybrid:
            return x, carry_caches
        sk, sv = carry_caches          # (A,B,S,KVH,hd) or dummy
        if decode:
            ck = jax.lax.dynamic_index_in_dim(sk, app_i, 0, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(sv, app_i, 0, keepdims=False)
            cache_kv = (ck, cv)
        else:
            cache_kv = None

        def run(x):
            y, new_kv, _ = _decoder_block(
                sp, x, cfg, positions=shared_ctx["positions"],
                is_local=False, causal=True, cache_kv=cache_kv,
                cache_pos=shared_ctx["cache_pos"], memory=None)
            return y, new_kv

        def skip(x):
            return x, cache_kv

        y, new_kv = jax.lax.cond(flag, run, skip, x)
        if decode:
            sk = jax.lax.dynamic_update_index_in_dim(sk, new_kv[0], app_i, 0)
            sv = jax.lax.dynamic_update_index_in_dim(sv, new_kv[1], app_i, 0)
        return y, (sk, sv)

    def body(carry, xs):
        x, caches = carry
        if decode:
            lp, flag, app_i, sh, scx, scb, scc = xs
            state = {"h": sh, "conv_x": scx, "conv_B": scb, "conv_C": scc}
        else:
            lp, flag, app_i = xs
            state = None
        x, caches = maybe_shared_attn(x, flag, app_i, caches)
        x, new_state = _ssm_block(lp, x, cfg, ssm_state=state,
                                  n_valid=n_valid)
        x = shard(x, "batch", "act_seq", None)
        ys = ((new_state["h"], new_state["conv_x"], new_state["conv_B"],
               new_state["conv_C"]) if decode else None)
        return (x, caches), ys

    if cfg.remat == "block":
        body = jax.checkpoint(body)

    if hybrid and decode:
        carry_caches = (cache["shared_k"], cache["shared_v"])
    else:
        carry_caches = (jnp.zeros((), jnp.float32),) * 2   # dummy
    if decode:
        xs = (params["layers"], attn_here, app_index, cache["ssm_h"],
              cache["conv_x"], cache["conv_B"], cache["conv_C"])
    else:
        xs = (params["layers"], attn_here, app_index)

    (x, caches), ys = jax.lax.scan(body, (x, carry_caches), xs)
    new_cache = None
    if decode:
        ssm_keys = ("ssm_h", "conv_x", "conv_B", "conv_C")
        # layer-independent state (seq_lens, …) rides along untouched, as
        # in _scan_decoder; seq_lens is stamped by apply_model
        new_cache = {k: v for k, v in cache.items()
                     if k not in ssm_keys + ("shared_k", "shared_v")}
        new_cache.update(zip(ssm_keys, ys))
        if hybrid:
            new_cache["shared_k"], new_cache["shared_v"] = caches
    return x, new_cache


# ===========================================================================
# Top level
# ===========================================================================
def apply_model(params: Params, tokens: jax.Array, cfg: ModelConfig, *,
                positions: jax.Array | None = None,
                cache: dict | None = None,
                cache_pos: jax.Array | None = None,
                frontend_embeds: jax.Array | None = None,
                encoder_frames: jax.Array | None = None,
                memory: jax.Array | None = None,
                n_valid: jax.Array | None = None):
    """Returns (logits, new_cache, aux).

    tokens: (B, S) int32 decoder/text tokens.
    frontend_embeds: (B, P, D) vision-patch embeddings prepended (phi3v).
    encoder_frames: (B, T, D) audio-frame embeddings (seamless encoder in).
    memory: (B, T, D) precomputed encoder output (decode steps).
    cache/cache_pos: decode state (see ``serving/cache.py`` layouts).
    ``cache_pos`` is a scalar (batch-synchronous) or (B,) int32 vector of
    per-sequence write positions; with a paged or SSM cache a scalar is
    broadcast.  ``n_valid`` (B,) int32 marks how many of the S tokens
    each row actually commits: SSM/hybrid prefill leaves the recurrent
    state untouched past it, and the paged attention stack runs in
    speculative verify mode (``docs/DESIGN.md`` §8) — rows past it are
    masked, their KV scattered to the scratch page, their outputs 0.
    The paged/SSM new_cache carries ``seq_lens = cache_pos + committed``.
    """
    x = embed_tokens(params["embed"], tokens, cfg)
    if frontend_embeds is not None and cache is None:
        x = jnp.concatenate(
            [frontend_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    x = shard(x, "batch", "seq" if b == 1 else None, None)

    paged = cache is not None and "k_pages" in cache
    ssm_cache = cache is not None and "ssm_h" in cache
    if (paged or ssm_cache) and (cache_pos is None
                                 or jnp.ndim(cache_pos) == 0):
        # paged/SSM serving is per-sequence — normalize to (B,) positions
        cache_pos = jnp.full((b,), 0 if cache_pos is None else cache_pos,
                             jnp.int32)
    if positions is None:
        if cache is None:
            positions = jnp.arange(s)
        elif jnp.ndim(cache_pos) == 0:
            positions = cache_pos + jnp.arange(s)              # (S,)
        else:
            positions = (cache_pos[:, None]
                         + jnp.arange(s)[None, :])             # (B, S)
    if cfg.pos_embedding == "sinusoidal":
        pe = sinusoidal_positions(positions, cfg.d_model).astype(x.dtype)
        x = x + (pe[None] if positions.ndim == 1 else pe)

    aux = {"load_balance_loss": jnp.zeros((), jnp.float32)}

    if cfg.is_encoder_decoder and memory is None and cache is None:
        memory = encode(params, encoder_frames, cfg)

    if cfg.family in ("ssm", "hybrid"):
        shared_ctx = {"positions": positions, "cache_pos": cache_pos}
        x, new_cache = _scan_ssm(params, x, cfg, cache=cache,
                                 shared_ctx=shared_ctx, n_valid=n_valid)
        if cache is not None and "seq_lens" in cache:
            new_cache["seq_lens"] = cache_pos + (s if n_valid is None
                                                 else n_valid)
    else:
        x, lb, new_cache = _scan_decoder(
            params, x, cfg, positions=positions, causal=True,
            cache=cache, cache_pos=cache_pos, memory=memory,
            n_valid=n_valid if paged else None)
        aux["load_balance_loss"] = lb
        if paged:
            new_cache["seq_lens"] = cache_pos + (s if n_valid is None
                                                 else n_valid)

    x = apply_norm(params["final_norm"], x, cfg)
    logits = unembed(params["embed"], x, cfg, params.get("lm_head"))
    return logits, new_cache, aux


def encode(params: Params, frames: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Bidirectional encoder over precomputed frame embeddings."""
    enc = params["encoder"]
    x = frames.astype(cfg.activation_dtype)
    s = x.shape[1]
    positions = jnp.arange(s)
    if cfg.pos_embedding == "sinusoidal":
        x = x + sinusoidal_positions(positions, cfg.d_model
                                     ).astype(x.dtype)[None]

    def body(carry, lp):
        x, = carry
        h = apply_norm(lp["norm_attn"], x, cfg)
        a_out, _ = apply_attention(lp["attn"], h, cfg, positions=positions,
                                   causal=False)
        x = x + a_out
        h = apply_norm(lp["norm_ffn"], x, cfg)
        x = x + apply_ffn(lp["ffn"], h, cfg)
        return (x,), None

    if cfg.remat == "block":
        body = jax.checkpoint(body)
    (x,), _ = jax.lax.scan(body, (x,), enc["layers"])
    return apply_norm(enc["final_norm"], x, cfg)
