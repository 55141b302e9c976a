"""Serving engine: prefill → decode handoff and the batched decode loop.

The serving architecture is documented in ``docs/DESIGN.md``; in short:

  * ``prefill`` runs the whole (right-padded) prompt batch through the
    cache-writing path — one pass, or fixed-size q-chunks (``chunk=``)
    that lower through the multi-query-row paged flash kernel for long
    prompts — committing prompt KV into the cache (dense rows or paged
    pools) and returning each sequence's next-token logits at its *own*
    last prompt position; a batch may mix prompt lengths, and
    ``start_pos`` starts past an already-committed (e.g. prefix-shared)
    context.
  * ``serve_step`` is one decode step: B new tokens against per-sequence
    contexts.  It is what the decode_32k / long_500k dry-run cells lower.
  * ``greedy_decode`` is the batched serving loop: a single jitted
    ``lax.scan`` over decode steps with the cache donated into the loop —
    one compile, no per-token Python dispatch, buffers updated in place.

All three take the cache dict from ``serving/cache.init_cache`` and work
with both layouts; per-sequence positions (``pos`` as a (B,) int32
vector) are what make mixed-length batches exact — prefill padding
garbage beyond a short prompt is masked until the decode loop overwrites
it, one slot per step (the overwrite-before-visible invariant,
``docs/DESIGN.md`` §2).
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.kernels.tiled_matmul.ops import replicated_operands
from repro.launch.sharding import activate_sharding
from repro.models.config import ModelConfig
from repro.models.transformer import apply_model
from repro.serving.cache import SERVING_RULES, CacheConfig, pool_page_size

Params = dict


@contextlib.contextmanager
def _mesh_context(mesh):
    """Sharding context for serving model calls: under a mesh the
    attention path routes paged KV through the shard_map'd partitioned
    schedules (``models/attention.py``) and activation annotations bind;
    without one this is a no-op.  ``SERVING_RULES`` pins the pool's page
    dim to the ``model`` axis so decode collectives and the shard-local
    allocator agree on the partitioning.  Serving holds params whole on
    every device, so the Pallas GEMM / quantization kernels run whole on
    each device (``replicated_operands``)."""
    if mesh is None:
        yield
        return
    with activate_sharding(mesh, SERVING_RULES), replicated_operands(mesh):
        yield


def prefill_step(params: Params, tokens: jax.Array, cfg: ModelConfig, *,
                 frontend_embeds=None, encoder_frames=None):
    """Cache-less forward pass producing logits for a prompt (no score
    materialization beyond the blockwise chunks).  Returns (logits, aux).
    This is the throughput-shape entry the prefill_32k dry-run cell
    lowers; the serving handoff that also *commits* KV is ``prefill``."""
    logits, _, aux = apply_model(params, tokens, cfg,
                                 frontend_embeds=frontend_embeds,
                                 encoder_frames=encoder_frames)
    return logits, aux


def validate_decode_cache(cache: dict, cfg: ModelConfig,
                          mode: str | None = None, *,
                          config: CacheConfig | None = None) -> None:
    """Fail loudly on cache layouts the decode path cannot execute.

    The serving loop donates the cache into a jitted scan — a layout the
    attention routing does not understand would not crash there, it would
    *silently compute garbage* (e.g. int8 pages without scale pools would
    be read as raw integers).  Every serving entry point calls this before
    touching the cache, so unsupported kernel-mode/layout/quant
    combinations raise a ``NotImplementedError`` naming the combo instead
    of producing a wrong-result path.  All checks are on dtypes and keys
    (static metadata), so the call is trace-safe and free.

    ``config`` (when given) is cross-checked against the cache it
    allegedly built: a ``CacheConfig`` that disagrees with the pytree's
    actual layout/quant would make the engine pick the wrong sharded
    routing for it.
    """
    if mode is None:
        from repro.kernels.tiled_matmul.ops import kernel_mode
        mode = kernel_mode()
    if config is not None:
        if (config.layout == "paged") != ("k_pages" in cache):
            raise ValueError(
                f"CacheConfig(layout={config.layout!r}) does not match "
                "this cache's layout — was it built with a different "
                "config?")
        if config.layout == "paged" and (
                (config.kv_quant == "int8") != ("k_scales" in cache)):
            raise ValueError(
                f"CacheConfig(kv_quant={config.kv_quant!r}) does not "
                "match this cache's page pools")
    if ("ssm_h" in cache) != (cfg.family in ("ssm", "hybrid")):
        # a family/cache mismatch would not crash — the ssm scan and the
        # attention scan would each happily trace the wrong state shapes
        got = "SSM slot state" if "ssm_h" in cache else "attention KV"
        raise ValueError(
            f"cache carries {got} but cfg.family is {cfg.family!r} — was "
            "it built with a different model config?")
    if "k_pages" in cache:
        kd, vd = cache["k_pages"].dtype, cache["v_pages"].dtype
        has_scales = "k_scales" in cache or "v_scales" in cache
        combo = (f"kernel_mode={mode!r}, layout='paged', "
                 f"kv dtype {kd}, kv_quant="
                 f"{'int8' if has_scales else 'none'}")
        if jnp.issubdtype(kd, jnp.integer) and not has_scales:
            raise NotImplementedError(
                f"unsupported decode cache combo ({combo}): integer KV "
                "pages need their k_scales/v_scales pools — build the "
                "cache with init_cache(..., kv_quant='int8')")
        if has_scales:
            if "k_scales" not in cache or "v_scales" not in cache:
                raise NotImplementedError(
                    f"unsupported decode cache combo ({combo}): the "
                    "quantized page layout needs BOTH k_scales and "
                    "v_scales")
            if kd != jnp.int8 or vd != jnp.int8:
                raise NotImplementedError(
                    f"unsupported decode cache combo ({combo}): scale "
                    "pools are present but the pages are not int8 — "
                    "kv_quant='int8' stores int8 pools")
    elif "k" in cache and jnp.issubdtype(cache["k"].dtype, jnp.integer):
        raise NotImplementedError(
            f"unsupported decode cache combo (kernel_mode={mode!r}, "
            f"layout='dense', kv dtype {cache['k'].dtype}): quantized KV "
            "is only implemented for the paged layout "
            "(init_cache(..., layout='paged', kv_quant='int8'))")


def cache_capacity(cache: dict) -> int | None:
    """Token capacity of a decode cache, or None for pure-SSM state
    (O(1) in context length — no positional capacity to exceed)."""
    if "k_pages" in cache:
        return (cache["page_table"].shape[1]
                * pool_page_size(cache["k_pages"]))
    if "k" in cache:
        return cache["k"].shape[2]
    if "shared_k" in cache:
        # hybrid (zamba2): the shared-attention sites carry the only
        # positional buffers — their S_max bounds the context
        return cache["shared_k"].shape[2]
    return None


@functools.partial(jax.jit, static_argnames=("cfg", "mode", "mesh"))
def _prefill_run(params, cache, prompts, prompt_lens, start_pos,
                 cfg: ModelConfig, mode: str, mesh=None):
    """Jitted prefill body for one pass or one chunk: one compile per
    (batch, width) shape.  ``start_pos`` rides in as a traced scalar so
    prefix-shared admissions forking at *any* prefix length — and every
    chunk of a chunked prefill — share the same executable; the
    scheduler's bucketed padding bounds the shape count, and admission
    ticks stop paying per-op eager dispatch for the whole model.
    ``mode`` (the live ``kernel_mode()``) only keys the jit cache, as in
    ``_greedy_run``.  Logits come back at row ``prompt_lens - 1 -
    start_pos`` (clamped into the block; the chunked caller keeps only
    rows that fall inside).  The cache is not donated: scheduler
    admissions prefill a slot *view* whose leaves the caller merges
    back."""
    b, s_pad = prompts.shape
    pos0 = jnp.broadcast_to(start_pos, (b,)).astype(jnp.int32)
    nv = (jnp.clip(prompt_lens - start_pos, 0, s_pad)
          if "ssm_h" in cache else None)
    with _mesh_context(mesh):
        logits, cache, _ = apply_model(params, prompts, cfg, cache=cache,
                                       cache_pos=pos0, n_valid=nv)
    rel = jnp.clip(prompt_lens - 1 - start_pos, 0, s_pad - 1)
    next_logits = jnp.take_along_axis(logits, rel[:, None, None],
                                      axis=1)[:, 0]
    return next_logits, cache


def prefill(params: Params, cache: dict, prompts: jax.Array,
            prompt_lens: jax.Array, cfg: ModelConfig, *,
            memory: jax.Array | None = None,
            chunk: int | None = None, start_pos: int = 0,
            config: CacheConfig | None = None):
    """Prefill → decode handoff: commit prompt KV, return first logits.

    prompts (B, S_pad) int32, right-padded to the longest prompt;
    prompt_lens (B,) int32 true lengths (may differ per sequence).  The
    whole padded batch runs through the cache-writing path at positions
    ``start_pos..start_pos+S_pad-1``, so every layer's K/V lands in the
    cache (pages for the paged layout).  Slots past ``prompt_lens[b]``
    hold padding garbage that decode masks per sequence until it
    overwrites them.

    ``chunk`` commits long prompts in fixed-size q-chunks instead of one
    pass: each chunk is a cache-writing step over positions already
    committed, which on a paged cache lowers through the multi-query-row
    paged flash kernel (``kernels/flash_attention/decode.py``) — a
    32k-class prompt streams pages chunk by chunk and never materializes
    a dense (S, T) attention problem.  One pass (``chunk=None``) remains
    the right call for serving-batch prompt sizes.

    ``start_pos > 0`` prefills a *suffix*: the first ``start_pos``
    positions are already committed (e.g. a prefix-shared admission,
    ``serving/allocator.fork_sequence``) and ``prompts`` holds the
    tokens from there on.  ``prompt_lens`` stays absolute (prefix +
    suffix).

    ``config`` (the cache's ``CacheConfig``) enables the sharded decode
    routing when it carries a mesh — required whenever the cache was
    built under one, or the eager prefill would fall back to the
    unpartitioned path and GSPMD would gather the pool.

    Returns (next_logits (B, V) — logits at each sequence's last real
    prompt token — and the updated cache with ``seq_lens = prompt_lens``
    for the paged layout).
    """
    b, s_pad = prompts.shape
    validate_decode_cache(cache, cfg, config=config)
    capacity = cache_capacity(cache)
    if capacity is not None and start_pos + s_pad > capacity:
        # past capacity the paged scatter would clamp to the last page and
        # silently corrupt it — fail loudly while shapes are still static
        raise ValueError(f"prompt width {start_pos + s_pad} exceeds cache "
                         f"capacity {capacity} tokens")
    prompt_lens = jnp.asarray(prompt_lens, jnp.int32)
    mesh = config.mesh if config is not None else None
    from repro.kernels.tiled_matmul.ops import kernel_mode
    # SSM state is a recurrence, not an addressed buffer: padded tails
    # can't be masked after the fact, so each row's valid-token count
    # rides into the model and zeroes dt at padded steps (decay 1,
    # contribution 0 — right-padding invisible to the state)
    is_ssm = "ssm_h" in cache
    # one pass, or chunk after chunk — each a cache-writing step
    width = s_pad if chunk is None else chunk
    next_logits = None
    for c0 in range(0, s_pad, width):
        cs = min(width, s_pad - c0)
        pos = start_pos + c0
        with TraceAnnotation("serving.prefill.chunk", start=pos, width=cs):
            if memory is None:
                got, cache = _prefill_run(
                    params, cache, prompts[:, c0:c0 + cs], prompt_lens,
                    jnp.asarray(pos, jnp.int32), cfg, kernel_mode(), mesh)
            else:
                # encoder-decoder: the cross-attention memory step runs
                # eagerly
                nv = jnp.clip(prompt_lens - pos, 0, cs) if is_ssm else None
                with _mesh_context(mesh):
                    logits, cache, _ = apply_model(
                        params, prompts[:, c0:c0 + cs], cfg, cache=cache,
                        cache_pos=jnp.full((b,), pos, jnp.int32),
                        memory=memory, n_valid=nv)
                got = jnp.take_along_axis(
                    logits, jnp.clip(prompt_lens - 1 - pos, 0,
                                     cs - 1)[:, None, None],
                    axis=1)[:, 0]
            # each sequence's last real prompt token lives in exactly one
            # chunk: harvest its logits as that chunk goes by
            rel = prompt_lens - 1 - pos
            inside = (rel >= 0) & (rel < cs)
            next_logits = (got if next_logits is None
                           else jnp.where(inside[:, None], got, next_logits))
    if "seq_lens" in cache:
        # padded tails were written but are NOT committed: visibility is
        # governed by seq_lens, and decode overwrites them slot by slot.
        # (copy, not alias: the cache is routinely donated downstream and
        # must not share a buffer with the caller's prompt_lens)
        cache["seq_lens"] = jnp.array(prompt_lens, jnp.int32, copy=True)
    return next_logits, cache


def serve_step(params: Params, cache: dict, tokens: jax.Array,
               pos: jax.Array | None, cfg: ModelConfig, *,
               memory: jax.Array | None = None,
               config: CacheConfig | None = None):
    """One decode step.

    tokens (B, 1) int32; pos is a scalar int32 (batch-synchronous, seed
    behaviour), a (B,) int32 vector of per-sequence lengths (mixed-length
    batches), or None to read the paged cache's own ``seq_lens``.

    Returns (logits (B, 1, V) f32, new_cache).  Attention lowers through
    the layout-matching schedule: dense caches use the masked dense path;
    paged caches use the paged flash-decode page walk when ``attn_impl``
    selects the flash engine (``auto`` + live Pallas kernels, or
    ``flash``), else the dense gather fallback.
    """
    validate_decode_cache(cache, cfg, config=config)
    if pos is None:
        if "seq_lens" not in cache:
            raise ValueError("pos=None requires a cache carrying seq_lens "
                             "(paged or SSM serving caches); plain dense "
                             "caches need an explicit pos")
        pos = cache["seq_lens"]
    with _mesh_context(config.mesh if config is not None else None):
        logits, new_cache, _ = apply_model(params, tokens, cfg, cache=cache,
                                           cache_pos=pos, memory=memory)
    return logits, new_cache


def greedy_decode(params: Params, cache: dict, first_token: jax.Array,
                  start_pos, n_steps: int, cfg: ModelConfig, *,
                  memory=None, config: CacheConfig | None = None):
    """Batched greedy serving loop: one jitted ``lax.scan`` over steps.

    first_token (B, 1) int32; start_pos is an int (batch-synchronous), a
    (B,) int32 vector of per-sequence lengths, or None to start from the
    paged cache's ``seq_lens``.  The cache is donated into the scan, so
    decode state is updated in place across all ``n_steps`` with a single
    compile and no per-token Python dispatch.

    Returns (tokens (B, n_steps + 1) — ``first_token`` followed by the
    greedy continuations — and the final cache).
    """
    from_cache_lens = start_pos is None
    if from_cache_lens and "seq_lens" not in cache:
        raise ValueError("start_pos=None requires a cache carrying "
                         "seq_lens (paged or SSM serving caches)")
    from repro.kernels.tiled_matmul.ops import kernel_mode
    # the donated-cache scan would otherwise *silently* mis-read an
    # unsupported layout (e.g. int8 pages without scales) — fail here
    validate_decode_cache(cache, cfg, kernel_mode(), config=config)
    pos_arg = jnp.asarray(0 if from_cache_lens else start_pos, jnp.int32)
    mesh = config.mesh if config is not None else None
    toks, cache = _greedy_run(params, cache, first_token, pos_arg, memory,
                              cfg, n_steps, from_cache_lens, kernel_mode(),
                              mesh)
    # (n_steps, B, 1) → (B, n_steps), oldest first
    seq = jnp.concatenate([first_token, jnp.swapaxes(toks[..., 0], 0, 1)],
                          axis=1)
    return seq, cache


def spec_step(params: Params, draft_params: Params, cache: dict,
              draft_cache: dict, tokens: jax.Array, budget_left: jax.Array,
              active: jax.Array, cfg: ModelConfig, draft_cfg: ModelConfig,
              *, n_draft: int, eos_id: int | None = None,
              config: CacheConfig | None = None):
    """One speculative draft-and-verify tick (``docs/DESIGN.md`` §8).

    ``tokens`` (B, 1) int32 — each live row's last emitted token;
    ``budget_left`` (B,) int32 — tokens each row may still emit;
    ``active`` (B,) bool.  The draft model proposes ``n_draft`` greedy
    tokens per row from its own dense cache, the target verifies all of
    them (plus the input token) in ONE forward pass through the paged
    flash schedule's n-token verify mode, and acceptance / rollback run
    in-engine: committed length advances by exactly the emitted count and
    every rejected row's page state is invalidated.

    Returns ``(pred (B, n_draft+1) int32 — the target's greedy token at
    every verify position, emitted = pred[b, :m[b]]; m (B,) int32 —
    emitted token counts; acc (B,) int32 — how many of the emitted
    tokens were draft proposals (``min(k, m)`` — when every draft
    matches, all ``m`` emitted tokens are accepted drafts); cache;
    draft_cache)``.  Both caches are donated.  Greedy outputs are
    bitwise equal to 1-token decode under the ``ref`` kernel mode (the
    kernel modes are argmax-stable in practice but carry no bitwise
    contract across q-block shapes).
    """
    validate_decode_cache(cache, cfg, config=config)
    from repro.kernels.tiled_matmul.ops import kernel_mode
    mesh = config.mesh if config is not None else None
    return _spec_run(params, draft_params, cache, draft_cache, tokens,
                     budget_left, jnp.asarray(active), cfg, draft_cfg,
                     n_draft, -1 if eos_id is None else int(eos_id),
                     kernel_mode(), mesh)


@functools.partial(jax.jit, donate_argnums=(1,),
                   static_argnames=("draft_cfg", "mode"))
def draft_prefill_row(draft_params, draft_cache, prompts, prompt_lens,
                      start_pos, slot, draft_cfg: ModelConfig, mode: str):
    """Commit a prompt into row ``slot`` of the dense draft cache as one
    jitted call (slice → prefill → merge fused; the slot index rides in
    as a traced scalar so every admission shares one executable per
    padded width).  ``prompts`` is (1, S_pad); the draft's logits are
    discarded — the first spec tick re-drafts from the target's first
    token.  The draft cache is donated: the scheduler owns it.  ``mode``
    (the live ``kernel_mode()``) keys the jit cache."""
    view = {key: jax.lax.dynamic_slice_in_dim(draft_cache[key], slot, 1,
                                              axis=1)
            for key in ("k", "v")}
    _, view = _prefill_run(draft_params, view, prompts, prompt_lens,
                           start_pos, draft_cfg, mode)
    return {key: jax.lax.dynamic_update_slice_in_dim(
                draft_cache[key], view[key], slot, axis=1)
            for key in ("k", "v")}


@functools.partial(jax.jit, donate_argnums=(2, 3),
                   static_argnames=("cfg", "draft_cfg", "n_draft", "eos_id",
                                    "mode", "mesh"))
def _spec_run(params, draft_params, cache, draft_cache, tok, budget_left,
              active, cfg: ModelConfig, draft_cfg: ModelConfig,
              n_draft: int, eos_id: int, mode: str, mesh=None):
    """Jitted body of ``spec_step`` — draft scan, one verify pass,
    in-engine acceptance with rollback.  Module-level jit for the same
    reasons as ``_greedy_run`` (its docstring); ``eos_id=-1`` means no
    EOS (token ids are non-negative).

    Acceptance math (greedy): with committed length ``c`` the verify
    input is ``[x0, d_1..d_n]`` at positions ``c..c+n``; ``pred[r]`` is
    the target's greedy token after position ``c+r``, so the drafts'
    leading agreement ``k = |{i: d_{i+1} == pred[i] for all j<=i}|``
    yields ``m = min(k+1, n)`` emitted tokens — capped at ``n`` (the
    full-accept bonus token is dropped: the draft cache only holds KV
    through position ``c+n-1``, so emitting ``n+1`` would desync it) —
    then capped by the first emitted EOS and by ``budget_left``.
    Rollback is ``seq_lens = c + m`` plus page-state invalidation of the
    rejected rows; pages never move.
    """
    from repro.serving.cache import invalidate_token_rows
    c = cache["seq_lens"]
    s = n_draft + 1

    with _mesh_context(mesh):
        def dstep(carry, t):
            dcache, dtok = carry
            lg, dcache = serve_step(draft_params, dcache, dtok, c + t,
                                    draft_cfg)
            nxt = jnp.argmax(lg[:, -1, :], axis=-1)[:, None].astype(
                jnp.int32)
            return (dcache, nxt), nxt

        (draft_cache, _), drafts = jax.lax.scan(
            dstep, (draft_cache, tok), jnp.arange(n_draft))
        drafts = jnp.swapaxes(drafts[..., 0], 0, 1)        # (B, n_draft)
        verify = jnp.concatenate([tok, drafts], axis=1)    # (B, S)
        n_valid = jnp.where(active, s, 0).astype(jnp.int32)
        logits, cache, _ = apply_model(params, verify, cfg, cache=cache,
                                       cache_pos=c, n_valid=n_valid)

    pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (B, S)
    match = (pred[:, :n_draft] == drafts).astype(jnp.int32)
    k = jnp.sum(jnp.cumprod(match, axis=1), axis=1)        # leading agrees
    m = jnp.minimum(k + 1, n_draft) if n_draft else jnp.ones_like(k)
    eos_hit = pred == eos_id
    m = jnp.where(jnp.any(eos_hit, axis=1),
                  jnp.minimum(m, jnp.argmax(eos_hit, axis=1) + 1), m)
    m = jnp.minimum(m, budget_left)
    m = jnp.where(active, m, 0).astype(jnp.int32)

    # rollback: rewind seq_lens and invalidate the written-but-rejected
    # rows (every PAGE_STATE_KEYS array — scales travel with their pages)
    row = jnp.arange(s)[None, :]
    rej = (row >= m[:, None]) & (row < n_valid[:, None])
    cache = invalidate_token_rows(cache, c[:, None] + row, rej)
    cache["seq_lens"] = jnp.where(active, c + m, 0).astype(jnp.int32)
    return pred, m, jnp.minimum(k, m).astype(jnp.int32), cache, draft_cache


@functools.partial(jax.jit, donate_argnums=(1,),
                   static_argnames=("cfg", "n_steps", "from_cache_lens",
                                    "mode", "mesh"))
def _greedy_run(params, cache, tok, pos_arg, memory, cfg: ModelConfig,
                n_steps: int, from_cache_lens: bool, mode: str,
                mesh=None):
    """Module-level jitted scan so repeated ``greedy_decode`` calls hit
    the jit cache (a closure-jitted loop would re-trace — and re-compile
    the whole n_steps scan — on every call).  ``mode`` (the live
    ``kernel_mode()``) only keys the cache: attention routing reads the
    env at trace time, so without it a REPRO_KERNELS change mid-process
    would silently replay the previously-traced path.  ``mesh`` is a
    static operand for the same reason — the sharded attention routing is
    a trace-time decision, and a ``Mesh`` is hashable — and the sharding
    context is (re)entered *inside* so the trace never depends on ambient
    contextvar state it isn't keyed on."""

    def step(carry, _):
        cache, tok, pos = carry
        logits, cache = serve_step(params, cache, tok, pos, cfg,
                                   memory=memory)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(tok.dtype)
        return (cache, nxt, pos + 1), nxt

    # read start positions from the donated cache itself — passing
    # seq_lens as a separate operand would alias the donated buffer
    pos0 = cache["seq_lens"] if from_cache_lens else pos_arg
    with _mesh_context(mesh):
        (cache, _, _), toks = jax.lax.scan(step, (cache, tok, pos0),
                                           length=n_steps)
    return toks, cache
