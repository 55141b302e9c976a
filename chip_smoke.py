#!/usr/bin/env python3
"""Serve qwen2.5-3B (w8a8) end to end on a TPU chip, and check the result.

    python chip_smoke.py              # one chip: kernel checks + serving
    python chip_smoke.py --chips 4    # mesh-sharded serving vs one device

Phases, in one process.  Any failure exits non-zero, and then no result
line is printed.

  device   A TPU backend with the compiled Pallas kernels live
           (``kernel_mode() == "pallas"``).  There is no CPU or
           interpreter fallback.
  kernels  The paper's GEMM (64x768 . 768x3072) through ``tiled_matmul``
           and DistilBERT's fused Q/K/V projection, bitwise equal to the
           ``ref`` path on the same chip.  The flash prefill kernel and
           the paged flash kernel (bf16 and int8 pools; one decode step
           and one 256-row prefill chunk) within ``KERNEL_RTOL`` of their
           jnp oracles.
  serving  qwen2.5-3B at its published widths (36 layers, w8a8
           projections, bf16 activations and paged KV), random weights
           from ``--seed``, served through ``Scheduler``: 8 requests of
           128-1024 prompt tokens (two share a 256-token prefix), 32 new
           tokens each, 4 slots, a pool small enough that one admission
           waits.  Run twice: the first run compiles, the second is
           timed.  Each request's prefill logits are checked against a
           cache-free ``apply_model`` forward of its prompt under the
           ``ref`` kernels, within ``LOGIT_RTOL``.
  mesh     ``--chips 4`` only, and nothing else: the same requests with
           the KV pool sharded over a 4-device ``model`` mesh, then on one
           device.  The w8a8 bf16 model's prefill logits must agree within
           ``LOGIT_RTOL``; in f32 with weight-only int8 every greedy token
           must be equal (``check_mesh``).

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The script imports ``src/repro`` from the directory it lives in.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import warnings

import numpy as np

ARCH = "qwen2_5_3b"
SLOTS = 4
PAGE_SIZE = 64
POOL_PAGES = 40           # < the 45 pages the second wave asks for
PREFILL_CHUNK = 256
NEW_TOKENS = 32
PROMPT_LENS = (1024, 128, 512, 384, 768, 256, 640, 896)
SHARED_PREFIX = (2, 3, 256)   # request 3 opens with request 2's first 256
# Served vs reference prefill logits, as max |diff| over max |ref|.  Two
# correct bf16 forwards of the w8a8 model differ by this much: bf16
# rounding in a different order (flash/paged vs dense attention) moves
# activations by a few ulps, and per-row int8 activation quantization
# turns some of those into whole quantization steps.  On the CPU, at
# 12 layers x 1024 wide, served vs reference spread 3e-2 to 5e-2, and
# each of them sat 4e-2 to 5.5e-2 from an f32-activation forward; in f32
# the two agree to 1e-6.  A layout fault reads far above it: KV heads
# swapped in one layer of 36 read 0.24 to 1.6 on the CPU (512 wide), and
# ``check_serving`` reads such a fault on every run.
LOGIT_RTOL = 8e-2
KERNEL_RTOL = 2e-2        # attention kernels vs their oracles (bf16 out)


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def kernels(mode: str):
    """Trace under ``REPRO_KERNELS=mode`` (kernel routing is read while
    tracing, so whatever is compiled inside keeps that mode)."""
    prev = os.environ.get("REPRO_KERNELS")
    os.environ["REPRO_KERNELS"] = mode
    try:
        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message="REPRO_KERNELS=")
            yield
    finally:
        if prev is None:
            os.environ.pop("REPRO_KERNELS")
        else:
            os.environ["REPRO_KERNELS"] = prev


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def check_kernels(mode: str = "pallas", seed: int = 0) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.quantization import quantize, quantize_kv
    from repro.kernels.flash_attention.ops import (flash_attention,
                                                   paged_decode_attention)
    from repro.kernels.fused_qkv.ops import fused_qkv
    from repro.kernels.tiled_matmul.ops import tiled_matmul

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def normal(shape, dtype=jnp.float32):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    def both(fn, *args):
        return [np.asarray(jax.jit(lambda *a, m=m: fn(*a, mode=m))(*args))
                for m in (mode, "ref")]

    # the paper's GEMM: per-row activation and per-column weight scales.
    # No bias: the bitwise contract covers the int32 product and the
    # scale epilogue, while a bias add may contract into an FMA in one
    # program and not the other (kernels/tiled_matmul/ref.py)
    a = quantize(normal((64, 768)), channel_axes=(0,))
    b = quantize(normal((768, 3072)), channel_axes=(1,))
    got, want = both(tiled_matmul, a, b)
    check(np.array_equal(got, want),
          f"tiled_matmul (64,768)x(768,3072) differs from ref: "
          f"rel err {rel_err(got, want):.3e}")
    log("kernels: tiled_matmul (64,768)x(768,3072) bitwise equal to ref")

    bert = get_config("distilbert_paper")
    d = bert.d_model
    ws = [quantize(normal((d, d)), channel_axes=(1,)) for _ in range(3)]
    x = quantize(normal((64, d)), channel_axes=(0,))
    got, want = (np.stack(o) for o in both(fused_qkv, x, *ws))
    check(np.array_equal(got, want), "fused_qkv differs from ref")
    log(f"kernels: fused_qkv 64x{d} -> {d}+{d}+{d} bitwise equal to ref")

    qwen = get_config(ARCH)
    h, kh, hd = qwen.n_heads, qwen.n_kv_heads, qwen.head_dim
    q = normal((1, 1024, h, hd), jnp.bfloat16)
    k = normal((1, 1024, kh, hd), jnp.bfloat16)
    v = normal((1, 1024, kh, hd), jnp.bfloat16)
    got, want = both(flash_attention, q, k, v)
    err = rel_err(got, want)
    check(err <= KERNEL_RTOL, f"flash prefill S=1024 rel err {err:.3e}")
    log(f"kernels: flash prefill S=1024 H={h} KVH={kh} rel err {err:.3e}")

    max_pages = 17
    n_pages = 4 * max_pages + 1
    table = jax.random.permutation(next(keys), n_pages)[:4 * max_pages]
    table = table.reshape(4, max_pages).astype(jnp.int32)
    pools = normal((2, n_pages, kh, PAGE_SIZE, hd))
    for quant in ("none", "int8"):
        if quant == "int8":
            (kp, ks), (vp, vs) = quantize_kv(pools[0]), quantize_kv(pools[1])
        else:
            kp, vp = pools.astype(jnp.bfloat16)
            ks = vs = None
        for rows, lens, chunk in ((1, [1056, 300, 64, 1], None),
                                  (PREFILL_CHUNK, [700], 128)):
            n = len(lens)
            qq = normal((n, rows, h, hd), jnp.bfloat16)

            def paged(qq, kp, vp, table, lens, ks, vs, mode, chunk=chunk):
                return paged_decode_attention(
                    qq, kp, vp, table, lens, q_chunk=chunk, k_scales=ks,
                    v_scales=vs, mode=mode)

            got, want = both(paged, qq, kp, vp, table[:n],
                             jnp.asarray(lens, jnp.int32), ks, vs)
            err = rel_err(got, want)
            check(err <= KERNEL_RTOL,
                  f"paged kernel ({quant} KV, {rows} rows) rel err {err:.3e}")
            log(f"kernels: paged flash {quant} KV, {n}x{rows} rows, page "
                f"{PAGE_SIZE}: rel err {err:.3e}")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def make_params(cfg, seed: int):
    """Random qwen2.5 weights from ``seed``: int8 projections (per output
    channel), a bf16 tied embedding, f32 norms and biases.  One jitted
    program, so the float masters are never all resident at once."""
    import jax

    from repro.core.quantize_params import quantize_model_params
    from repro.models.transformer import init_model

    def build(key):
        params = init_model(key, cfg)
        params["embed"]["table"] = params["embed"]["table"].astype(
            cfg.activation_dtype)
        return quantize_model_params(params)

    return jax.block_until_ready(jax.jit(build)(jax.random.PRNGKey(seed)))


def make_prompts(vocab: int, seed: int, lens=PROMPT_LENS,
                 shared=SHARED_PREFIX) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, n).astype(np.int32) for n in lens]
    parent, child, n = shared
    prompts[child][:n] = prompts[parent][:n]
    return prompts


def serve(params, cfg, prompts, *, mesh=None, page_size=PAGE_SIZE,
          pool_pages=POOL_PAGES, chunk=PREFILL_CHUNK,
          new_tokens=NEW_TOKENS):
    """One ``Scheduler`` run over ``prompts``; returns the scheduler (its
    ``first_logits`` and ``seconds`` recorded), the tokens by request id
    and the wall seconds."""
    import jax
    import jax.numpy as jnp

    from repro.serving.cache import CacheConfig
    from repro.serving.scheduler import Scheduler

    class Recording(Scheduler):
        """Keeps each admission's prefill logits (admissions are FIFO, so
        the k-th is request k's) and the wall time of prefill and
        decode."""

        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            self.first_logits = []
            self.seconds = {"prefill": 0.0, "decode": 0.0}

        def _prefill_slot(self, b, prompt, start):
            t0 = time.perf_counter()
            logits = jax.block_until_ready(
                super()._prefill_slot(b, prompt, start))
            self.seconds["prefill"] += time.perf_counter() - t0
            self.first_logits.append(logits)
            return logits

        def _decode(self):
            t0 = time.perf_counter()
            super()._decode()               # ends on a host copy of tokens
            self.seconds["decode"] += time.perf_counter() - t0

    longest = max(p.size for p in prompts) + new_tokens
    max_len = -(-longest // page_size) * page_size
    config = CacheConfig(layout="paged", alloc="dynamic",
                         page_size=page_size, pool_pages=pool_pages,
                         mesh=mesh)
    sched = Recording(params, cfg, slots=SLOTS, max_len=max_len,
                      config=config, prefill_chunk=chunk,
                      dtype=jnp.dtype(cfg.dtype))
    for p in prompts:
        sched.submit(p, new_tokens)
    t0 = time.perf_counter()
    out = sched.run(max_ticks=50 * len(prompts) * new_tokens)
    return sched, out, time.perf_counter() - t0


def reference_logits(cfg, prompts, *param_sets) -> list[list[np.ndarray]]:
    """For each params in ``param_sets``, the last-token logits of a
    cache-free ``apply_model`` forward of each prompt under the ``ref``
    kernels (right-padded to one width: causal attention keeps the
    padding invisible).  One compile serves every param set."""
    import jax
    import jax.numpy as jnp

    from repro.models.transformer import apply_model

    def forward(params, toks, last):
        logits, _, _ = apply_model(params, toks, cfg)
        return logits[0, last]

    width = max(p.size for p in prompts)
    with kernels("ref"):
        fwd = jax.jit(forward).lower(
            param_sets[0], jnp.zeros((1, width), jnp.int32),
            jnp.asarray(0, jnp.int32)).compile()
    out = []
    for params in param_sets:
        out.append([])
        for p in prompts:
            toks = np.zeros((1, width), np.int32)
            toks[0, :p.size] = p
            out[-1].append(np.asarray(fwd(params, jnp.asarray(toks),
                                          jnp.asarray(p.size - 1,
                                                      jnp.int32))))
    return out


def swap_kv_heads(params, cfg, layer: int | None = None):
    """``params`` with the K/V projections' output heads in reverse order,
    in every layer or only in ``layer``.  A forward of these is what a
    served forward reads when its head-major pool holds each KV head's
    rows under another head (query group g then attends to KV head
    KVH-1-g): the layout fault the logit tolerance has to catch."""
    import jax

    kh, hd = cfg.n_kv_heads, cfg.head_dim

    def flip(x):        # (L, ..., KVH * hd): reverse the head blocks
        y = x.reshape(*x.shape[:-1], kh, hd)[..., ::-1, :].reshape(x.shape)
        return y if layer is None else x.at[layer].set(y[layer])

    attn = dict(params["layers"]["attn"])
    for name in ("wk", "wv"):
        attn[name] = jax.tree.map(flip, attn[name])
    return dict(params, layers=dict(params["layers"], attn=attn))


def compare_logits(label: str, got, want) -> list[float]:
    """Prefill logits ``got`` against ``want``, request by request.  Fails
    when the rel err of any exceeds ``LOGIT_RTOL``, or when a first token
    (the argmax) differs where ``want``'s top-2 margin is wider than the
    largest logit difference: noise closes such a gap only with
    near-largest differences of opposite sign on both top entries.
    Returns the rel errs."""
    errs, agree, decided = [], 0, 0
    for rid, (g, w) in enumerate(zip(got, want)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        errs.append(rel_err(g, w))
        top2 = np.sort(w)[-2:]
        same = int(np.argmax(g)) == int(np.argmax(w))
        agree += same
        if top2[1] - top2[0] > np.max(np.abs(g - w)):
            decided += 1
            check(same, f"{label}: request {rid}'s first token differs "
                  f"though the top-2 margin {top2[1] - top2[0]:.4g} exceeds "
                  f"the logit difference {np.max(np.abs(g - w)):.4g}")
    log(f"{label}: max rel err {max(errs):.6e} (tolerance {LOGIT_RTOL:g}; "
        f"per request {', '.join(f'{e:.3e}' for e in errs)}); first token "
        f"agrees {agree}/{len(errs)} ({decided} decided by their margin)")
    check(max(errs) <= LOGIT_RTOL,
          f"{label}: rel err {max(errs):.3e} > {LOGIT_RTOL:g}")
    return errs


def check_serving(cfg, seed: int, **serve_kw) -> None:
    import jax

    t0 = time.perf_counter()
    params = make_params(cfg, seed)
    setup = time.perf_counter() - t0
    log(f"serving: {cfg.name} {cfg.n_layers} layers d_model {cfg.d_model} "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads} d_ff {cfg.d_ff} vocab "
        f"{cfg.vocab_size} quant {cfg.quant_proj} dtype {cfg.dtype}; "
        f"params built in {setup:.2f} s")
    prompts = make_prompts(cfg.vocab_size, seed, **{
        k: serve_kw.pop(k) for k in ("lens", "shared") if k in serve_kw})

    cold, cold_out, cold_wall = serve(params, cfg, prompts, **serve_kw)
    log(f"serving: first run (compiles) {cold_wall:.2f} s")
    warm, out, wall = serve(params, cfg, prompts, **serve_kw)
    check(sorted(out) == list(range(len(prompts))),
          f"finished requests {sorted(out)}")
    for rid in out:
        check(np.array_equal(out[rid], cold_out[rid]),
              f"request {rid}: second run's tokens differ from the first's")
    n_tok = sum(len(t) for t in out.values())
    _, total, _ = warm.handler.occupancy(warm.cache)
    log(f"serving: second run {wall:.2f} s: prefill "
        f"{warm.seconds['prefill']:.2f} s, decode "
        f"{warm.seconds['decode']:.2f} s over {warm._ticks} ticks; "
        f"{len(out)} requests, {n_tok} tokens emitted; peak pool "
        f"{max(warm.occupancy_log)}/{total} pages")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"serving: peak_bytes_in_use {stats['peak_bytes_in_use']}")

    for rid in out:
        check(int(out[rid][0]) == int(np.argmax(np.asarray(
            warm.first_logits[rid]))),
            f"request {rid}: first token is not its prefill argmax")
    mid = cfg.n_layers // 2
    refs, *faults = reference_logits(
        cfg, prompts, params, swap_kv_heads(params, cfg, mid),
        swap_kv_heads(params, cfg))
    sound = compare_logits("serving: prefill logits vs cache-free ref "
                           "forward", warm.first_logits, refs)
    # the limit has to separate sound runs from a layout fault: read the
    # served logits against references whose KV heads are swapped
    for name, bad in zip((f"layer {mid}", "every layer"), faults):
        errs = [rel_err(g, w) for g, w in zip(warm.first_logits, bad)]
        log(f"serving: planted fault, KV heads swapped in {name}: rel err "
            f"min {min(errs):.6e} max {max(errs):.6e} (sound max "
            f"{max(sound):.6e}, tolerance {LOGIT_RTOL:g})")
        check(min(errs) > LOGIT_RTOL,
              f"a KV-head swap in {name} reads {min(errs):.3e}, within "
              f"the tolerance {LOGIT_RTOL:g}")


def serve_mesh_and_one(cfg, params, prompts, n: int, **serve_kw):
    """Serve ``prompts`` with the KV pool over an ``n``-device ``model``
    mesh and params replicated on it, then on one device.  Checks that
    params and pool land on every mesh device; returns both schedulers
    and both token maps."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.launch.mesh import make_serving_mesh

    mesh = make_serving_mesh(n)
    devices = set(mesh.devices.flat)
    mparams = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
    check(all(leaf.sharding.device_set == devices
              for leaf in jax.tree.leaves(mparams)),
          "params are not on every mesh device")
    sharded, got, wall = serve(mparams, cfg, prompts, mesh=mesh, **serve_kw)
    for key in ("k_pages", "v_pages"):
        sh = sharded.cache[key].sharding
        check(sh.device_set == devices and not sh.is_fully_replicated,
              f"{key} is not partitioned over the mesh: {sh}")
    policy = sharded.config.resolved_kv_shard(cfg.n_kv_heads)
    log(f"mesh: {cfg.name} {cfg.n_layers} layers, quant {cfg.quant_proj}, "
        f"dtype {cfg.dtype}; {n} devices, KV policy {policy!r}, params "
        f"replicated on all, pool partitioned; served in {wall:.2f} s")
    del mparams
    one, want, wall = serve(params, cfg, prompts, **serve_kw)
    log(f"mesh: one device served in {wall:.2f} s")
    check(sorted(got) == sorted(want) == list(range(len(prompts))),
          f"finished requests: sharded {sorted(got)}, one device "
          f"{sorted(want)}")
    return sharded, got, one, want


def check_mesh(cfg, seed: int, n: int, **serve_kw) -> None:
    """Mesh-sharded serving against one device, twice.

    1. The configured model (w8a8, bf16).  Under the mesh the Pallas
       GEMM, fused QKV and activation-quant kernels run whole on each
       device, and attention runs the mesh's partitioned path (for
       qwen2.5's 2 KV heads on 4 devices, the pool split over its pages).
       Served prefill logits are held to the one-device run's as
       ``compare_logits`` holds them to the reference.  Greedy tokens are
       not compared: attention sums in another order under the mesh,
       which is correct, but in bf16 with int8 activations the last-bit
       differences flip random-weight argmaxes within a few tokens.
    2. The same model in f32 with weight-only int8 (``quant_proj="w8"``)
       at full matmul precision, where the two runs differ only by f32
       rounding: every greedy token must be equal.
    """
    import jax

    lens = {k: serve_kw.pop(k) for k in ("lens", "shared") if k in serve_kw}
    prompts = make_prompts(cfg.vocab_size, seed, **lens)
    params = make_params(cfg, seed)
    sharded, _, one, _ = serve_mesh_and_one(cfg, params, prompts, n,
                                            **serve_kw)
    compare_logits(f"mesh: {n}-device prefill logits vs one device",
                   sharded.first_logits, one.first_logits)
    del params, sharded, one

    cfg = cfg.replace(quant_proj="w8", dtype="float32")
    params = make_params(cfg, seed)
    with jax.default_matmul_precision("highest"):
        _, got, _, want = serve_mesh_and_one(cfg, params, prompts, n,
                                             **serve_kw)
    same = [rid for rid in want if np.array_equal(got[rid], want[rid])]
    log(f"mesh: greedy tokens equal for {len(same)}/{len(want)} requests")
    check(len(same) == len(want), f"sharded tokens differ for requests "
          f"{sorted(set(want) - set(same))}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-sharded serving check")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SmokeFailure(f"no repro package under {src}")
    sys.path.insert(0, src)
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    from repro.configs import get_config
    from repro.kernels.tiled_matmul.ops import kernel_mode

    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu", f"no TPU: JAX's first device is "
          f"{dev.platform!r}")
    check(len(devices) >= args.chips,
          f"{args.chips} chips asked for, {len(devices)} found")
    mode = kernel_mode()
    check(mode == "pallas", f"kernel mode {mode!r}, not 'pallas'")
    log(f"device: {dev.device_kind} x{len(devices)} ({dev.platform}); "
        f"kernel mode {mode}; compile cache {cache_dir}")

    cfg = get_config(ARCH).replace(quant_proj="w8a8", dtype="bfloat16")
    if args.chips == 4:
        check(len(devices) == 4, f"--chips 4 needs 4 devices, not "
              f"{len(devices)}")
        check_mesh(cfg, args.seed, 4)
    else:
        check_kernels(seed=args.seed)
        check_serving(cfg, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
