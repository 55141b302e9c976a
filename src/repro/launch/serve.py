"""Quantized serving launcher (the paper's deployment, batched).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2_5_3b --smoke \
        --batch 4 --prompt-len 16 --tokens 32 [--quant w8a8|w8|none]

Offline weight quantization (paper §5) → prefill via cache-writing steps →
batched greedy decode, reporting per-phase latency and tokens/s.
"""
import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_5_3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--quant", default="w8a8",
                    choices=["none", "w8", "w8a8"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs import get_config, get_smoke_config
    from repro.core.quantize_params import quantize_model_params
    from repro.models.transformer import init_model
    from repro.serving.cache import init_cache
    from repro.serving.engine import serve_step

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch)).replace(quant_proj=args.quant)
    params = init_model(jax.random.PRNGKey(0),
                        cfg.replace(quant_proj="none"))
    if args.quant != "none":
        params = quantize_model_params(params,
                                       quantize_experts=cfg.is_moe)
    max_len = args.prompt_len + args.tokens
    cache = init_cache(cfg, args.batch, max_len=max_len)
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)

    @jax.jit
    def step(cache, tok, pos):
        logits, cache = serve_step(params, cache, tok, pos, cfg)
        nxt = jnp.argmax(logits[:, -1, :], -1)[:, None].astype(tok.dtype)
        return cache, nxt

    t0 = time.perf_counter()
    for t in range(args.prompt_len):
        cache, tok = step(cache, prompts[:, t:t + 1],
                          jnp.asarray(t, jnp.int32))
    jax.block_until_ready(tok)
    t_prefill = time.perf_counter() - t0

    t0 = time.perf_counter()
    out = []
    for i in range(args.tokens):
        cache, tok = step(cache, tok,
                          jnp.asarray(args.prompt_len + i, jnp.int32))
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0

    tps = args.batch * args.tokens / t_decode
    print(f"arch={cfg.name} quant={args.quant} batch={args.batch}")
    print(f"prefill: {t_prefill:.2f}s   decode: {t_decode:.2f}s "
          f"({tps:.1f} tok/s)")
    print("sample:", jnp.concatenate(out, 1)[0].tolist()[:16])


if __name__ == "__main__":
    main()
