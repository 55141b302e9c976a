"""Random weights from ``--seed``, made on the device in their served form.

One jitted call builds every weight as it is served: int8 projections
with an f32 scale per output channel, f32 Q/K/V biases and norms, a bf16
embedding and (untied) a bf16 LM head.  No float master of a whole model
is ever made: the layers are built one after another (``lax.map``), so
the largest temporary is one projection's f32 draw.

The same call made again gives the same arrays, which is how the plain
reference gets its weights once the program's state is freed: it takes
nothing that the program made.

Draws (per layer, from ``fold_in(key, layer)``): int8 values
``round(63.5 z)`` for ``z`` truncated-normal on [-2, 2]; per-channel scales
``std * u / 63.5`` with ``u`` uniform on [0.75, 1.25] (``std`` the fan-in
init: in_dim^-1/2, and for O and down also / sqrt(layers)); biases
0.1 N(0, 1); norm weights 1 + 0.1 N(0, 1); embedding and LM head
N(0, 1) / sqrt(d_model).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness.costs import Shapes
from harness.traffic import rng_for

PROJ = ("wq", "wk", "wv", "wo", "gate", "up", "down")
ATTN = ("wq", "wk", "wv", "wo")
BIASED = ("wq", "wk", "wv")


def proj_shape(s: Shapes, name: str) -> tuple[int, int]:
    return {"wq": (s.d_model, s.q_dim), "wk": (s.d_model, s.kv_dim),
            "wv": (s.d_model, s.kv_dim), "wo": (s.q_dim, s.d_model),
            "gate": (s.d_model, s.d_ff), "up": (s.d_model, s.d_ff),
            "down": (s.d_ff, s.d_model)}[name]


def _std(s: Shapes, name: str) -> float:
    k = proj_shape(s, name)[0]
    return k ** -0.5 / (s.layers ** 0.5 if name in ("wo", "down") else 1.0)


def weight_seed(seed: int) -> int:
    """The 31-bit JAX seed of ``--seed``'s weights."""
    return int(rng_for(seed, 1).integers(0, 1 << 31))


def _layer(key: jax.Array, s: Shapes) -> dict:
    keys = iter(jax.random.split(key, 3 * len(PROJ) + 2))
    out = {}
    for name in PROJ:
        k, n = proj_shape(s, name)
        z = jax.random.truncated_normal(next(keys), -2.0, 2.0, (k, n),
                                        jnp.float32)
        values = jnp.clip(jnp.round(z * 63.5), -127, 127).astype(jnp.int8)
        u = jax.random.uniform(next(keys), (n,), jnp.float32, 0.75, 1.25)
        out[name] = {"values": values, "scale": _std(s, name) * u / 63.5}
        bias_key = next(keys)
        if s.qkv_bias and name in BIASED:
            out[name]["bias"] = 0.1 * jax.random.normal(bias_key, (n,))
    out["norm_attn"] = 1.0 + 0.1 * jax.random.normal(next(keys),
                                                     (s.d_model,))
    out["norm_ffn"] = 1.0 + 0.1 * jax.random.normal(next(keys),
                                                    (s.d_model,))
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _build(seed: jax.Array, s: Shapes) -> dict:
    key = jax.random.PRNGKey(seed)
    k_embed, k_head, k_norm, k_layers = jax.random.split(key, 4)
    layers = jax.lax.map(
        lambda i: _layer(jax.random.fold_in(k_layers, i), s),
        jnp.arange(s.layers))
    std = s.d_model ** -0.5
    w = {"layers": layers,
         "embed": (std * jax.random.normal(
             k_embed, (s.vocab, s.d_model))).astype(jnp.bfloat16),
         "final_norm": 1.0 + 0.1 * jax.random.normal(k_norm, (s.d_model,))}
    if not s.tied_embeddings:
        w["lm_head"] = (std * jax.random.normal(
            k_head, (s.vocab, s.d_model))).astype(jnp.bfloat16)
    return w


def build(seed: int, s: Shapes) -> dict:
    """Every weight of ``--seed`` as plain arrays (layer-stacked), on the
    default device."""
    return jax.block_until_ready(
        _build(np.int32(weight_seed(seed)), s))


def program_params(w: dict, s: Shapes) -> dict:
    """``w`` in the program's params layout (``QTensor`` projections);
    no array is copied."""
    from repro.core.quantization import QTensor

    lw = w["layers"]

    def linear(name):
        p = lw[name]
        out = {"w_q": QTensor(values=p["values"],
                              scale=p["scale"][:, None, :], bits=8)}
        if "bias" in p:
            out["b"] = p["bias"]
        return out

    params = {
        "embed": {"table": w["embed"]},
        "final_norm": {"w": w["final_norm"]},
        "layers": {
            "norm_attn": {"w": lw["norm_attn"]},
            "attn": {n: linear(n) for n in ATTN},
            "norm_ffn": {"w": lw["norm_ffn"]},
            "ffn": {n: linear(n) for n in ("gate", "up", "down")},
        },
    }
    if not s.tied_embeddings:
        params["lm_head"] = {"w": w["lm_head"]}
    return params
