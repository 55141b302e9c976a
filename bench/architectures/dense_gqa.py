"""The harness's part of a dense GQA decoder (Qwen2, ChatGLM3, ...).

What ``references/dense_gqa.py`` computes plainly, this module gives the
harness: the block's sizes, the check that the program runs the block
the configuration states, the seed's weights in their served form, and
the operations and bytes the work needs.  The block: RMSNorm, grouped
Q/K/V heads (optional QKV bias), O, a SwiGLU FFN (gate, up, down), every
projection w8a8; an embedding and, untied, an LM head.

Weights (``build``): one jitted call builds every weight as it is served
(int8 projections with an f32 scale per output channel, f32 Q/K/V biases
and norms, a bf16 embedding and, untied, a bf16 LM head), the layers one
after another (``lax.map``), so the largest temporary is one
projection's f32 draw.  Per layer, from ``fold_in(key, layer)``: each
projection's int8 draw (``harness.weights.int8_projection``) with ``std``
the fan-in init, in_dim^-1/2, and for O and down also / sqrt(layers);
biases 0.1 N(0, 1); norm weights 1 + 0.1 N(0, 1); embedding and LM head
N(0, 1) / sqrt(d_model).

Counts, from ``shapes`` alone and never from the program: the least work
the algorithm needs, so a program that pads, recomputes or reads more
shows as a lower share of the peak.

  * a token's matmul operations: 2 x the projection parameters;
  * its attention operations at its real context ``c`` (keys it
    attends, itself included): 4 x heads x head_dim x c per layer
    (QK^T and PV);
  * the LM head (2 x vocab x d_model) only for rows whose logits are
    used: a prefill's last prompt token, and each decode token;
  * no padding and no recomputation.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import int8_projection, weight_seed

PROJ = ("wq", "wk", "wv", "wo", "gate", "up", "down")
ATTN = ("wq", "wk", "wv", "wo")
BIASED = ("wq", "wk", "wv")


@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied_embeddings: bool
    qkv_bias: bool

    @classmethod
    def of(cls, config: dict) -> "Shapes":
        s = config["shapes"]
        return cls(**{f.name: s[f.name] for f in dataclasses.fields(cls)})

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


def check_config(cfg, config: dict) -> None:
    """Refuses a program config ``cfg`` whose block or widths differ from
    what ``config`` (``configs/<config>.json``) states."""
    s = config["shapes"]
    have = {"layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "d_ff": cfg.d_ff,
            "vocab": cfg.vocab_size, "tied_embeddings": cfg.tie_embeddings,
            "qkv_bias": cfg.qkv_bias, "norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta,
            "rope_fraction": (cfg.rope_fraction
                              if cfg.rope_style == "partial" else 1.0)}
    bad = {k: (v, s[k]) for k, v in have.items() if v != s[k]}
    if bad or cfg.ffn_type != "swiglu" or cfg.norm_type != "rmsnorm":
        raise ValueError(f"program config {cfg.name} differs from "
                         f"{config['name']}.json: {bad}")


# --- weights -------------------------------------------------------------

def proj_shape(s: Shapes, name: str) -> tuple[int, int]:
    return {"wq": (s.d_model, s.q_dim), "wk": (s.d_model, s.kv_dim),
            "wv": (s.d_model, s.kv_dim), "wo": (s.q_dim, s.d_model),
            "gate": (s.d_model, s.d_ff), "up": (s.d_model, s.d_ff),
            "down": (s.d_ff, s.d_model)}[name]


def _std(s: Shapes, name: str) -> float:
    k = proj_shape(s, name)[0]
    return k ** -0.5 / (s.layers ** 0.5 if name in ("wo", "down") else 1.0)


def _layer(key: jax.Array, s: Shapes) -> dict:
    keys = iter(jax.random.split(key, 3 * len(PROJ) + 2))
    out = {}
    for name in PROJ:
        out[name] = int8_projection(next(keys), next(keys),
                                    proj_shape(s, name), _std(s, name))
        bias_key = next(keys)
        if s.qkv_bias and name in BIASED:
            out[name]["bias"] = 0.1 * jax.random.normal(
                bias_key, (proj_shape(s, name)[1],))
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
    default device: what the reference reads."""
    return jax.block_until_ready(_build(np.int32(weight_seed(seed)), s))


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


# --- operations and bytes ------------------------------------------------

def projection_params(s: Shapes) -> int:
    """Projection weights of one layer: Q, K, V, O and a SwiGLU FFN."""
    return (s.d_model * (s.q_dim + 2 * s.kv_dim) + s.q_dim * s.d_model
            + 3 * s.d_model * s.d_ff)


def projection_channels(s: Shapes) -> int:
    """Output channels of one layer's projections (one scale each)."""
    return s.q_dim + 2 * s.kv_dim + s.d_model + 2 * s.d_ff + s.d_model


def head_ops(s: Shapes) -> int:
    return 2 * s.vocab * s.d_model


def token_ops(s: Shapes, ctx: int) -> int:
    """One token's forward through every layer, attending ``ctx`` keys,
    without the LM head."""
    return s.layers * (2 * projection_params(s) + 4 * s.q_dim * ctx)


def prefill_ops(s: Shapes, start: int, end: int) -> int:
    """Prefill of prompt positions ``start..end-1`` (positions before
    ``start`` already committed), with the one LM-head row used."""
    n = end - start
    keys = (end * (end + 1) - start * (start + 1)) // 2   # sum of contexts
    return (s.layers * (2 * projection_params(s) * n + 4 * s.q_dim * keys)
            + head_ops(s))


def decode_ops(s: Shapes, ctx: int) -> int:
    """One decode token attending ``ctx`` keys, LM head included."""
    return token_ops(s, ctx) + head_ops(s)


def weight_bytes(s: Shapes) -> int:
    """Every weight as served: int8 projections with an f32 scale per
    output channel, f32 Q/K/V biases and norms, the bf16 embedding, and
    a bf16 LM head when it is not the embedding."""
    per_layer = (projection_params(s) + 4 * projection_channels(s)
                 + (4 * (s.q_dim + 2 * s.kv_dim) if s.qkv_bias else 0)
                 + 4 * 2 * s.d_model)
    embed = 2 * s.vocab * s.d_model
    head = 0 if s.tied_embeddings else 2 * s.vocab * s.d_model
    return s.layers * per_layer + 4 * s.d_model + embed + head


def decode_weight_bytes(s: Shapes) -> int:
    """Weights a decode tick must read: all but the embedding rows it
    does not look up (the tied table is read whole as the LM head)."""
    if s.tied_embeddings:
        return weight_bytes(s)
    return weight_bytes(s) - 2 * s.vocab * s.d_model


def kv_bytes_per_token(s: Shapes, itemsize: int = 2) -> int:
    """K and V of one token over every layer (bf16 by default)."""
    return 2 * s.layers * s.kv_dim * itemsize


def decode_tick_bytes(s: Shapes, contexts) -> int:
    """Least HBM bytes of one decode tick whose live rows attend
    ``contexts`` keys each (their own new one included): the weights,
    each row's ``ctx - 1`` committed K/V rows read and its new row
    written."""
    return decode_weight_bytes(s) + kv_bytes_per_token(s) * sum(contexts)
