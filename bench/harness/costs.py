"""Operations and bytes that the work needs, from a configuration's shapes.

Counted from ``shapes`` in ``configs/<config>.json`` alone, never from the
program: the least work the algorithm needs, so a program that pads,
recomputes or reads more than this shows as a lower share of the peak.

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
