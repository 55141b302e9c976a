"""Plain reference: a dense GQA decoder with w8a8 projections, in float32.

Straightforward ``jax.numpy`` at ``HIGHEST`` matmul precision, with no
kernel, page, batch or cache layout of the program: it imports nothing
from ``src/repro`` and is given only the seed's weights as plain arrays
(``build`` of ``architectures/dense_gqa.py``).  It follows the published
block:

  x = embed[token]
  per layer:  x += O(attn(rope(Q(n1(x))), rope(K(n1(x))), V(n1(x))))
              x += down(silu(gate(n2(x))) * up(n2(x)))
  logits = n_f(x) @ head^T          (head = embed when tied)

with RMSNorm, causal softmax attention scaled by head_dim^-1/2, grouped
K/V heads, and RoPE on the first ``rope_fraction`` of each head's dims.
Each projection is the configuration's w8a8: the input row quantized to
int8 by its absmax (round half to even, +-127), an exact int32 product
with the int8 weight, then row scale x channel scale, plus the bias.

Departures, none of which changes the function of the given weights:
RoPE rotates adjacent pairs of dims (2i, 2i+1), as ChatGLM does; Qwen2's
published form rotates (i, i + d/2), which is the same model under a
fixed permutation of each head's Q/K output columns, and random weights
have no preferred column order.

The sequence runs in blocks of ``CHUNK`` rows through every layer with an
f32 K/V cache of its own, so a long sequence fits beside the weights.

``variant`` ``"w4"`` computes the same function at the precision of the
control: projections requantized to int4 per output channel, the
precision below the configuration's int8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 512
HI = jax.lax.Precision.HIGHEST
VARIANTS = ("exact", "w4")


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _quant(x, qmax, axis=-1):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax <= 1e-12, 1.0, amax / qmax)
    return jnp.clip(jnp.round(x / scale), -qmax, qmax), scale


def _linear(x, p, variant):
    """w8a8 projection of rows ``x`` (C, K) by ``p`` (values (K, N) int8,
    scale (N,), bias (N,)?)."""
    values, scale = p["values"], p["scale"]
    if variant == "w4":
        w = values.astype(jnp.float32) * scale
        v4, s4 = _quant(w, 7, axis=0)
        values, scale = v4.astype(jnp.int8), s4[0]
    xq, xs = _quant(x, 127)
    acc = jax.lax.dot_general(xq.astype(jnp.int8), values,
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * (xs * scale[None, :])
    return y + p["bias"] if "bias" in p else y


def _rope(x, pos, frac, theta):
    """x (C, heads, hd); rotate adjacent pairs of the first ``frac`` dims."""
    rot = int(x.shape[-1] * frac)
    rot -= rot % 2
    freq = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos.astype(jnp.float32)[:, None, None] * freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    r = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([r.reshape(*x.shape[:-1], rot), x[..., rot:]], -1)


@functools.partial(jax.jit, static_argnames=("shape", "variant"),
                   donate_argnums=(3,))
def _chunk(layers, embed, tokens, kv, start, *, shape, variant):
    """Rows ``start .. start+CHUNK-1`` through every layer; returns the
    final hidden rows (C, D) and the updated (k, v) caches."""
    layers_n, d, h, kvh, hd, eps, frac, theta = shape
    g = h // kvh
    c = tokens.shape[0]
    pos = start + jnp.arange(c)
    x = embed[tokens].astype(jnp.float32)
    k_all, v_all = kv
    keys = jnp.arange(k_all.shape[1])
    allowed = keys[None, :] <= pos[:, None]                  # (C, S)

    def layer(x, xs):
        p, kc, vc = xs
        a = _rmsnorm(x, p["norm_attn"], eps)
        q = _linear(a, p["wq"], variant).reshape(c, h, hd)
        k = _linear(a, p["wk"], variant).reshape(c, kvh, hd)
        v = _linear(a, p["wv"], variant).reshape(c, kvh, hd)
        q, k = _rope(q, pos, frac, theta), _rope(k, pos, frac, theta)
        kc = jax.lax.dynamic_update_slice(kc, k, (start, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v, (start, 0, 0))
        s = jnp.einsum("ckgd,skd->kgcs", q.reshape(c, kvh, g, hd), kc,
                       precision=HI) * hd ** -0.5
        s = jnp.where(allowed[None, None], s, -jnp.inf)
        o = jnp.einsum("kgcs,skd->ckgd", jax.nn.softmax(s, -1), vc,
                       precision=HI).reshape(c, h * hd)
        x = x + _linear(o, p["wo"], variant)
        f = _rmsnorm(x, p["norm_ffn"], eps)
        f = (jax.nn.silu(_linear(f, p["gate"], variant))
             * _linear(f, p["up"], variant))
        return x + _linear(f, p["down"], variant), (kc, vc)

    x, (k_all, v_all) = jax.lax.scan(layer, x, (layers, k_all, v_all))
    return x, (k_all, v_all)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, probes, *, eps):
    """Logits of rows ``x``: each row's max, argmax and its logits at
    ``probes`` (C, P) token ids."""
    logits = jnp.einsum("cd,vd->cv", _rmsnorm(x, norm, eps),
                        head.astype(jnp.float32), precision=HI)
    return (jnp.max(logits, -1), jnp.argmax(logits, -1),
            jnp.take_along_axis(logits, probes, -1))


def _shape_key(shapes: dict) -> tuple:
    return (shapes["layers"], shapes["d_model"], shapes["n_heads"],
            shapes["n_kv_heads"], shapes["head_dim"], shapes["norm_eps"],
            shapes["rope_fraction"], shapes["rope_theta"])


def _run(weights, shapes, max_len, tokens, checked, probes, variant):
    """One sequence through the reference.  ``checked`` are the positions
    whose logits are read, ``probes`` (len(checked), P) the token ids read
    at each.  Returns (max, argmax, probe logits) at ``checked``."""
    shape = _shape_key(shapes)
    n_pad = -(-tokens.size // CHUNK) * CHUNK
    if n_pad > max_len:
        raise ValueError(f"sequence of {tokens.size} tokens is past the "
                         f"reference's {max_len}")
    toks = np.zeros(n_pad, np.int32)
    toks[:tokens.size] = tokens
    cache = (shapes["layers"], max_len, shapes["n_kv_heads"],
             shapes["head_dim"])
    kv = (jnp.zeros(cache, jnp.float32), jnp.zeros(cache, jnp.float32))
    head = weights["lm_head"] if "lm_head" in weights else weights["embed"]
    out_max, out_arg, out_probe = [], [], []
    for c0 in range(0, n_pad, CHUNK):
        x, kv = _chunk(weights["layers"], weights["embed"],
                       jnp.asarray(toks[c0:c0 + CHUNK]), kv,
                       jnp.asarray(c0, jnp.int32), shape=shape,
                       variant=variant)
        rows = [i for i, p in enumerate(checked) if c0 <= p < c0 + CHUNK]
        if not rows:
            continue
        pr = np.zeros((CHUNK, probes.shape[1]), np.int32)
        pr[[checked[i] - c0 for i in rows]] = probes[rows]
        mx, am, pl = _head(x, weights["final_norm"], head, jnp.asarray(pr),
                           eps=shapes["norm_eps"])
        sel = np.asarray([checked[i] - c0 for i in rows])
        out_max.append(np.asarray(mx)[sel])
        out_arg.append(np.asarray(am)[sel])
        out_probe.append(np.asarray(pl)[sel])
    return (np.concatenate(out_max), np.concatenate(out_arg),
            np.concatenate(out_probe))


def readings(weights, shapes: dict, max_len: int, seqs, controls=()):
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over every served token of ``seqs``, a list of
    (prompt int32 array, served int32 array).  With ``controls`` (variant
    names), also each control's widest gap: at the same positions, the
    gap of the token that control puts first.

    Returns {"served": gap, "tokens": n served tokens read,
    "per_request": [gap, ...], <control>: gap, ...}."""
    out = {"served": 0.0, "tokens": 0, "per_request": []}
    out.update({v: 0.0 for v in controls})
    for prompt, served in seqs:
        tokens = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        checked = list(range(prompt.size - 1, tokens.size))
        nxt = served.astype(np.int32)[:, None]
        firsts = [_run(weights, shapes, max_len, tokens, checked, nxt,
                       v)[1] for v in controls]
        probes = np.concatenate([nxt] + [f[:, None] for f in firsts], 1)
        mx, _, pl = _run(weights, shapes, max_len, tokens, checked, probes,
                         "exact")
        gaps = mx[:, None] - pl
        out["per_request"].append(float(gaps[:, 0].max()))
        out["served"] = max(out["served"], float(gaps[:, 0].max()))
        out["tokens"] += int(served.size)
        for j, v in enumerate(controls):
            out[v] = max(out[v], float(gaps[:, 1 + j].max()))
    return out
