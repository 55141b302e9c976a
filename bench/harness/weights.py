"""Random weights from ``--seed``: the draws every architecture shares.

Each architecture module (``architectures/<name>.py``) builds its own
weights, in their served form, on the device in one jitted call, from
these pieces:

  * ``weight_seed``: the one JAX seed that ``--seed`` gives the weights;
  * ``int8_projection``: an int8 projection with an f32 scale per output
    channel (the configurations' w8a8).

No float master of a whole model is ever made.  The same call made again
gives the same arrays, which is how the plain reference gets its weights
once the program's state is freed: it takes nothing that the program made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.traffic import rng_for


def weight_seed(seed: int) -> int:
    """The 31-bit JAX seed of ``--seed``'s weights."""
    return int(rng_for(seed, 1).integers(0, 1 << 31))


def int8_projection(key_values: jax.Array, key_scale: jax.Array,
                    shape: tuple[int, int], std: float) -> dict:
    """A ``(k, n)`` projection as served: int8 values ``round(63.5 z)`` for
    ``z`` truncated-normal on [-2, 2], and per-output-channel f32 scales
    ``std * u / 63.5`` with ``u`` uniform on [0.75, 1.25]."""
    z = jax.random.truncated_normal(key_values, -2.0, 2.0, shape,
                                    jnp.float32)
    values = jnp.clip(jnp.round(z * 63.5), -127, 127).astype(jnp.int8)
    u = jax.random.uniform(key_scale, (shape[1],), jnp.float32, 0.75, 1.25)
    return {"values": values, "scale": std * u / 63.5}
