"""Mesh construction: production (16×16 / 2×16×16), host, and serving.

FUNCTIONS, not module-level constants — importing this module never
touches jax device state (the dry-run sets the host-device count before
any jax initialization; see dryrun.py).

Every axis is ``Auto``: the model code annotates activations with
``with_sharding_constraint`` and lets GSPMD propagate the rest.  Under
``jax.make_mesh``'s default ``Explicit`` axes sharding is part of each
array's type, and ops such as the embedding gather then refuse inputs
whose output sharding is ambiguous.
"""
from __future__ import annotations

import jax


def _make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = data or (n // model)
    return _make_mesh((data, model), ("data", "model"))


def make_serving_mesh(model: int):
    """Single-axis ``("model",)`` mesh over the first ``model`` devices —
    the shape the serving stack expects (``CacheConfig(mesh=...)``): the
    paged pool, the per-shard allocator, and the shard_map'd decode all
    partition over exactly this axis.  On CPU, simulate devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before
    jax import)."""
    import numpy as np
    devs = jax.devices()
    if model > len(devs):
        raise ValueError(
            f"serving mesh needs {model} devices; only {len(devs)} "
            "available (on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count)")
    return jax.sharding.Mesh(np.asarray(devs[:model]), ("model",))
