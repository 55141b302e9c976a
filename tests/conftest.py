import os
import sys

# tests run against the pure-jnp ref kernels by default (CPU); Pallas
# kernels are exercised explicitly with mode="pallas_interpret".
os.environ.setdefault("REPRO_KERNELS", "ref")
# NOTE: no xla_force_host_platform_device_count here — smoke tests and
# benches must see 1 device (multi-device sharding tests use subprocesses).

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "src"))


import pytest


@pytest.fixture
def cpu_subprocess():
    """``subprocess.run`` keywords for a child Python that must stay on the
    CPU: this environment plus ``JAX_PLATFORMS=cpu`` and ``src`` on the
    path, run from the repository root."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(REPO_ROOT, "src"), env.get("PYTHONPATH"))
        if p)
    return {"env": env, "cwd": REPO_ROOT}


@pytest.fixture
def tune_cache(tmp_path, monkeypatch):
    """Isolated autotuner cache: private file, 1 measurement iter, shipped
    seed table disabled (it covers the paper shapes several tests use to
    assert analytic fallback).  Shared by test_dispatch / test_fused_schedule."""
    from repro.core import dispatch

    path = tmp_path / "tune.json"
    monkeypatch.setenv(dispatch.CACHE_ENV, str(path))
    monkeypatch.setenv(dispatch.ITERS_ENV, "1")
    monkeypatch.setenv(dispatch.SEED_ENV, "0")
    dispatch.reset_cache_state()        # drop any in-process mirror
    yield path
    dispatch.reset_cache_state()
