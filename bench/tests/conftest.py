"""Harness tests: CPU only, small shapes.  Run from the repository root:

    python -m pytest bench/tests
"""
import json
import os
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]
os.environ.setdefault("REPRO_KERNELS", "ref")

# a cell at a size a CPU test can hold: the qwen2.5 block at toy widths
TINY_CONFIG = {
    "name": "tiny", "model": "qwen2_5_3b",
    "overrides": {"quant_proj": "w8a8", "dtype": "bfloat16", "n_layers": 2,
                  "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                  "head_dim": 16, "d_ff": 128, "vocab_size": 256},
    "shapes": {"layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
               "head_dim": 16, "d_ff": 128, "vocab": 256,
               "tied_embeddings": True, "qkv_bias": True, "norm_eps": 1e-06,
               "rope_theta": 1000000.0, "rope_fraction": 1.0},
    "serving": {"slots": 4, "page_size": 16, "pool_pages": 64,
                "prefill_chunk": 32, "bucket": 32, "max_len": 512,
                "kv_dtype": "bfloat16"},
    "reference": "dense_gqa"}
TINY_TRAFFIC = {
    "name": "small", "loop": "open", "rate_per_s": 8.0, "preroll_s": 1.0,
    "prompt": {"median": 40, "sigma": 0.5, "min": 8, "max": 128},
    "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 32}}
# set from this size's readings (test_control): sound runs read up to
# about 0.03, the int4 control 0.4 and more
TINY_LIMIT = 0.15


def add_cell(root: pathlib.Path, name="tiny.small", config=None,
             traffic=None, limit=TINY_LIMIT) -> pathlib.Path:
    """Add a cell to the benchmark under ``root`` as new files and entries
    only, as a later change would."""
    config = config or TINY_CONFIG
    traffic = traffic or TINY_TRAFFIC
    bench = root / "bench"
    for sub in ("configs", "traffic", "limits"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    (bench / "configs" / f"{config['name']}.json").write_text(
        json.dumps(config))
    (bench / "traffic" / f"{traffic['name']}.json").write_text(
        json.dumps(traffic))
    (bench / "limits" / f"{name}.json").write_text(
        json.dumps({"max_logit_gap": {"limit": limit}}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": config["name"], "source": "test",
                            "file": f"bench/configs/{config['name']}.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": name, "config": config["name"],
                              "traffic": traffic["name"], "chips": 1,
                              "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return bench


@pytest.fixture
def bench_copy(tmp_path):
    """A copy of the benchmark (``BENCHMARK.json`` and ``bench/``)."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path


@pytest.fixture
def tiny(bench_copy):
    """``bench_copy`` with the tiny cell added."""
    add_cell(bench_copy)
    return bench_copy
