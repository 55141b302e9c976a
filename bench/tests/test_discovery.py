"""Configurations, mixes, limits and metric readers are found by name."""
import json
import subprocess
import sys

import pytest
from conftest import BENCH, ROOT, add_cell

from harness import spec


def test_every_cell_of_the_benchmark_loads():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["name"] == w["traffic"]
        assert isinstance(cell.limits["max_logit_gap"]["limit"], float)
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.metric_reader(BENCH, m["name"]))
        assert hasattr(spec.reference_module(cell), "readings")


def test_benchmark_files_are_where_it_says():
    bench = spec.load_benchmark()
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert {m["moves"] for m in bench["per_layer"]} <= {
        m["name"] for m in bench["end_to_end"]}


def test_a_cell_added_as_files_only_is_found(tiny):
    """A new configuration, mix, limit and per-layer metric, added as new
    files and entries, load through the same loader."""
    (tiny / "bench" / "metrics" / "sched.ticks.py").write_text(
        "def read(run):\n    return float(len(run.window.ticks))\n")
    spec_path = tiny / "BENCHMARK.json"
    b = json.loads(spec_path.read_text())
    b["per_layer"].append({"name": "sched.ticks", "unit": "ticks",
                           "better": "higher", "source": "program_counter",
                           "layer": "scheduler", "moves": "output_tok_per_s",
                           "workloads": ["tiny.small"]})
    spec_path.write_text(json.dumps(b))
    cell = spec.load_cell("tiny.small", tiny, tiny / "bench")
    assert cell.config["serving"]["slots"] == 4
    assert cell.traffic["rate_per_s"] == 8.0
    assert "sched.ticks" in [m["name"] for m in cell.per_layer]
    reader = spec.metric_reader(tiny / "bench", "sched.ticks")

    class W:
        ticks = [1, 2, 3]

    class R:
        window = W()

    assert reader(R()) == 3.0
    # cells that are not listed do not get the new metric
    first = spec.load_benchmark(tiny)["workloads"][0]["name"]
    assert "sched.ticks" not in [
        m["name"] for m in spec.load_cell(first, tiny, tiny / "bench")
        .per_layer]


def test_unknown_names_are_errors(tiny):
    with pytest.raises(KeyError):
        spec.load_cell("no.such.cell", tiny, tiny / "bench")
    with pytest.raises(KeyError):
        spec.peaks(tiny / "bench", "TPU v99")
    assert spec.peaks(BENCH, "TPU v5 lite")["int8_ops_per_s"] == 393e12


def test_run_refuses_a_machine_without_a_tpu():
    """JAX's first device is the CPU here: the command fails and prints
    no result line."""
    cell = spec.load_benchmark()["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_refuses_a_tree_without_the_program(bench_copy):
    """A directory with only ``BENCHMARK.json`` and ``bench/``: no
    result."""
    cell = spec.load_benchmark()["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=bench_copy, capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_add_cell_helper_is_files_only(bench_copy):
    before = {p for p in (bench_copy / "bench").rglob("*") if p.is_file()}
    add_cell(bench_copy)
    after = {p for p in (bench_copy / "bench").rglob("*") if p.is_file()}
    assert before <= after
