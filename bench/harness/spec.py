"""Find a cell's files by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files behind those names sit in directories of their own, so a new
configuration, mix, cell or per-layer metric is added as new files and
entries only:

  configs/<config>.json      sizes, serving geometry, source, reference
  references/<module>.py     the configuration's plain reference forward
  traffic/<mix>.json         loop, rate or clients, length distributions
  limits/<cell>.json         the limit of each number ``correct`` compares
  metrics/<metric>.py        one reader per per-layer metric
  peaks.json                 the chip's peaks, keyed by ``device_kind``
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent.parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple       # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple
    bench_dir: pathlib.Path


def read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return read_json(pathlib.Path(root) / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT,
              bench_dir: pathlib.Path | None = None) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files loaded.
    ``bench_dir`` is where the data files live (default: ``bench/`` beside
    this package)."""
    bench_dir = pathlib.Path(bench_dir or BENCH_DIR)
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = read_json(bench_dir / "configs" / f"{w['config']}.json")
    if config["name"] != w["config"] or w["config"] not in configs:
        raise ValueError(f"configuration {w['config']!r} is not the one "
                         f"its file names ({config['name']!r})")
    traffic = read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = read_json(bench_dir / "limits" / f"{name}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        limits=limits,
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
        bench_dir=bench_dir)


def _load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(bench_dir: pathlib.Path, name: str):
    """``metrics/<name>.py``'s ``read(run) -> float | None``."""
    path = pathlib.Path(bench_dir) / "metrics" / f"{name}.py"
    return _load_module(path, f"bench_metric_{name}").read


def reference_module(cell: Cell):
    """The plain reference the configuration names
    (``references/<reference>.py``)."""
    ref = cell.config["reference"]
    return _load_module(cell.bench_dir / "references" / f"{ref}.py",
                        f"bench_reference_{ref}")


def peaks(bench_dir: pathlib.Path, device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    table = read_json(pathlib.Path(bench_dir) / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
