"""Benchmark harness: one module per paper table/figure.

  gemm_paper_shapes — Table 2 (GEMM latency/throughput ladder)
  tile_sweep        — §5 tile-size DSE (T∈{16,32,64} → block shapes)
  vmem_budget       — Table 1 (resource utilization → VMEM/MXU budget)
  quant_accuracy    — §6.2/§7 (accuracy deviation, confidence agreement)
  qkv_end2end       — §6.2(2) (DistilBERT QKV-offload scenario)
  partial_tile      — §5 (fractional-tile overhead)
  persistence       — §4.2 (update_A amortization via fused QKV)
  flash_attention   — beyond-paper: block-sparse KV schedule counters
  decode            — beyond-paper: paged-KV decode engine (ms/token,
                      pages touched dense vs paged)
  serving           — beyond-paper: continuous vs static batching under
                      a mixed-arrival trace (tok/s, pool occupancy)

Host wall-times off the chip are ordering-only: a CPU run measures the
XLA CPU backend or the Pallas interpreter, never the TPU.
"""
from __future__ import annotations

import sys
import time

MODULES = [
    "gemm_paper_shapes",
    "tile_sweep",
    "vmem_budget",
    "quant_accuracy",
    "qkv_end2end",
    "partial_tile",
    "persistence",
    "flash_attention",
    "decode",
    "serving",
]


def main() -> None:
    rest = sys.argv[1:]
    only = None
    if rest and not rest[0].startswith("-"):
        only = rest.pop(0)
        if only not in MODULES:
            sys.exit(f"unknown benchmark module {only!r}; "
                     f"choose from {', '.join(MODULES)}")
    # strip the selector but forward flags (--smoke/--json) to the modules'
    # own argparse (benchmarks.common.bench_options)
    sys.argv = sys.argv[:1] + rest
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for name in MODULES:
        if only and only != name:
            continue
        mod = __import__(f"benchmarks.{name}", fromlist=["main"])
        t0 = time.time()
        mod.main()
        print(f"[{name} done in {time.time() - t0:.1f}s]")


if __name__ == "__main__":
    main()
