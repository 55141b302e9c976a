"""GEMM dispatch: one plan-selection + partial-tile policy for every hot path.

The paper's accelerator picks its tile size (T=32) from a one-off DSE sweep
measured on hardware (§5 "Tile size selection"); FTRANS and later FPGA work
show the same lesson — analytic models get you the right *neighbourhood*,
measurement picks the winner.  This module is the TPU analogue: every
quantized GEMM in the repo (``quantized_matmul``, ``fused_qkv``,
``quantized_linear``) routes its block-shape choice through ``select_plan``,
which layers an *empirical autotuner* with a persistent JSON cache on top of
the analytic ``choose_plan`` model.

Modes (env var ``REPRO_TUNE``):

  * ``off``    — pure analytic ``choose_plan`` (the seed behaviour).
  * ``cached`` — default: use a measured plan if the persistent cache has one
                 for this (M, K, N, dtype) key, else fall back to the
                 analytic plan.  Never measures, never writes.
  * ``full``   — on a cache miss, *measure* the candidate plans with real
                 kernel executions on the current backend, store the winner
                 in the cache, and use it from then on.

The user cache is the file ``$REPRO_TUNE_CACHE`` names, read and written
only when that variable is set (``full`` requires it) — unset, plan
selection reads nothing outside the checkout; measured entries are keyed by
``MxKxN:dtype:backend`` (tuning on one backend never clobbers or shadows
another's winners) and the unqualified ``MxKxN:dtype`` key is the
hand-shipped-table escape hatch, trusted on any backend — a tuned serving
container ships its table as a plain JSON artifact.  A seeded table for the
paper shapes ships with the package (``src/repro/core/gemm_tune.json``) and
is merged underneath the user cache (disable with ``REPRO_TUNE_SEED=0``).

Schedules are first-class in the plan (``Schedule``: ``panel`` holds the
whole contraction resident per invocation — the paper's persistent-A
schedule; ``k_split`` streams K slabs through carried accumulators).  The
fused QKV projection has its own key family ``MxKxNq+Nkv:dtype[:backend]``
— the (Nq, Nkv) output split changes the winning schedule (GQA shrinks the
K/V sweep), so it is part of the key, and entries record the measured
``schedule``.  ``select_fused_plan`` falls back to the legacy single-GEMM
``MxKxNq`` key when no fused key matches, so pre-extension tables keep
working.

Partial tiles: the dispatcher's policy is **no host-side padding** on the
Pallas path — edge blocks are handled natively in-kernel (iota masks on the
contraction dim, out-of-bounds stores dropped by Pallas).  ``padded_shape``
and ``pad_overhead`` remain available for the benchmarks that quantify what
the old zero-pad policy cost.

Plan selection happens at Python trace time (shapes are static under jit),
so ``REPRO_TUNE`` changes require re-tracing (new process or cleared jit
cache) to take effect.
"""
from __future__ import annotations

import dataclasses
import enum
import json
import os
import tempfile
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tiling import (MXU_DIM, VMEM_PLAN_BUDGET, TilePlan,
                               ceil_div, choose_plan, round_up,
                               vmem_tile_bytes)

__all__ = [
    "Schedule",
    "FusedPlan",
    "select_plan",
    "select_fused_plan",
    "select_fused_blocks",
    "candidate_plans",
    "fused_candidate_plans",
    "tune",
    "tune_fused",
    "tune_mode",
    "cache_path",
    "seed_table_path",
    "load_cache",
    "clear_cache",
    "reset_cache_state",
    "padded_shape",
    "pad_overhead",
]

TUNE_ENV = "REPRO_TUNE"
CACHE_ENV = "REPRO_TUNE_CACHE"
ITERS_ENV = "REPRO_TUNE_ITERS"
SEED_ENV = "REPRO_TUNE_SEED"
_VALID_MODES = ("off", "cached", "full")


class Schedule(str, enum.Enum):
    """Contraction schedule of a GEMM plan (first-class in dispatch).

    ``PANEL`` — block_k spans the full K: the activation panel stays resident
    in VMEM across the whole weight sweep (the paper's persistent-A /
    ``update_A`` schedule).  ``K_SPLIT`` — K is streamed in block_k slabs
    through carried int32 accumulators (paper §8 double-buffered streaming),
    trading weight residency for a bounded footprint.  str-valued so it
    serialises directly into the JSON tune cache and compares equal to
    ``TilePlan.schedule``.
    """
    PANEL = "panel"
    K_SPLIT = "k_split"


def plan_schedule(plan: TilePlan) -> Schedule:
    return Schedule.PANEL if plan.k_steps == 1 else Schedule.K_SPLIT


@dataclasses.dataclass(frozen=True)
class FusedPlan:
    """Dispatch plan for the fused QKV kernel: blocks + explicit schedule.

    ``block_k == k`` under ``Schedule.PANEL``; under ``Schedule.K_SPLIT`` it
    is the contraction slab streamed through the three accumulators.
    """
    m: int
    k: int
    nq: int
    nkv: int
    block_m: int
    block_n: int
    block_k: int
    schedule: Schedule

    @property
    def k_steps(self) -> int:
        return ceil_div(self.k, self.block_k)

    def footprint(self, out_bytes: int = 2) -> int:
        return _fused_qkv_footprint(
            self.block_m, self.block_n, self.k, out_bytes,
            block_k=None if self.schedule is Schedule.PANEL
            else self.block_k)

    def fits_vmem(self, budget: int = VMEM_PLAN_BUDGET,
                  out_bytes: int = 2) -> bool:
        return self.footprint(out_bytes) <= budget


# in-process mirror of the JSON file, so repeated trace-time lookups do not
# re-read the file for every matmul in a model
_mem_cache: dict[str, dict] | None = None
_mem_cache_file: tuple[str, bool] | None = None


def tune_mode() -> str:
    mode = os.environ.get(TUNE_ENV, "cached")
    if mode not in _VALID_MODES:
        raise ValueError(
            f"{TUNE_ENV} must be one of {_VALID_MODES}, got {mode!r}")
    if mode == "full" and cache_path() is None:
        raise ValueError(f"{TUNE_ENV}=full needs {CACHE_ENV}: the file the "
                         "measured plans are written to")
    return mode


def cache_path() -> str | None:
    """The user tune cache, or None when ``REPRO_TUNE_CACHE`` is unset."""
    return os.environ.get(CACHE_ENV) or None


def seed_table_path() -> str:
    """The tuned table shipped with the package (the paper shapes)."""
    return os.path.join(os.path.dirname(__file__), "gemm_tune.json")


def _seed_enabled() -> bool:
    return os.environ.get(SEED_ENV, "1").lower() not in ("0", "off", "false")


def _key(m: int, k: int, n: int, out_dtype, backend: str | None = None) -> str:
    """Cache key.  Measured entries are backend-qualified so tuning on one
    backend can never clobber (or shadow) another backend's winners; the
    unqualified key is reserved for hand-shipped tables, trusted anywhere."""
    base = f"{m}x{k}x{n}:{jnp.dtype(out_dtype).name}"
    return f"{base}:{backend}" if backend else base


def _fused_key(m: int, k: int, nq: int, nkv: int, out_dtype,
               backend: str | None = None) -> str:
    """Fused-QKV key: the (Nq, Nkv) output split is part of the identity —
    GQA shrinks the K/V sweep, which changes the winning schedule."""
    base = f"{m}x{k}x{nq}+{nkv}:{jnp.dtype(out_dtype).name}"
    return f"{base}:{backend}" if backend else base


def _read_table(path: str) -> dict[str, dict]:
    try:
        with open(path) as f:
            raw = json.load(f)
        if isinstance(raw, dict):
            return {k: v for k, v in raw.items() if isinstance(v, dict)}
    except (OSError, ValueError):
        pass                       # missing or corrupt cache = empty table
    return {}


def load_cache() -> dict[str, dict]:
    """User cache merged over the shipped seed table (user entries win)."""
    global _mem_cache, _mem_cache_file
    path = cache_path()
    state = (path, _seed_enabled())
    if _mem_cache is not None and _mem_cache_file == state:
        return _mem_cache
    table = _read_table(seed_table_path()) if _seed_enabled() else {}
    if path is not None:
        table.update(_read_table(path))
    _mem_cache = table
    _mem_cache_file = state
    return table


def _store(key: str, entry: dict) -> None:
    """Read-merge-write so concurrent tuners lose at most their own entry."""
    global _mem_cache, _mem_cache_file
    path = cache_path()
    if path is None:
        raise ValueError(f"tuning writes to {CACHE_ENV}, which is unset")
    table = _read_table(path)      # persist only user entries, not the seed
    table[key] = entry
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(table, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
    _mem_cache = None              # next lookup re-merges seed + user
    _mem_cache_file = None


def reset_cache_state() -> None:
    """Drop the in-process cache mirror (file untouched).

    Call after changing ``REPRO_TUNE_CACHE`` mid-process (tests, benchmarks)
    so the next lookup re-reads the new file.
    """
    global _mem_cache, _mem_cache_file
    _mem_cache = None
    _mem_cache_file = None


def clear_cache() -> None:
    reset_cache_state()
    path = cache_path()
    if path is None:
        return
    try:
        os.unlink(path)
    except OSError:
        pass


def _plan_from_entry(m: int, k: int, n: int, out_bytes: int,
                     entry: dict) -> TilePlan | None:
    try:
        plan = TilePlan(m, k, n, block_m=int(entry["block_m"]),
                        block_n=int(entry["block_n"]),
                        # hand-shipped panel-resident entries may omit
                        # block_k; full K is what panel-resident means
                        block_k=int(entry.get("block_k", k)),
                        out_bytes=out_bytes)
    except (KeyError, TypeError, ValueError):
        return None
    # hold cached (possibly hand-shipped / version-skewed) entries to the
    # same VMEM budget the tuner's own candidates are generated under
    return plan if plan.fits_vmem(VMEM_PLAN_BUDGET) else None


def _measurement_backend(interpret: bool | None) -> str:
    if interpret or (interpret is None and jax.default_backend() != "tpu"):
        return "interpret"
    return jax.default_backend()


# ---------------------------------------------------------------------------
# Candidate generation — the analytic model seeds the search space
# ---------------------------------------------------------------------------
def candidate_plans(m: int, k: int, n: int, *, out_bytes: int = 2,
                    vmem_budget: int = VMEM_PLAN_BUDGET,
                    max_candidates: int = 8) -> list[TilePlan]:
    """Feasible TilePlans around the analytic pick, analytic pick first.

    This is the paper's T∈{16,32,64} sweep generalised: block_m/block_n vary
    over MXU multiples (plus the sublane-aligned small-M panel), block_k over
    {K} ∪ power-of-two splits.  Everything returned fits the VMEM budget.
    """
    seed = choose_plan(m, k, n, out_bytes=out_bytes, vmem_budget=vmem_budget)
    m_cap = round_up(m, 8) if m < MXU_DIM else round_up(m, MXU_DIM)
    n_cap = round_up(n, MXU_DIM)

    bms = sorted({min(b, m_cap) for b in (128, 256, 512)})
    bns = sorted({min(b, n_cap) for b in (128, 256, 512)})
    bks = [k] + [bk for bk in (2048, 1024, 512, 256) if bk < k]

    plans: list[TilePlan] = [seed]
    seen = {(seed.block_m, seed.block_n, seed.block_k)}
    for bk in bks:
        for bm in bms:
            for bn in bns:
                if (bm, bn, bk) in seen:
                    continue
                plan = TilePlan(m, k, n, block_m=bm, block_n=bn, block_k=bk,
                                out_bytes=out_bytes)
                if not plan.fits_vmem(vmem_budget):
                    continue
                seen.add((bm, bn, bk))
                plans.append(plan)
    # rank non-seed candidates by the analytic estimate so a small
    # max_candidates still measures the most promising schedules
    head, tail = plans[:1], plans[1:]
    tail.sort(key=lambda p: p.time_estimate(int8=True))
    return (head + tail)[:max_candidates]


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------
def _measure_plan(m: int, k: int, n: int, plan: TilePlan, out_dtype,
                  interpret: bool, iters: int) -> float:
    """Median wall-clock of the real kernel under ``plan`` (seconds)."""
    from repro.kernels.tiled_matmul.kernel import tiled_matmul_kernel

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(-127, 128, (m, k), dtype=np.int8))
    b = jnp.asarray(rng.integers(-127, 128, (k, n), dtype=np.int8))
    sa = jnp.ones((m, 1), jnp.float32)
    sb = jnp.ones((1, n), jnp.float32)

    block_k = None if plan.k_steps == 1 else plan.block_k
    fn = jax.jit(lambda av, bv: tiled_matmul_kernel(
        av, sa, bv, sb, None, block_m=plan.block_m, block_n=plan.block_n,
        block_k=block_k, out_dtype=out_dtype, interpret=interpret))
    jax.block_until_ready(fn(a, b))            # compile + warm
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(a, b))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def tune(m: int, k: int, n: int, *, out_dtype=jnp.bfloat16,
         interpret: bool | None = None, iters: int | None = None,
         max_candidates: int = 8,
         results: list | None = None) -> TilePlan:
    """Measure candidate plans for (M, K, N), persist and return the winner.

    ``interpret`` defaults to True off-TPU so tuning works in this container;
    interpreter timings still rank *schedules* (grid shape, K-split depth)
    even though absolute numbers are host-bound.  Pass ``results`` to
    receive every ``(plan, seconds)`` measurement from this single pass
    (benchmarks report them; the winner is consistent by construction).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if iters is None:
        iters = int(os.environ.get(ITERS_ENV, "3"))
    out_bytes = jnp.dtype(out_dtype).itemsize
    backend = _measurement_backend(interpret)
    best_plan, best_t = None, float("inf")
    n_results = 0
    for plan in candidate_plans(m, k, n, out_bytes=out_bytes,
                                max_candidates=max_candidates):
        t = _measure_plan(m, k, n, plan, out_dtype, interpret, iters)
        n_results += 1
        if results is not None:
            results.append((plan, t))
        if t < best_t:
            best_plan, best_t = plan, t
    assert best_plan is not None
    _store(_key(m, k, n, out_dtype, backend), {
        "block_m": best_plan.block_m,
        "block_n": best_plan.block_n,
        "block_k": best_plan.block_k,
        "schedule": plan_schedule(best_plan).value,
        "us": best_t * 1e6,
        "backend": backend,
        "candidates": n_results,
    })
    return best_plan


# ---------------------------------------------------------------------------
# The dispatch entry point
# ---------------------------------------------------------------------------
def select_plan(m: int, k: int, n: int, *, out_dtype=jnp.bfloat16,
                interpret: bool | None = None) -> TilePlan:
    """Plan for C[M,N] = A[M,K] @ B[K,N]: tuned if available, analytic else.

    This is the single funnel every quantized GEMM goes through; callers
    never call ``choose_plan`` directly on a hot path.
    """
    out_bytes = jnp.dtype(out_dtype).itemsize
    mode = tune_mode()
    if mode == "off":
        return choose_plan(m, k, n, out_bytes=out_bytes)
    # a plan measured on a different backend ranks a different machine's
    # schedules (interpret timings are host-bound), so measured entries are
    # keyed per backend; the unqualified key is the hand-shipped-table
    # escape hatch, trusted on any backend
    table = load_cache()
    backend = _measurement_backend(interpret)
    for key in (_key(m, k, n, out_dtype, backend),
                _key(m, k, n, out_dtype)):
        entry = table.get(key)
        if entry is not None:
            plan = _plan_from_entry(m, k, n, out_bytes, entry)
            if plan is not None:
                return plan
    if mode == "full":
        try:
            return tune(m, k, n, out_dtype=out_dtype, interpret=interpret)
        except Exception as e:
            # on the chip a kernel that fails to compile or run is a bug
            # to surface; elsewhere measurement is advisory
            if backend == "tpu":
                raise
            warnings.warn(
                f"REPRO_TUNE=full: measurement for ({m},{k},{n}) failed "
                f"({type(e).__name__}: {e}); using the analytic plan")
            return choose_plan(m, k, n, out_bytes=out_bytes)
    return choose_plan(m, k, n, out_bytes=out_bytes)


def _fused_qkv_footprint(bm: int, bn: int, k: int, out_bytes: int,
                         block_k: int | None = None) -> int:
    """VMEM bytes of the fused QKV kernel under either schedule.

    Every block is double-buffered by the grid pipeline and padded to the
    VMEM tiling (``tiling.vmem_tile_bytes``).  Panel (``block_k is
    None``): the A panel (bm, K), three weight blocks (K, bn) and three
    outputs.  K-split: the A slab (bm, bk) and weight slabs (bk, bn), plus
    three int32 accumulators carried across the K sweep.  Both hold one
    int32 product and its f32 dequant as live temporaries.
    """
    bk = k if block_k is None or block_k >= k else block_k
    blocks = (vmem_tile_bytes(bm, bk, 1) + 3 * vmem_tile_bytes(bk, bn, 1)
              + 3 * vmem_tile_bytes(bm, bn, out_bytes)
              + vmem_tile_bytes(bm, 1, 4) + 3 * vmem_tile_bytes(1, bn, 4))
    acc = vmem_tile_bytes(bm, bn, 4)
    scratch = 3 * acc if bk < k else 0
    return 2 * blocks + 2 * acc + scratch


def _block_caps(m: int, n: int) -> tuple[int, int]:
    m_cap = round_up(m, 8) if m < MXU_DIM else round_up(m, MXU_DIM)
    return m_cap, round_up(n, MXU_DIM)


def _analytic_fused_plan(m: int, k: int, nq: int, nkv: int, *,
                         out_bytes: int,
                         vmem_budget: int) -> FusedPlan:
    """The paper's DSE for the fused kernel: prefer the largest
    panel-resident blocks that fit; K-split only when no panel does."""
    m_cap, n_cap = _block_caps(m, max(nq, nkv))
    for bm in (512, 256, 128):
        for bn in (512, 256, 128):
            bm2, bn2 = min(bm, m_cap), min(bn, n_cap)
            if _fused_qkv_footprint(bm2, bn2, k, out_bytes) <= vmem_budget:
                return FusedPlan(m, k, nq, nkv, bm2, bn2, k, Schedule.PANEL)
    for bk in (2048, 1024, 512, 256, 128):
        if bk >= k:
            continue
        for bm in (256, 128):
            for bn in (256, 128):
                bm2, bn2 = min(bm, m_cap), min(bn, n_cap)
                if _fused_qkv_footprint(bm2, bn2, k, out_bytes,
                                        block_k=bk) <= vmem_budget:
                    return FusedPlan(m, k, nq, nkv, bm2, bn2, bk,
                                     Schedule.K_SPLIT)
    # degenerate budget: minimum MXU-aligned panel, caller's problem
    return FusedPlan(m, k, nq, nkv, min(128, m_cap), min(128, n_cap), k,
                     Schedule.PANEL)


def fused_candidate_plans(m: int, k: int, nq: int, nkv: int, *,
                          out_bytes: int = 2,
                          vmem_budget: int = VMEM_PLAN_BUDGET,
                          max_candidates: int = 8) -> list[FusedPlan]:
    """Feasible FusedPlans across BOTH schedules, analytic pick first.

    The single-GEMM candidate generator varies block_k over {K} ∪ splits;
    here the same sweep decides the *schedule* — block_k == K is the
    panel-resident candidate, anything smaller a K-split candidate — so the
    tuner empirically picks panel vs K-split per (M, K, Nq, Nkv) shape.
    """
    seed = _analytic_fused_plan(m, k, nq, nkv, out_bytes=out_bytes,
                                vmem_budget=vmem_budget)
    m_cap, n_cap = _block_caps(m, max(nq, nkv))
    bms = sorted({min(b, m_cap) for b in (128, 256, 512)})
    bns = sorted({min(b, n_cap) for b in (128, 256, 512)})
    bks = [k] + [bk for bk in (2048, 1024, 512, 256) if bk < k]

    plans: list[FusedPlan] = [seed]
    seen = {(seed.block_m, seed.block_n, seed.block_k)}
    for bk in bks:
        for bm in bms:
            for bn in bns:
                if (bm, bn, bk) in seen:
                    continue
                sched = Schedule.PANEL if bk >= k else Schedule.K_SPLIT
                if _fused_qkv_footprint(
                        bm, bn, k, out_bytes,
                        block_k=None if sched is Schedule.PANEL else bk) \
                        > vmem_budget:
                    continue
                seen.add((bm, bn, bk))
                plans.append(FusedPlan(m, k, nq, nkv, bm, bn, bk, sched))
    # rank non-seed candidates analytically: the fused GEMM moves A once and
    # all three weight matrices, so model it as (M, K, Nq + 2*Nkv)
    head, tail = plans[:1], plans[1:]
    tail.sort(key=lambda p: TilePlan(
        m, k, nq + 2 * nkv, block_m=p.block_m, block_n=p.block_n,
        block_k=p.block_k, out_bytes=out_bytes).time_estimate(int8=True))
    return (head + tail)[:max_candidates]


def _measure_fused_plan(plan: FusedPlan, out_dtype, interpret: bool,
                        iters: int) -> float:
    """Median wall-clock of the fused kernel under ``plan`` (seconds)."""
    from repro.kernels.fused_qkv.kernel import fused_qkv_kernel

    m, k, nq, nkv = plan.m, plan.k, plan.nq, plan.nkv
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(-127, 128, (m, k), dtype=np.int8))
    ws = [jnp.asarray(rng.integers(-127, 128, (k, n), dtype=np.int8))
          for n in (nq, nkv, nkv)]
    sa = jnp.ones((m, 1), jnp.float32)
    ss = [jnp.ones((1, n), jnp.float32) for n in (nq, nkv, nkv)]

    block_k = None if plan.schedule is Schedule.PANEL else plan.block_k
    fn = jax.jit(lambda av, wq, wk, wv: fused_qkv_kernel(
        av, sa, wq, ss[0], wk, ss[1], wv, ss[2],
        block_m=plan.block_m, block_n=plan.block_n, block_k=block_k,
        out_dtype=out_dtype, interpret=interpret))
    jax.block_until_ready(fn(a, *ws))          # compile + warm
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(a, *ws))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def tune_fused(m: int, k: int, nq: int, nkv: int, *,
               out_dtype=jnp.bfloat16, interpret: bool | None = None,
               iters: int | None = None, max_candidates: int = 8,
               results: list | None = None) -> FusedPlan:
    """Measure fused candidates across both schedules, persist the winner.

    The stored entry records the measured ``schedule`` alongside the blocks,
    under the extended ``MxKxNq+Nkv:dtype:backend`` key.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if iters is None:
        iters = int(os.environ.get(ITERS_ENV, "3"))
    out_bytes = jnp.dtype(out_dtype).itemsize
    backend = _measurement_backend(interpret)
    best_plan, best_t = None, float("inf")
    n_results = 0
    for plan in fused_candidate_plans(m, k, nq, nkv, out_bytes=out_bytes,
                                      max_candidates=max_candidates):
        t = _measure_fused_plan(plan, out_dtype, interpret, iters)
        n_results += 1
        if results is not None:
            results.append((plan, t))
        if t < best_t:
            best_plan, best_t = plan, t
    assert best_plan is not None
    _store(_fused_key(m, k, nq, nkv, out_dtype, backend), {
        "block_m": best_plan.block_m,
        "block_n": best_plan.block_n,
        "block_k": best_plan.block_k,
        "schedule": best_plan.schedule.value,
        "us": best_t * 1e6,
        "backend": backend,
        "candidates": n_results,
    })
    return best_plan


def _fused_plan_from_entry(m: int, k: int, nq: int, nkv: int,
                           out_bytes: int, entry: dict,
                           vmem_budget: int) -> FusedPlan | None:
    try:
        block_m = int(entry["block_m"])
        block_n = int(entry["block_n"])
        block_k = int(entry.get("block_k", k))
        sched = Schedule(entry["schedule"]) if "schedule" in entry \
            else (Schedule.PANEL if block_k >= k else Schedule.K_SPLIT)
    except (KeyError, TypeError, ValueError):
        return None
    if sched is Schedule.PANEL:
        block_k = k                 # panel means the full contraction
    plan = FusedPlan(m, k, nq, nkv, block_m, block_n, block_k, sched)
    # hold cached (possibly hand-shipped / version-skewed) entries to the
    # same VMEM budget the tuner's own candidates are generated under
    return plan if plan.footprint(out_bytes) <= vmem_budget else None


def select_fused_plan(m: int, k: int, nq: int, nkv: int, *,
                      out_dtype=jnp.bfloat16,
                      interpret: bool | None = None,
                      vmem_budget: int = VMEM_PLAN_BUDGET) -> FusedPlan:
    """Schedule-aware plan for the fused QKV projection.

    Lookup order under ``cached``/``full``: the extended fused key
    (backend-qualified, then hand-shipped), then the *legacy* single-GEMM
    (M, K, Nq) key — pre-extension tables keep working: a panel entry maps
    directly, a K-split entry maps to the fused K-split schedule — and
    finally (``full`` only) a fused-kernel measurement pass.
    """
    out_bytes = jnp.dtype(out_dtype).itemsize
    mode = tune_mode()
    if mode == "off":
        return _analytic_fused_plan(m, k, nq, nkv, out_bytes=out_bytes,
                                    vmem_budget=vmem_budget)
    table = load_cache()
    backend = _measurement_backend(interpret)
    for key in (_fused_key(m, k, nq, nkv, out_dtype, backend),
                _fused_key(m, k, nq, nkv, out_dtype)):
        entry = table.get(key)
        if entry is not None:
            plan = _fused_plan_from_entry(m, k, nq, nkv, out_bytes, entry,
                                          vmem_budget)
            if plan is not None:
                return plan
    for key in (_key(m, k, nq, out_dtype, backend),
                _key(m, k, nq, out_dtype)):
        entry = table.get(key)
        if entry is not None:
            plan = _fused_plan_from_entry(m, k, nq, nkv, out_bytes, entry,
                                          vmem_budget)
            if plan is not None:
                return plan
    if mode == "full":
        try:
            return tune_fused(m, k, nq, nkv, out_dtype=out_dtype,
                              interpret=interpret)
        except Exception as e:
            # as in select_plan: on the chip a measurement failure raises
            if backend == "tpu":
                raise
            warnings.warn(
                f"REPRO_TUNE=full: fused measurement for "
                f"({m},{k},{nq}+{nkv}) failed ({type(e).__name__}: {e}); "
                f"using the analytic plan")
    return _analytic_fused_plan(m, k, nq, nkv, out_bytes=out_bytes,
                                vmem_budget=vmem_budget)


def select_fused_blocks(m: int, k: int, n: int, *, out_dtype=jnp.bfloat16,
                        interpret: bool | None = None,
                        vmem_budget: int = VMEM_PLAN_BUDGET) -> tuple[int,
                                                                       int]:
    """Back-compat shim: panel-only (block_m, block_n) for MHA (Nkv == Nq).

    Pre-schedule callers assume the panel-resident kernel, so a K-split pick
    from ``select_fused_plan`` is shrunk down the MXU ladder to the largest
    panel whose fused footprint fits.  New code should call
    ``select_fused_plan`` and pass ``block_k`` through.
    """
    out_bytes = jnp.dtype(out_dtype).itemsize
    plan = select_fused_plan(m, k, n, n, out_dtype=out_dtype,
                             interpret=interpret, vmem_budget=vmem_budget)
    if plan.schedule is Schedule.PANEL and \
            _fused_qkv_footprint(plan.block_m, plan.block_n, k,
                                 out_bytes) <= vmem_budget:
        return plan.block_m, plan.block_n
    m_cap, n_cap = _block_caps(m, n)
    for bm in (512, 256, 128):
        for bn in (512, 256, 128):
            bm2, bn2 = min(bm, m_cap), min(bn, n_cap)
            if _fused_qkv_footprint(bm2, bn2, k, out_bytes) <= vmem_budget:
                return bm2, bn2
    return min(128, m_cap), min(128, n_cap)


# ---------------------------------------------------------------------------
# Partial-tile accounting (the policy the dispatcher replaced, kept for
# benchmarks/partial_tile.py to quantify the win)
# ---------------------------------------------------------------------------
def padded_shape(m: int, k: int, n: int, plan: TilePlan) -> tuple[int, int,
                                                                  int]:
    """The block-multiple shape the old zero-pad policy would compute on."""
    kp = round_up(k, plan.block_k) if plan.k_steps > 1 else k
    return (round_up(m, plan.block_m), kp, round_up(n, plan.block_n))


def pad_overhead(m: int, k: int, n: int, plan: TilePlan) -> float:
    """Wasted-FLOP fraction of the zero-pad policy: padded/useful − 1."""
    mp, kp, np_ = padded_shape(m, k, n, plan)
    return (mp * kp * np_) / (m * k * n) - 1.0


def grid_shape(m: int, n: int, plan: TilePlan) -> tuple[int, ...]:
    """Pallas grid for ``plan`` under the native partial-tile policy."""
    g = (ceil_div(m, plan.block_m), ceil_div(n, plan.block_n))
    return g if plan.k_steps == 1 else g + (plan.k_steps,)
