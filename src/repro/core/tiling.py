"""Analytic tiling model — MAESTRO-flavored reuse accounting (paper §3/§4).

The paper picks its tile sizes (T=32, BLOCK_M=256) from a BRAM/DSP budget and
a routing-feasibility constraint.  On TPU the constraints are VMEM capacity
and MXU alignment; this module does the same budgeting analytically so that

  * ``ops.py`` can auto-select block shapes for arbitrary GEMM dims,
  * ``benchmarks/tile_sweep.py`` can reproduce the paper's T∈{16,32,64} DSE
    as a block-shape sweep with predicted-vs-ideal roofline numbers,
  * tests can assert the invariants (footprint ≤ VMEM, full coverage).

The footprint model counts what Pallas actually allocates: every input
and output block double-buffered by the grid pipeline, each padded to the
VMEM tiling, plus the kernel's int32/f32 temporaries.  Plans are budgeted
against ``VMEM_PLAN_BUDGET``, a fixed share of the scoped-VMEM limit the
GEMM kernels are compiled with (``VMEM_LIMIT_BYTES``).
"""
from __future__ import annotations

import dataclasses

# --- TPU v5e constants (single chip; brief §Roofline) ---------------------
PEAK_BF16_FLOPS = 197e12          # FLOP/s
PEAK_INT8_OPS = 394e12            # int8 MAC*2/s (2x bf16 on the MXU)
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link
VMEM_BYTES = 128 * 1024 * 1024    # physical VMEM per core
# scoped-VMEM limit the GEMM kernels are compiled with
# (pltpu.CompilerParams(vmem_limit_bytes=...)); the planner budgets three
# quarters of it and leaves the rest to Mosaic's internal scratch
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
VMEM_PLAN_BUDGET = VMEM_LIMIT_BYTES * 3 // 4
MXU_DIM = 128                     # systolic array edge (the paper's "32")
LANES = 128                       # minor dim of the VMEM tiling


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(x: int, m: int) -> int:
    return ceil_div(x, m) * m


def sublane_rows(itemsize: int) -> int:
    """Rows of one sublane tile of the (rows, 128) VMEM tiling: 8 rows of
    32-bit words, so 16 for bf16 and 32 for int8."""
    return 32 // itemsize


def vmem_tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols) block: the minor dim pads to 128
    lanes, the second-minor to the dtype's sublane tile."""
    return (round_up(rows, sublane_rows(itemsize)) * round_up(cols, LANES)
            * itemsize)


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """A two-level tiling of C[M,N] = A[M,K] @ B[K,N] (dtypes in bytes)."""
    m: int
    k: int
    n: int
    block_m: int
    block_n: int
    block_k: int            # == k for the panel-resident schedule
    a_bytes: int = 1        # int8
    b_bytes: int = 1
    out_bytes: int = 2      # bf16
    acc_bytes: int = 4      # int32 accumulator

    @property
    def k_steps(self) -> int:
        return ceil_div(self.k, self.block_k)

    @property
    def schedule(self) -> str:
        """Contraction schedule this plan implies — ``"panel"`` (block_k
        spans K, the paper's persistent-A schedule) or ``"k_split"``.
        String-valued so this module stays import-free of ``core.dispatch``;
        compares equal to the ``dispatch.Schedule`` str-enum."""
        return "panel" if self.k_steps == 1 else "k_split"

    # -- level-1 (VMEM) footprint ------------------------------------------
    @property
    def vmem_footprint(self) -> int:
        """Bytes of VMEM the kernel allocates under this plan."""
        bm, bn, bk = self.block_m, self.block_n, self.block_k
        blocks = (vmem_tile_bytes(bm, bk, self.a_bytes)           # A
                  + vmem_tile_bytes(bk, bn, self.b_bytes)         # B
                  + vmem_tile_bytes(bm, bn, self.out_bytes)       # C
                  + vmem_tile_bytes(bm, 1, 4)                     # row scales
                  + 2 * vmem_tile_bytes(1, bn, 4))                # col scales, bias
        # the grid pipeline double-buffers every block, the j-invariant A
        # panel included; the int32 product and its f32 dequant are live
        # temporaries, and the K-split schedule adds its accumulator
        acc = vmem_tile_bytes(bm, bn, self.acc_bytes)
        temps = 2 * acc + (acc if self.k_steps > 1 else 0)
        return 2 * blocks + temps

    def fits_vmem(self, budget: int = VMEM_PLAN_BUDGET) -> bool:
        return self.vmem_footprint <= budget

    # -- reuse / traffic model (MAESTRO-style temporal reuse) ---------------
    @property
    def hbm_traffic(self) -> int:
        """Bytes moved HBM<->VMEM for the whole GEMM.

        A row-panel is loaded once per M-block and reused across all N-blocks
        (the paper's persistent-A reuse); B is re-streamed once per M-block;
        C is written once.  With the K-split schedule the same holds per
        (m,k)/(k,n) block pair.
        """
        m_blocks = ceil_div(self.m, self.block_m)
        a = self.m * self.k * self.a_bytes                    # each A elem once
        b = m_blocks * self.k * self.n * self.b_bytes         # B per M-block
        c = self.m * self.n * self.out_bytes
        return a + b + c

    @property
    def flops(self) -> int:
        return 2 * self.m * self.k * self.n

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / self.hbm_traffic

    # -- single-chip roofline estimate --------------------------------------
    def time_estimate(self, int8: bool = True) -> float:
        peak = PEAK_INT8_OPS if int8 else PEAK_BF16_FLOPS
        # MXU utilisation penalty when tile dims are not MXU-aligned — the
        # TPU analogue of the paper's "T=16 reduced concurrency".
        align = (min(self.block_m, MXU_DIM) / MXU_DIM) \
            * (min(self.block_n, MXU_DIM) / MXU_DIM)
        compute = self.flops / (peak * max(align, 1e-9))
        memory = self.hbm_traffic / HBM_BW
        return max(compute, memory)

    @property
    def bound(self) -> str:
        compute = self.flops / PEAK_INT8_OPS
        memory = self.hbm_traffic / HBM_BW
        return "compute" if compute >= memory else "memory"


def choose_plan(m: int, k: int, n: int, *,
                out_bytes: int = 2,
                vmem_budget: int = VMEM_PLAN_BUDGET) -> TilePlan:
    """Pick block shapes: the paper's DSE, automated.

    Strategy (mirrors paper §5 "Tile size selection", with MXU=128 replacing
    their DSP-array 32): prefer the panel-resident schedule (block_k == K,
    maximal A reuse == `update_A`); shrink block_m/block_n from 512→128 in
    MXU multiples until the footprint fits; if even the minimum panel does
    not fit, fall back to the K-split schedule.
    """
    # a small M (e.g. the paper's 64-token panel) uses a sublane-aligned
    # block rather than padding to the full MXU edge (50% fill beats 100%
    # padded compute)
    m_cap = round_up(m, 8) if m < MXU_DIM else round_up(m, MXU_DIM)
    for bm in (512, 256, 128):
        for bn in (512, 256, 128):
            plan = TilePlan(m, k, n, block_m=min(bm, m_cap),
                            block_n=min(bn, round_up(n, MXU_DIM)),
                            block_k=k, out_bytes=out_bytes)
            if plan.fits_vmem(vmem_budget):
                return plan
    # K-split fallback for very large K
    for bk in (2048, 1024, 512, 256, 128):
        if bk > k:
            continue
        plan = TilePlan(m, k, n, block_m=128, block_n=128,
                        block_k=bk, out_bytes=out_bytes)
        if plan.fits_vmem(vmem_budget):
            return plan
    raise ValueError(f"no feasible tiling for ({m},{k},{n})")
