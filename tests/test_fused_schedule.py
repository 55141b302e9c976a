"""Fused-QKV schedules + extended tune keys (ISSUE 2).

Acceptance: the fused K-split schedule is bitwise identical to the reference
across a partial-tile (M, K, Nq, Nkv) sweep including the paper's
64x768x(2304) DistilBERT panel; the autotuner cache key carries the
(Nq, Nkv) output split and the schedule, round-trips through
REPRO_TUNE=full -> cached, and falls back to the legacy single-GEMM key;
the shipped seed table covers the paper shapes.
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dispatch
from repro.core.dispatch import FusedPlan, Schedule
from repro.core.quantization import quantize
from repro.core.tiling import VMEM_PLAN_BUDGET
from repro.kernels.fused_qkv.ops import fused_qkv

RNG = np.random.default_rng(11)

# (M, K, Nq, Nkv, block_k) — K-split forced via explicit block_k < K;
# partial tiles in every dim somewhere; GQA (Nkv < Nq); the paper panel.
KSPLIT_SHAPES = [
    (64, 768, 768, 768, 256),     # paper DistilBERT 64-row QKV panel (2304)
    (33, 300, 65, 65, 128),       # partial in every dim
    (61, 513, 130, 36, 256),      # GQA + fractional K slab
    (16, 257, 384, 128, 128),     # K just past two slabs
    (7, 96, 100, 36, 32),         # tiny sub-sublane GQA
]


def _fused_operands(m, kd, nq, nkv):
    a = quantize(jnp.asarray(RNG.normal(size=(m, kd)).astype(np.float32)),
                 channel_axes=(0,))
    ws = [quantize(jnp.asarray((RNG.normal(size=(kd, n)) * 0.05)
                               .astype(np.float32)), channel_axes=(1,))
          for n in (nq, nkv, nkv)]
    return a, ws


# the isolated-cache ``tune_cache`` fixture lives in conftest.py (shared
# with test_dispatch.py)


# ---------------------------------------------------------------------------
# K-split schedule parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,kd,nq,nkv,bk", KSPLIT_SHAPES)
def test_fused_ksplit_parity_bitwise(m, kd, nq, nkv, bk):
    """Acceptance: fused K-split output is bitwise identical to the ref."""
    a, ws = _fused_operands(m, kd, nq, nkv)
    ref = fused_qkv(a, *ws, out_dtype=jnp.float32, mode="ref")
    pal = fused_qkv(a, *ws, block_m=32, block_n=64, block_k=bk,
                    out_dtype=jnp.float32, mode="pallas_interpret")
    for r, p in zip(ref, pal):
        assert p.shape == r.shape
        np.testing.assert_array_equal(np.asarray(r), np.asarray(p))


@pytest.mark.parametrize("m,kd,nq,nkv,bk", KSPLIT_SHAPES[:2])
def test_fused_schedules_agree_bitwise(m, kd, nq, nkv, bk):
    """Panel and K-split run the same int32 accumulation order, so the two
    schedules agree bit-for-bit with each other, not just with the ref."""
    a, ws = _fused_operands(m, kd, nq, nkv)
    panel = fused_qkv(a, *ws, block_m=32, block_n=64,
                      out_dtype=jnp.float32, mode="pallas_interpret")
    ksplit = fused_qkv(a, *ws, block_m=32, block_n=64, block_k=bk,
                       out_dtype=jnp.float32, mode="pallas_interpret")
    for p, s in zip(panel, ksplit):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(s))


def test_dispatched_ksplit_plan_drives_kernel(tune_cache):
    """A cached fused K-split entry flows through the shared launch path."""
    m, kd, nq, nkv = 33, 300, 65, 65
    tune_cache.write_text(json.dumps({
        f"{m}x{kd}x{nq}+{nkv}:float32": {
            "block_m": 32, "block_n": 64, "block_k": 128,
            "schedule": "k_split"}}))
    a, ws = _fused_operands(m, kd, nq, nkv)
    ref = fused_qkv(a, *ws, out_dtype=jnp.float32, mode="ref")
    pal = fused_qkv(a, *ws, out_dtype=jnp.float32, mode="pallas_interpret")
    for r, p in zip(ref, pal):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(p))


# ---------------------------------------------------------------------------
# Extended (Nq, Nkv)+schedule tune key
# ---------------------------------------------------------------------------
def test_fused_tune_cache_roundtrip(tune_cache, monkeypatch):
    """REPRO_TUNE=full writes the extended key with a schedule; cached mode
    returns the identical plan without re-measuring."""
    m, kd, nq, nkv = 16, 96, 48, 16
    monkeypatch.setenv(dispatch.TUNE_ENV, "full")
    tuned = dispatch.select_fused_plan(m, kd, nq, nkv,
                                       out_dtype=jnp.float32,
                                       interpret=True)
    assert tune_cache.exists()
    entry = json.loads(tune_cache.read_text())[
        f"{m}x{kd}x{nq}+{nkv}:float32:interpret"]
    assert entry["schedule"] in ("panel", "k_split")
    assert entry["schedule"] == tuned.schedule.value
    assert entry["us"] > 0

    monkeypatch.setenv(dispatch.TUNE_ENV, "cached")
    dispatch.reset_cache_state()
    hit = dispatch.select_fused_plan(m, kd, nq, nkv, out_dtype=jnp.float32)
    assert hit == tuned

    monkeypatch.setenv(dispatch.TUNE_ENV, "off")
    analytic = dispatch.select_fused_plan(m, kd, nq, nkv,
                                          out_dtype=jnp.float32)
    assert analytic == dispatch._analytic_fused_plan(
        m, kd, nq, nkv, out_bytes=4, vmem_budget=VMEM_PLAN_BUDGET)


def test_fused_key_distinguishes_nq_nkv_split(tune_cache, monkeypatch):
    """Same total output width, different (Nq, Nkv) split -> different key:
    a GQA entry must never be served to the MHA shape."""
    m, kd = 32, 128
    monkeypatch.setenv(dispatch.TUNE_ENV, "cached")
    tune_cache.write_text(json.dumps({
        f"{m}x{kd}x256+64:float32": {"block_m": 32, "block_n": 64,
                                     "block_k": kd, "schedule": "panel"}}))
    gqa = dispatch.select_fused_plan(m, kd, 256, 64, out_dtype=jnp.float32)
    assert (gqa.block_m, gqa.block_n) == (32, 64)
    # (192, 96) also sums to 384 output cols but misses the cache
    other = dispatch.select_fused_plan(m, kd, 192, 96,
                                       out_dtype=jnp.float32)
    assert other == dispatch._analytic_fused_plan(
        m, kd, 192, 96, out_bytes=4, vmem_budget=VMEM_PLAN_BUDGET)


def test_legacy_single_gemm_key_fallback_panel(tune_cache, monkeypatch):
    """Pre-extension tables (single-GEMM MxKxNq keys) keep working: a panel
    entry maps straight onto the fused panel schedule."""
    m, kd, nq, nkv = 40, 256, 96, 96
    monkeypatch.setenv(dispatch.TUNE_ENV, "cached")
    tune_cache.write_text(json.dumps({
        f"{m}x{kd}x{nq}:float32": {"block_m": 40, "block_n": 96}}))
    plan = dispatch.select_fused_plan(m, kd, nq, nkv, out_dtype=jnp.float32)
    assert (plan.block_m, plan.block_n) == (40, 96)
    assert plan.schedule is Schedule.PANEL and plan.block_k == kd


def test_legacy_ksplit_single_key_maps_to_fused_ksplit(tune_cache,
                                                       monkeypatch):
    """A legacy K-split single-GEMM entry becomes a fused K-split plan —
    the shape class that previously fell back to an under-filled panel."""
    m, kd, n = 512, 28672, 4096
    monkeypatch.setenv(dispatch.TUNE_ENV, "cached")
    tune_cache.write_text(json.dumps({
        f"{m}x{kd}x{n}:bfloat16": {"block_m": 256, "block_n": 256,
                                   "block_k": 2048}}))
    plan = dispatch.select_fused_plan(m, kd, n, n, out_dtype=jnp.bfloat16)
    assert plan.schedule is Schedule.K_SPLIT
    assert (plan.block_m, plan.block_n, plan.block_k) == (256, 256, 2048)
    assert plan.fits_vmem(VMEM_PLAN_BUDGET, out_bytes=2)


def test_fused_entry_without_schedule_inferred_from_block_k(tune_cache,
                                                            monkeypatch):
    """Hand-shipped fused entries may omit 'schedule' (inferred)."""
    m, kd, nq, nkv = 32, 512, 64, 64
    monkeypatch.setenv(dispatch.TUNE_ENV, "cached")
    tune_cache.write_text(json.dumps({
        f"{m}x{kd}x{nq}+{nkv}:float32": {"block_m": 32, "block_n": 64,
                                         "block_k": 128}}))
    plan = dispatch.select_fused_plan(m, kd, nq, nkv, out_dtype=jnp.float32)
    assert plan.schedule is Schedule.K_SPLIT and plan.block_k == 128


def test_oversized_fused_entry_rejected(tune_cache, monkeypatch):
    """Cached fused entries are held to the planning VMEM budget."""
    m, kd, nq = 512, 65536, 4096
    monkeypatch.setenv(dispatch.TUNE_ENV, "cached")
    tune_cache.write_text(json.dumps({
        f"{m}x{kd}x{nq}+{nq}:bfloat16": {"block_m": 512, "block_n": 512,
                                         "block_k": kd,
                                         "schedule": "panel"}}))
    plan = dispatch.select_fused_plan(m, kd, nq, nq, out_dtype=jnp.bfloat16)
    assert plan.fits_vmem(VMEM_PLAN_BUDGET, out_bytes=2)
    assert (plan.block_m, plan.block_n, plan.block_k) != (512, 512, kd)


def test_analytic_huge_k_picks_ksplit():
    """The analytic fused fallback now has the K-split escape the ROADMAP
    asked for: when no panel fits the budget, schedule is K_SPLIT (not an
    under-filled minimum panel)."""
    plan = dispatch._analytic_fused_plan(512, 262144, 4096, 4096,
                                         out_bytes=2,
                                         vmem_budget=VMEM_PLAN_BUDGET)
    assert plan.schedule is Schedule.K_SPLIT
    assert plan.fits_vmem(VMEM_PLAN_BUDGET, out_bytes=2)


def test_fused_candidates_cover_both_schedules():
    """For large-K shapes the tuner's candidate set races both schedules —
    that is what makes the schedule pick empirical."""
    plans = dispatch.fused_candidate_plans(48, 2048, 256, 64,
                                           max_candidates=8)
    scheds = {p.schedule for p in plans}
    assert scheds == {Schedule.PANEL, Schedule.K_SPLIT}
    for p in plans:
        assert p.footprint(2) <= VMEM_PLAN_BUDGET


# ---------------------------------------------------------------------------
# Seed table
# ---------------------------------------------------------------------------
def test_seed_table_covers_paper_shapes(tmp_path, monkeypatch):
    """With no user cache, the shipped gemm_tune.json serves the paper
    shapes — including the fused 64-row DistilBERT panel."""
    monkeypatch.setenv(dispatch.CACHE_ENV, str(tmp_path / "nonexistent.json"))
    monkeypatch.delenv(dispatch.SEED_ENV, raising=False)
    monkeypatch.setenv(dispatch.TUNE_ENV, "cached")
    dispatch.reset_cache_state()
    try:
        seed = json.load(open(dispatch.seed_table_path()))
        plan = dispatch.select_plan(64, 768, 3072, out_dtype=jnp.bfloat16)
        entry = seed["64x768x3072:bfloat16"]
        assert (plan.block_m, plan.block_n) == (entry["block_m"],
                                                entry["block_n"])
        fused = dispatch.select_fused_plan(64, 768, 768, 768,
                                           out_dtype=jnp.bfloat16)
        fentry = seed["64x768x768+768:bfloat16"]
        assert (fused.block_m, fused.block_n) == (fentry["block_m"],
                                                  fentry["block_n"])
        assert fused.schedule.value == fentry["schedule"]
    finally:
        dispatch.reset_cache_state()


def test_seed_table_disabled_by_env(tmp_path, monkeypatch):
    monkeypatch.setenv(dispatch.CACHE_ENV, str(tmp_path / "nonexistent.json"))
    monkeypatch.setenv(dispatch.SEED_ENV, "0")
    dispatch.reset_cache_state()
    try:
        assert dispatch.load_cache() == {}
    finally:
        dispatch.reset_cache_state()


def test_user_cache_overrides_seed(tmp_path, monkeypatch):
    """User-measured entries shadow the shipped seed for the same key."""
    path = tmp_path / "user.json"
    path.write_text(json.dumps({
        "64x768x3072:bfloat16": {"block_m": 128, "block_n": 128,
                                 "block_k": 768, "schedule": "panel"}}))
    monkeypatch.setenv(dispatch.CACHE_ENV, str(path))
    monkeypatch.delenv(dispatch.SEED_ENV, raising=False)
    monkeypatch.setenv(dispatch.TUNE_ENV, "cached")
    dispatch.reset_cache_state()
    try:
        plan = dispatch.select_plan(64, 768, 3072, out_dtype=jnp.bfloat16)
        assert (plan.block_m, plan.block_n) == (128, 128)
    finally:
        dispatch.reset_cache_state()


def test_store_does_not_persist_seed_entries(tune_cache, monkeypatch):
    """Tuning writes only user entries to the cache file — the merged-in
    seed table never leaks into (or bloats) the user's JSON."""
    monkeypatch.delenv(dispatch.SEED_ENV, raising=False)
    dispatch.reset_cache_state()
    dispatch._store("1x2x3:float32", {"block_m": 8, "block_n": 128,
                                      "block_k": 2})
    on_disk = json.loads(tune_cache.read_text())
    assert list(on_disk) == ["1x2x3:float32"]
    # but lookups see seed + user merged
    table = dispatch.load_cache()
    assert "1x2x3:float32" in table and "64x768x3072:bfloat16" in table


# ---------------------------------------------------------------------------
# FusedPlan invariants
# ---------------------------------------------------------------------------
def test_fused_plan_footprint_panel_vs_ksplit():
    panel = FusedPlan(64, 4096, 768, 768, 64, 256, 4096, Schedule.PANEL)
    ksplit = FusedPlan(64, 4096, 768, 768, 64, 256, 512, Schedule.K_SPLIT)
    # K-split trades weight residency for bounded footprint: strictly
    # smaller here (weights dominate at K=4096)
    assert ksplit.footprint(2) < panel.footprint(2)
    assert ksplit.k_steps == 8 and panel.k_steps == 1
