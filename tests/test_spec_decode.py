"""Speculative draft-and-verify decode (``docs/DESIGN.md`` §8).

Four layers of coverage:

  * **Kernel verify mode** — the n-token verify launch
    (``new_lens=``) against the plain decode launch and the dense
    oracle: ``new_lens`` of all-ones must be *bitwise* the existing
    1-token decode in both the jnp oracle and the interpreted kernel
    across {GQA} × {window} × {page size} × {mixed lens} (the big cross
    product is marked slow); variable per-sequence counts match a
    per-sequence exact-width launch on live rows and return exact zeros
    on dead rows.
  * **Rollback** — ``allocator.rewind_sequence`` zeroes the rewound
    token rows in *every* ``PAGE_STATE_KEYS`` array (§2 invariant 5:
    int8 scale rows rewind with their pages), touches nothing else, and
    never moves a page.
  * **Scheduler parity** — the tentpole claim: a mixed-arrival,
    prefix-sharing serving trace decoded speculatively emits bitwise
    the tokens of plain 1-token decode (ref kernel mode), for an
    independent draft (partial acceptance) and a truncated
    self-speculation draft, over float32 and int8 page pools
    (fork-then-reject parity), with EOS and budget caps live.
  * **Event log** — one ``token_tick`` per *emitted* token, so a
    multi-accept tick contributes that many entries and the benchmark's
    per-token latency percentiles stay per-token.
"""
import itertools
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels.flash_attention.ops import paged_decode_attention
from repro.models.transformer import init_model
from repro.serving.allocator import rewind_sequence
from repro.serving.cache import (PAGE_STATE_KEYS, CacheConfig,
                                 default_page_table, init_cache)
from repro.serving.scheduler import Scheduler, SpecConfig

ROOT = pathlib.Path(__file__).resolve().parent.parent
RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# kernel verify mode
# ---------------------------------------------------------------------------
def _pools(b, t, kh, d, page):
    table = default_page_table(b, t // page, "striped")
    hist_k = RNG.normal(size=(b, t, kh, d)).astype(np.float32)
    hist_v = RNG.normal(size=(b, t, kh, d)).astype(np.float32)
    mp = t // page
    kp = np.zeros((b * mp, kh, page, d), np.float32)     # head-major
    vp = np.zeros_like(kp)
    for bb in range(b):
        for j in range(mp):
            rows = slice(j * page, (j + 1) * page)
            kp[int(table[bb, j])] = hist_k[bb, rows].swapaxes(0, 1)
            vp[int(table[bb, j])] = hist_v[bb, rows].swapaxes(0, 1)
    return jnp.asarray(kp), jnp.asarray(vp), table


def _verify_n1_case(g, window, page, lens):
    """new_lens of all-ones is bitwise the plain 1-token decode launch."""
    h, kh, d = 4, 4 // g, 16
    b, t = len(lens), 64
    kp, vp, table = _pools(b, t, kh, d, page)
    q = jnp.asarray(RNG.normal(size=(b, 1, h, d)).astype(np.float32))
    lens = jnp.asarray(lens, jnp.int32)
    ones = jnp.ones((b,), jnp.int32)
    for mode in ("ref", "pallas_interpret"):
        plain = paged_decode_attention(q, kp, vp, table, lens,
                                       window=window, mode=mode)
        verify = paged_decode_attention(q, kp, vp, table, lens,
                                        window=window, mode=mode,
                                        new_lens=ones)
        np.testing.assert_array_equal(np.asarray(plain), np.asarray(verify))


def test_verify_n1_bitwise():
    _verify_n1_case(2, None, 8, [33, 17])
    _verify_n1_case(2, 12, 8, [33, 17])


@pytest.mark.slow
@pytest.mark.parametrize(
    "g,window,page,lens",
    list(itertools.product([1, 4], [None, 24], [8, 16],
                           [[64, 64], [37, 5], [64, 1], [48, 23]])))
def test_verify_n1_bitwise_sweep(g, window, page, lens):
    """{GQA} × {window} × {page size} × {mixed/non-multiple lens}."""
    _verify_n1_case(g, window, page, lens)


def test_verify_variable_rows():
    """Variable per-sequence counts: dead rows are exact zeros; live
    rows match an exact-width per-sequence launch (bitwise under ref —
    the serving path; allclose under the interpreted kernel, which
    carries no bitwise contract across q-block shapes)."""
    h, kh, d, page, s = 4, 2, 16, 8, 4
    b, t = 2, 64
    kp, vp, table = _pools(b, t, kh, d, page)
    q = jnp.asarray(RNG.normal(size=(b, s, h, d)).astype(np.float32))
    lens = jnp.asarray([39, 21], jnp.int32)     # committed + live rows
    new_lens = jnp.asarray([3, 1], jnp.int32)
    for mode, exact in (("ref", True), ("pallas_interpret", False)):
        out = np.asarray(paged_decode_attention(
            q, kp, vp, table, lens, mode=mode, new_lens=new_lens))
        for bb, nl in enumerate([3, 1]):
            np.testing.assert_array_equal(out[bb, nl:], 0.0)
            want = np.asarray(paged_decode_attention(
                q[bb:bb + 1, :nl], kp, vp, table[bb:bb + 1],
                lens[bb:bb + 1], mode=mode))
            if exact:
                np.testing.assert_array_equal(out[bb, :nl], want[0])
            else:
                np.testing.assert_allclose(out[bb, :nl], want[0],
                                           atol=5e-6, rtol=1e-5)


# ---------------------------------------------------------------------------
# rollback
# ---------------------------------------------------------------------------
def test_rewind_invalidates_all_page_state():
    cfg = get_smoke_config("qwen2_5_3b").replace(quant_proj="none",
                                                 dtype="float32")
    config = CacheConfig(layout="paged", alloc="dynamic", page_size=4,
                         pool_pages=30, kv_quant="int8")
    cache = init_cache(cfg, 2, 32, dtype=jnp.float32, config=config)
    from repro.serving.allocator import admit_sequence
    cache, ok0 = admit_sequence(cache, 0, 16)
    cache, ok1 = admit_sequence(cache, 1, 16)
    assert bool(ok0) and bool(ok1)
    # fill every page-state array with ones and commit 11 tokens each
    for key in PAGE_STATE_KEYS:
        cache[key] = jnp.ones_like(cache[key])
    cache["seq_lens"] = jnp.asarray([11, 11], jnp.int32)
    table = np.asarray(cache["page_table"])
    rewound = rewind_sequence(cache, 0, 6)
    assert rewound["seq_lens"].tolist() == [6, 11]
    # pages never move
    np.testing.assert_array_equal(np.asarray(rewound["page_table"]), table)
    page = config.page_size
    for key in PAGE_STATE_KEYS:
        arr = np.asarray(rewound[key])
        for tok in range(16):
            pidx, slot = int(table[0, tok // page]), tok % page
            want = 0 if 6 <= tok < 11 else 1
            # head-major page state: (L, P, KVH, page[, hd])
            assert (arr[:, pidx, :, slot] == want).all(), (key, tok)
        # slot 1 untouched
        for tok in range(11):
            pidx, slot = int(table[1, tok // page]), tok % page
            assert (arr[:, pidx, :, slot] == 1).all(), (key, tok)


# ---------------------------------------------------------------------------
# scheduler parity (tentpole) + event log
# ---------------------------------------------------------------------------
def _models():
    cfg = get_smoke_config("qwen2_5_3b").replace(quant_proj="none",
                                                 dtype="float32")
    params = init_model(jax.random.PRNGKey(0), cfg)
    draft_cfg = cfg.replace(n_layers=1)
    # independent tiny draft (partial acceptance) and truncated
    # self-speculation draft (first target layer + shared embed/head)
    independent = init_model(jax.random.PRNGKey(7), draft_cfg)
    self_trunc = dict(params)
    self_trunc["layers"] = jax.tree.map(lambda x: x[:1], params["layers"])
    return cfg, params, draft_cfg, independent, self_trunc


def _spec_trace():
    rng = np.random.default_rng(3)
    base = rng.integers(0, 256, 6).astype(np.int32)
    reqs = []
    for i in range(6):
        if i % 3 == 2:     # shared prefixes exercise fork-then-reject
            prompt = np.concatenate(
                [base, rng.integers(0, 256, 1 + i).astype(np.int32)])
        else:
            prompt = rng.integers(0, 256, int(rng.integers(3, 9)))
        reqs.append((prompt.astype(np.int32), int(rng.integers(2, 9))))
    return reqs, [0, 1, 1, 3, 5, 6]


def _serve(cfg, params, spec, kv_quant):
    config = CacheConfig(layout="paged", alloc="dynamic", page_size=4,
                         pool_pages=30, kv_quant=kv_quant)
    sched = Scheduler(params, cfg, slots=3, max_len=64, bucket=8,
                      config=config, eos_id=5, spec=spec)
    reqs, arrivals = _spec_trace()
    i = 0
    while i < len(reqs) or sched.queue or sched.n_active:
        while i < len(reqs) and arrivals[i] <= sched._ticks:
            sched.submit(reqs[i][0], reqs[i][1])
            i += 1
        sched.step()
        assert sched._ticks < 500
    return sched


@pytest.mark.slow
@pytest.mark.parametrize("kv_quant", ["none", "int8"])
@pytest.mark.parametrize("draft", ["independent", "self_trunc"])
def test_spec_serving_bitwise_parity(draft, kv_quant):
    """Speculative greedy tokens == plain 1-token decode, bitwise, on a
    mixed-arrival prefix-sharing trace with EOS and budget caps (int8
    covers fork-then-reject scale-row parity)."""
    cfg, params, draft_cfg, independent, self_trunc = _models()
    dp = independent if draft == "independent" else self_trunc
    plain = _serve(cfg, params, None, kv_quant)
    spec = _serve(cfg, params, SpecConfig(dp, draft_cfg, n_draft=3),
                  kv_quant)
    assert plain.finished.keys() == spec.finished.keys()
    for rid in plain.finished:
        np.testing.assert_array_equal(plain.finished[rid],
                                      spec.finished[rid])
    st = spec.spec_stats
    # each request's first token comes from its prefill logits; every
    # later token was emitted by a spec tick
    assert st["emitted"] == (sum(len(v) for v in spec.finished.values())
                             - len(spec.finished))
    assert 0 <= st["accepted"] <= st["proposed"]
    if draft == "self_trunc":
        # a correlated draft must actually multi-accept somewhere
        assert st["accepted"] > 0
        assert spec._ticks < plain._ticks


@pytest.mark.slow
def test_spec_event_log_one_tick_per_token():
    """Satellite: multi-accept steps log one ``token_tick`` per emitted
    token, so latency percentiles stay per-token."""
    cfg, params, draft_cfg, _, self_trunc = _models()
    sched = _serve(cfg, params, SpecConfig(self_trunc, draft_cfg,
                                           n_draft=3), "none")
    multi = 0
    for rid, log in sched.request_log.items():
        tt = log["token_ticks"]
        assert len(tt) == len(sched.finished[rid])
        assert tt == sorted(tt)
        assert log["submitted"] <= log["admitted"] <= tt[0]
        multi = max(multi, max(tt.count(t) for t in set(tt)))
    # the trace must actually exercise a multi-accept tick
    assert multi > 1


def test_latency_stats_per_emitted_token():
    """The benchmark joins token ticks to per-tick wall times: a
    multi-accept tick contributes one per-token sample per emitted
    token, all costing that tick's duration."""
    sys.path.insert(0, str(ROOT))
    from benchmarks.serving import _latency_stats

    class _S:
        request_log = {1: {"submitted": 0, "admitted": 2,
                           "token_ticks": [2, 4, 4, 4]}}

    durations = [0.010, 0.010, 0.030, 0.010, 0.060]
    got = _latency_stats(_S(), durations)
    # TTFT spans submission through the first-token tick
    assert got["ttft_p50_ms"] == pytest.approx(50.0)
    # three decode tokens, all emitted at tick 4
    assert got["tok_p50_ms"] == pytest.approx(60.0)
    assert got["tok_p95_ms"] == pytest.approx(60.0)


def test_ssm_family_degrades_to_plain_decode():
    """SSM slot state can't rewind: a spec request warns and serves
    through the plain 1-token path with identical output."""
    cfg = get_smoke_config("mamba2_370m").replace(quant_proj="none",
                                                  dtype="float32")
    params = init_model(jax.random.PRNGKey(0), cfg)
    draft_cfg = cfg.replace(n_layers=1)
    draft = dict(params)
    draft["layers"] = jax.tree.map(lambda x: x[:1], params["layers"])
    prompt = np.arange(3, 9).astype(np.int32)

    def serve(spec):
        sched = Scheduler(params, cfg, slots=2, max_len=32, bucket=8,
                          spec=spec)
        sched.submit(prompt, 4)
        while sched.queue or sched.n_active:
            sched.step()
            assert sched._ticks < 50
        return sched

    plain = serve(None)
    with pytest.warns(UserWarning, match="degrading to 1-token decode"):
        spec = serve(SpecConfig(draft, draft_cfg, n_draft=3))
    assert spec.spec is None and spec.draft_cache is None
    for rid in plain.finished:
        np.testing.assert_array_equal(plain.finished[rid],
                                      spec.finished[rid])
