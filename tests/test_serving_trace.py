"""The scheduler's ``serving.*`` spans, read back from a profiler trace.

A tiny ``Scheduler`` runs on the CPU under ``jax.profiler.start_trace``;
the ``.xplane.pb`` it writes is read with ``jax.profiler.ProfileData``.
Every span of ``docs/DESIGN.md`` §6 must be there, nested as the tick
nests its layers and carrying its arguments as stats, and tracing must
not change a generated token.
"""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.models.transformer import init_model
from repro.serving.cache import CacheConfig
from repro.serving.scheduler import Scheduler, SpecConfig

SPANS = ("serving.step", "serving.admit", "serving.prefill",
         "serving.prefill.chunk", "serving.first_token", "serving.decode",
         "serving.decode.wait", "serving.decode.advance", "serving.retire",
         "serving.occupancy")
CHUNK = 4


def _models():
    cfg = get_smoke_config("qwen2_5_3b").replace(quant_proj="none",
                                                 dtype="float32")
    params = init_model(jax.random.PRNGKey(0), cfg)
    draft_cfg = cfg.replace(n_layers=1)
    return cfg, params, draft_cfg, init_model(jax.random.PRNGKey(7),
                                              draft_cfg)


def _serve(path):
    """Serve a short mixed trace (the third prompt shares the first's
    prefix, so one admission forks) and return the scheduler."""
    cfg, params, draft_cfg, draft = _models()
    spec = SpecConfig(draft, draft_cfg, n_draft=2) if path == "spec" else None
    sched = Scheduler(params, cfg, slots=2, max_len=64, bucket=4,
                      prefill_chunk=CHUNK, spec=spec,
                      config=CacheConfig(layout="paged", alloc="dynamic",
                                         page_size=4, pool_pages=32))
    rng = np.random.default_rng(11)
    base = rng.integers(0, cfg.vocab_size, 11)
    sched.submit(base, 8)
    sched.submit(rng.integers(0, cfg.vocab_size, 6), 3)
    sched.step()
    # admitted once the second retires, while the first is still live
    sched.submit(np.concatenate([base[:9],
                                 rng.integers(0, cfg.vocab_size, 3)]), 3)
    sched.run(max_ticks=50)
    return sched


class Span:
    def __init__(self, line, ev):
        self.line, self.name = line, ev.name
        self.start, self.end = ev.start_ns, ev.start_ns + ev.duration_ns
        self.stats = dict(ev.stats)

    def within(self, other):
        return (self.line == other.line and other.start <= self.start
                and self.end <= other.end)


def _spans(log_dir):
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = {name: [] for name in SPANS}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in out:
                    out[ev.name].append(Span((plane.name, i), ev))
    return out


def _parent(span, candidates):
    parents = [p for p in candidates if span.within(p)]
    assert len(parents) == 1, (span.name, span.stats, len(parents))
    return parents[0]


@pytest.mark.parametrize("path", ["plain", "spec"])
def test_serving_spans_nest_and_carry_their_arguments(tmp_path, path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = _serve(path)
    finally:
        jax.profiler.stop_trace()
    sp = _spans(str(tmp_path))
    assert all(sp[name] for name in SPANS), {n: len(v) for n, v in sp.items()}

    # one step span per tick, numbered by the tick
    assert [s.stats["tick"] for s in sp["serving.step"]] == list(
        range(traced._ticks))
    for name in ("serving.admit", "serving.decode", "serving.retire",
                 "serving.occupancy"):
        for s in sp[name]:
            _parent(s, sp["serving.step"])

    # admission: every request once, with its prefill and first token
    # inside; the forked one prefills only its unshared suffix
    admits = {s.stats["rid"]: s for s in sp["serving.admit"]}
    assert sorted(admits) == sorted(traced.finished)
    assert any(s.stats["shared_tokens"] > 0 for s in admits.values())
    for name in ("serving.prefill", "serving.first_token"):
        assert len(sp[name]) == len(admits)
        for s in sp[name]:
            assert _parent(s, sp["serving.admit"]).stats["rid"] \
                == s.stats["rid"]
    for s in sp["serving.prefill"]:
        a = admits[s.stats["rid"]]
        assert s.stats["tokens"] == (a.stats["prompt_tokens"]
                                     - a.stats["shared_tokens"])
        chunks = [c for c in sp["serving.prefill.chunk"] if c.within(s)]
        assert len(chunks) == -(-s.stats["padded"] // CHUNK)
        assert [c.stats["start"] for c in chunks] == [
            a.stats["shared_tokens"] + CHUNK * i for i in range(len(chunks))]
    assert len(sp["serving.prefill.chunk"]) == sum(
        -(-s.stats["padded"] // CHUNK) for s in sp["serving.prefill"])

    # decode: the host wait and the bookkeeping inside each tick
    for name in ("serving.decode.wait", "serving.decode.advance"):
        assert len(sp[name]) == len(sp["serving.decode"])
        for s in sp[name]:
            _parent(s, sp["serving.decode"])
    assert all(1 <= s.stats["live"] <= 2 for s in sp["serving.decode"])
    assert sum(s.stats["finished"] for s in sp["serving.retire"]) == len(
        traced.finished)
    assert [s.stats["pages_used"] for s in sp["serving.occupancy"]] == \
        traced.occupancy_log

    # the profiler changes no token
    plain = _serve(path)
    assert plain.finished.keys() == traced.finished.keys()
    for rid, toks in traced.finished.items():
        np.testing.assert_array_equal(plain.finished[rid], toks)
