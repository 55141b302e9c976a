"""``correct`` at a size a test can hold: sound runs pass, the control and
each fault the served path can have fail.

These drive the whole run (set-up, window, reference) on the CPU with the
device check skipped; only the served path is broken underneath.
"""
import time

import numpy as np
import pytest
from conftest import TINY_GLM_LIMIT, TINY_LIMIT

import control
from harness import runner, serve


def run(root, seed=20240611, seconds=2.0, cell="tiny.small"):
    return runner.run(cell, seed, seconds, False,
                      time.perf_counter(), root=root,
                      bench_dir=root / "bench", require_tpu=False,
                      compile_cache=False, out_dir=str(root / "out"))


def test_sound_run_is_correct(tiny):
    res = run(tiny)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"output_tok_per_s", "ttft_p50_ms",
                                   "itl_p95_ms", "setup_s"}
    assert list(res)[-1] == "compared"


def test_controls_are_not_correct(tiny):
    """Judged by the run's own comparison, the sound runs are correct and
    both controls are not: the int4 reference in the program's place and
    the program's own int8 K/V path.  The limit sits between the sound
    readings and the int4 control's."""
    rows = list(control.read("tiny.small", [3, 4, 5], 2.0, root=tiny,
                             bench_dir=tiny / "bench", require_tpu=False,
                             compile_cache=False,
                             out_dir=str(tiny / "out")))
    assert all(r["sound"]["correct"] for r in rows)
    assert not any(r[c]["correct"] for r in rows for c in ("w4", "kv8"))
    sound = max(r["sound"]["value"] for r in rows)
    w4 = min(r["w4"]["value"] for r in rows)
    assert sound < TINY_LIMIT < w4
    assert w4 >= 3 * sound
    for r in rows:
        assert r["kv8"]["compared"]["kv_bits_below_config"]["value"] == 8
        assert r["w4"]["compared"]["max_logit_gap"]["value"] > TINY_LIMIT


def test_partial_rope_cell_sound_and_controls(tiny_glm):
    """The chatglm3 block (partial RoPE, untied LM head, 4 query heads to a
    K/V head) at toy widths: a sound run is correct, and the int4 control
    and the int8 K/V path are not."""
    assert run(tiny_glm, cell="tiny-glm.small")["correct"]
    rows = list(control.read("tiny-glm.small", [3, 4, 5], 2.0,
                             root=tiny_glm, bench_dir=tiny_glm / "bench",
                             require_tpu=False, compile_cache=False,
                             out_dir=str(tiny_glm / "out")))
    assert all(r["sound"]["correct"] for r in rows)
    assert not any(r[c]["correct"] for r in rows for c in ("w4", "kv8"))
    sound = max(r["sound"]["value"] for r in rows)
    assert sound < TINY_GLM_LIMIT < min(r["w4"]["value"] for r in rows)


def test_tokens_take_the_time_of_the_step_that_emitted_them():
    """One stamp per entry of ``token_ticks``, however many a tick
    emits (speculative decode emits several)."""
    timed = object.__new__(serve.scheduler_class())
    req = serve.Request(5, np.zeros(3, np.int32), 8, 0.0)
    rec = serve.Record(req, 0.0, token_times=[1.0])
    live = type("S", (), {"req": req, "token_ticks": [0, 1, 1, 1]})()
    timed.slots, timed.records = [None, live], {5: rec}
    timed.occupancy_log, timed.request_log = [0, 0], {}
    timed._stamp([])
    assert len(rec.token_times) == 4 and rec.token_times[0] == 1.0
    assert len(set(rec.token_times[1:])) == 1


def _faulty(kind):
    base = serve.scheduler_class()

    class Faulty(base):
        def _decode(self):
            before = {k: v.copy() for k, v in self.cache.items()}
            n = {id(s): len(s.generated) for s in self.slots if s}
            super()._decode()
            if kind == "state_unchanged":
                # the step hands back the cache it was given
                self.cache = before
                return
            for b, s in enumerate(self.slots):
                if s is None or len(s.generated) == n[id(s)]:
                    continue
                if kind == "half_batch" and b % 2:
                    # odd rows left out: they repeat their last token
                    s.generated[-1] = s.generated[-2]
                elif kind == "token_altered" and len(self.ticks) % 3 == 0:
                    s.generated[-1] = (s.generated[-1]
                                       + self.cfg.vocab_size // 2
                                       ) % self.cfg.vocab_size
                s.last_token = s.generated[-1]

    return lambda: Faulty


@pytest.mark.parametrize("fixture,cell,limit", [
    ("tiny", "tiny.small", TINY_LIMIT),
    ("tiny_glm", "tiny-glm.small", TINY_GLM_LIMIT)])
@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "token_altered"])
def test_a_broken_served_path_is_not_correct(request, monkeypatch, kind,
                                             fixture, cell, limit):
    root = request.getfixturevalue(fixture)
    monkeypatch.setattr(serve, "scheduler_class", _faulty(kind))
    res = run(root, cell=cell)
    assert not res["correct"], (kind, res["compared"])
    assert res["compared"]["max_logit_gap"]["value"] > limit


def test_pick_holds_the_longest_request():
    recs = [serve.Record(serve.Request(i, np.zeros(10 + i, np.int32),
                                       4 + i % 5, 0.0), 0.0)
            for i in range(40)]
    picked = runner.pick(recs, 7)
    assert picked[0] == 39              # prompt 49 + 8 new: the longest
    assert 1 < len(picked) <= runner.SAMPLE_MAX
    assert len(set(picked)) == len(picked)
    assert picked == runner.pick(recs, 7)
    assert runner.pick(recs, 8) != picked
