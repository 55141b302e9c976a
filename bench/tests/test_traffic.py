"""The generator: one schedule for every seed, the seed's own tokens."""
import numpy as np
from conftest import BENCH

from harness import spec, traffic

CHAT = spec.read_json(BENCH / "traffic" / "chat.json")


def schedule(mix, seed, seconds=20.0, vocab=1000):
    return traffic.generate(mix, seconds, seed, vocab)


def test_same_seed_same_schedule():
    a, b = schedule(CHAT, 2 ** 31 + 11), schedule(CHAT, 2 ** 31 + 11)
    assert [(r.due, r.max_new, r.prompt.tolist()) for r in a] == [
        (r.due, r.max_new, r.prompt.tolist()) for r in b]


def test_every_seed_gets_the_same_schedule():
    a, b = schedule(CHAT, 1), schedule(CHAT, -5)
    assert [(r.due, r.prompt.size, r.max_new) for r in a] == [
        (r.due, r.prompt.size, r.max_new) for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, b))


def test_the_schedule_is_not_in_length_order():
    lens = [r.prompt.size for r in schedule(CHAT, 1) if r.due >= 0]
    assert lens != sorted(lens) and lens != sorted(lens, reverse=True)


def test_open_loop_rate_and_bounds():
    reqs = schedule(dict(CHAT, preroll_s=0.0), 3, seconds=40.0)
    assert len(reqs) == round(CHAT["rate_per_s"] * 40)
    due = [r.due for r in reqs]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 40.0
    lens = np.array([r.prompt.size for r in reqs])
    assert lens.min() >= CHAT["prompt"]["min"]
    assert lens.max() <= CHAT["prompt"]["max"]
    assert abs(np.median(lens) - CHAT["prompt"]["median"]) < 0.1 * 512


def test_preroll_comes_before_the_window():
    mix = dict(CHAT, preroll_s=10.0)
    reqs = schedule(mix, 3, seconds=30.0)
    due = np.array([r.due for r in reqs])
    assert np.sum(due < 0) == round(CHAT["rate_per_s"] * 10)
    assert np.sum(due >= 0) == round(CHAT["rate_per_s"] * 30)
    assert due[0] == -10.0 and due.max() < 30.0
    assert np.all(np.diff(due) > 0)


def test_every_seed_times_the_same_requests():
    mix = dict(CHAT, preroll_s=10.0)

    def timed(seed):
        return sorted((r.prompt.size, r.max_new)
                      for r in schedule(mix, seed) if r.due >= 0)

    assert timed(2 ** 33 + 1) == timed(7)


def test_closed_loop_pool():
    mix = dict(CHAT, loop="closed", clients=8)
    reqs = schedule(mix, 5)
    assert all(r.due is None for r in reqs)
    assert len(reqs) == 8 * traffic.CLOSED_POOL_PER_CLIENT


def test_padded_lengths_cover_the_mix():
    lens = traffic.padded_prompt_lengths(CHAT, 256)
    assert lens[0] == 256 and lens[-1] == 4096 and len(lens) == 16
