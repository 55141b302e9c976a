"""Drive the system under test: ``repro.serving.scheduler.Scheduler``.

``Timed`` is the harness's subclass of the scheduler.  It changes no
decision of the scheduler.  The tokens a request was served, and when,
come from the scheduler's public event log: after every ``step()`` the
harness stamps the host clock, and each entry of a request's
``token_ticks`` (one per emitted token, whatever a tick emits) takes the
stamp of the step that emitted it, since a caller of ``step()`` gets its
tokens when it returns.  The per-layer spans come from wrapping the
calls into each layer in host spans (``jax.profiler.TraceAnnotation``,
so a trace shows what the host was doing in every device gap):

  * each admission's prefill, from its start to its logits being ready
    (``block_until_ready``);
  * each decode tick (``_decode`` ends on a host copy of the tokens):
    how many rows were live and the keys each attended.

``drive`` offers the traffic: open loop on the mix's schedule (a request
is timed from when it was due, so a stall shows in the tail), closed
loop as callers that each wait for their last reply.
"""
from __future__ import annotations

import collections
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from harness.traffic import Request, padded_prompt_lengths

clock = time.perf_counter


@dataclasses.dataclass
class Record:
    """What the harness saw of one request (times on ``clock``)."""
    req: Request
    due: float                       # absolute
    submitted: float | None = None
    prefill: tuple | None = None     # (start, end, prompt_len, start_pos)
    token_times: list = dataclasses.field(default_factory=list)

    @property
    def first(self) -> float | None:
        return self.token_times[0] if self.token_times else None


@dataclasses.dataclass
class Tick:
    start: float
    end: float
    live: int
    contexts: tuple                  # keys each live row attended


def scheduler_class():
    from repro.serving.scheduler import Scheduler

    class Timed(Scheduler):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            self.records: dict[int, Record] = {}
            self.ticks: list[Tick] = []
            self.spans: list[tuple] = []     # (name, start, end)
            self._fifo: collections.deque[int] = collections.deque()

        def offer(self, rec: Record) -> None:
            rec.submitted = clock()
            self.records[rec.req.rid] = rec
            self._fifo.append(rec.req.rid)
            self.submit(rec.req.prompt, rec.req.max_new, rid=rec.req.rid)

        def _span(self, name, t0):
            self.spans.append((name, t0, clock()))

        def step(self):
            t0 = clock()
            with TraceAnnotation("bench.step"):
                done = super().step()
            self._span("step", t0)
            self._stamp(done)
            return done

        def _stamp(self, done):
            """Give every token the step just emitted the step's end."""
            now, tick = clock(), len(self.occupancy_log) - 1
            ticks = [(s.req.rid, s.token_ticks) for s in self.slots
                     if s is not None]
            ticks += [(rid, self.request_log[rid]["token_ticks"])
                      for rid in done]
            for rid, tt in ticks:
                times = self.records[rid].token_times
                for k in tt[len(times):]:
                    assert k == tick, (rid, k, tick)
                    times.append(now)

        def _admit(self):
            t0 = clock()
            with TraceAnnotation("bench.admit"):
                super()._admit()
            self._span("admit", t0)

        def _prefill_slot(self, b, prompt, start):
            # admissions are FIFO: the next one is the oldest offered
            rec = self.records[self._fifo.popleft()]
            assert rec.req.prompt.size == prompt.size
            t0 = clock()
            with TraceAnnotation("bench.prefill"):
                logits = jax.block_until_ready(
                    super()._prefill_slot(b, prompt, start))
            t1 = clock()
            self.spans.append(("prefill", t0, t1))
            rec.prefill = (t0, t1, int(prompt.size), int(start))
            return logits

        def _decode(self):
            live = [(s.req.rid, len(s.generated)) for s in self.slots
                    if s is not None]
            if not live:
                return super()._decode()
            contexts = tuple(self.records[r].req.prompt.size + n
                             for r, n in live)
            t0 = clock()
            with TraceAnnotation("bench.decode"):
                super()._decode()
            t1 = clock()
            self.spans.append(("decode", t0, t1))
            self.ticks.append(Tick(t0, t1, len(live), contexts))

        def _retire(self):
            t0 = clock()
            with TraceAnnotation("bench.retire"):
                done = super()._retire()
            self._span("retire", t0)
            return done

    return Timed


def make_scheduler(params, cfg, serving: dict):
    """The scheduler as the configuration deploys it: paged dynamic KV
    pool, chunked prefill, greedy decoding."""
    from repro.serving.cache import CacheConfig

    config = CacheConfig(layout="paged", alloc="dynamic",
                         page_size=serving["page_size"],
                         pool_pages=serving["pool_pages"],
                         kv_quant=serving.get("kv_quant", "none"))
    return scheduler_class()(
        params, cfg, slots=serving["slots"], max_len=serving["max_len"],
        config=config, prefill_chunk=serving["prefill_chunk"],
        bucket=serving["bucket"], dtype=jnp.dtype(cfg.dtype))


def warm_up(sched, traffic: dict, serving: dict, vocab: int, seed: int):
    """Run every shape the mix will use once: a prompt of each padded
    length it can draw (each pads and chunks differently on the host),
    the full-width decode tick, admission and retirement.  Returns the
    seconds taken."""
    t0 = clock()
    rng = np.random.default_rng([seed % (1 << 64), 7])
    lens = padded_prompt_lengths(traffic, serving["bucket"])
    base = 1 << 30
    for i, n in enumerate(lens):
        prompt = rng.integers(0, vocab, n, dtype=np.int32)
        sched.offer(Record(Request(base + i, prompt, 2, 0.0), clock()))
    while sched.queue or sched.n_active:
        sched.step()
    sched.records.clear()
    sched.ticks.clear()
    sched.spans.clear()
    return clock() - t0


@dataclasses.dataclass
class Window:
    start: float
    end: float
    records: dict
    ticks: list
    spans: list
    lateness: list          # seconds each submission ran behind its due time
    trace: tuple | None     # (start, end) of the traced slice, or None
    unfinished: int         # due in the window, no first token by the cut


def drive(sched, reqs: list[Request], traffic: dict, seconds: float,
          trace_from: float | None = None, start_trace=None,
          drain_s: float = 60.0) -> Window:
    """Offer ``reqs`` through the mix's pre-roll (``preroll_s``) and a
    window of ``seconds``, then keep stepping until every request due in
    the window has its first token (at most ``drain_s`` past the close).
    Due times are seconds from the window's opening.  ``start_trace`` is
    called between two steps once ``trace_from`` seconds of the window
    have passed; the caller stops the profiler after the drain, and a
    ``bench.window_close`` span marks the close on the trace's clock."""
    closed = traffic["loop"] == "closed"
    lateness, traced = [], None
    t0 = clock() + float(traffic.get("preroll_s", 0.0))   # window opens
    end = t0 + seconds

    def offer(req, due):
        rec = Record(req, due)
        sched.offer(rec)
        lateness.append(rec.submitted - due)

    pending = collections.deque(reqs)
    if closed:
        for _ in range(traffic["clients"]):
            offer(pending.popleft(), clock())
    while True:
        now = clock()
        if now >= end:
            break
        if trace_from is not None and traced is None \
                and now >= t0 + trace_from:
            start_trace()
            traced = (clock(), end)
        while not closed and pending and t0 + pending[0].due <= now:
            req = pending.popleft()
            offer(req, t0 + req.due)
        if not sched.queue and not sched.n_active:
            nxt = end if closed or not pending else t0 + pending[0].due
            wait = min(nxt, end) - clock()
            if wait > 0:
                ts = clock()
                with TraceAnnotation("bench.gen_wait"):
                    time.sleep(wait)
                sched.spans.append(("gen_wait", ts, clock()))
            continue
        for rid in sched.step():
            # a closed-loop caller sends its next request on the reply
            if closed and pending:
                offer(pending.popleft(), sched.records[rid].token_times[-1])
    with TraceAnnotation("bench.window_close"):
        pass
    # requests that fell due before the close but after the last check
    while not closed and pending and t0 + pending[0].due < end:
        req = pending.popleft()
        offer(req, t0 + req.due)
    due = [r for r in sched.records.values() if t0 <= r.due < end]
    cut = end + drain_s
    while any(r.first is None for r in due) and clock() < cut:
        sched.step()
    return Window(t0, end, sched.records, sched.ticks, sched.spans,
                  lateness, traced, sum(r.first is None for r in due))
