"""Model step: operations the window's tokens need, over the window's
seconds times the chip's int8 peak.  Prompt tokens count when their
admission's prefill ended in the window, output tokens when they reached
the host in it; operations as the cell's architecture module counts
them (``prefill_ops``, ``decode_ops``: the least work, no padding, no
recomputation)."""


def read(run):
    if not run.peaks:
        return None
    w, s, arch = run.window, run.shapes, run.arch
    ops = 0
    for r in w.records.values():
        if r.prefill is not None and w.start <= r.prefill[1] <= w.end:
            _, _, n, start = r.prefill
            ops += arch.prefill_ops(s, start, n)
        for i, t in enumerate(r.token_times[1:], start=1):
            if w.start <= t <= w.end:
                # the i-th output token came from a step that attended
                # the prompt and the i tokens before it
                ops += arch.decode_ops(s, r.req.prompt.size + i)
    return 100.0 * ops / ((w.end - w.start) * run.peaks["int8_ops_per_s"])
