"""Engine: host milliseconds of admission prefill (``_prefill_slot`` to
``block_until_ready``) per 1,000 prompt tokens prefilled, over the
admissions that started in the window.  Padding is not counted."""


def read(run):
    w = run.window
    pre = [r.prefill for r in w.records.values()
           if r.prefill is not None and w.start <= r.prefill[0] < w.end]
    tokens = sum(n - start for _, _, n, start in pre)
    if not tokens:
        return None
    return sum(t1 - t0 for t0, t1, _, _ in pre) * 1e3 / (tokens / 1e3)
