"""One run of one cell: set-up, the measured window, the check, the line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``, from process start to the window): the device check,
the seed's weights built on the device in their served form, the
scheduler with the configuration's KV pool, a warm-up that runs every
shape the mix uses, so nothing compiles in the window, and the mix's
pre-roll, which brings the batch to its steady state.  The window offers
the mix for ``--seconds`` and then keeps stepping until every request due
in it has its first token.  After it, the device's peak memory is read,
the program's state is freed, and the plain reference checks a sample of
the requests finished by then (``pick``, ``check``); the K/V pool the
window ran on must be stored as the configuration states (``judge``).

With ``--trace 1`` the profiler records the window's last seconds and the
drain; the per-layer metrics are read from that run.

The last line of standard output is one JSON object; the numbers compared
are the last lines of standard error and the last key of that object.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import sys

import numpy as np

from harness import costs, devtrace, serve, spec, stats, traffic, weights

TRACE_SECONDS = 3.0     # length of the traced slice at the window's end
SAMPLE_TOKENS = 384     # served tokens the reference reads, at least
SAMPLE_MAX = 16         # requests the reference reads, at most


class NoDevice(RuntimeError):
    """The machine lacks the accelerator the cell needs."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def check_device(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX's first device is "
                       f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, {len(devices)} found")
    return devices


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (its fixed directory,
    or the one ``JAX_COMPILATION_CACHE_DIR`` names), keeping every
    program however quickly it compiled, so that a later run of the cell
    finds all that the first compiled."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return enable()


def program_config(config: dict):
    """The program's model config as the configuration file states it;
    refuses one whose widths differ from the file's ``shapes``."""
    from repro.configs import get_config

    cfg = get_config(config["model"]).replace(**config["overrides"])
    s = config["shapes"]
    have = {"layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "d_ff": cfg.d_ff,
            "vocab": cfg.vocab_size, "tied_embeddings": cfg.tie_embeddings,
            "qkv_bias": cfg.qkv_bias, "norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta,
            "rope_fraction": (cfg.rope_fraction
                              if cfg.rope_style == "partial" else 1.0)}
    bad = {k: (v, s[k]) for k, v in have.items() if v != s[k]}
    if bad or cfg.ffn_type != "swiglu" or cfg.norm_type != "rmsnorm":
        raise ValueError(f"program config {cfg.name} differs from "
                         f"{config['name']}.json: {bad}")
    return cfg


def pick(records: list, seed: int) -> list[int]:
    """The requests the reference will read, drawn from the seed among
    ``records`` (the finished ones): the longest (prompt + output) first,
    then others until ``SAMPLE_TOKENS`` output tokens or ``SAMPLE_MAX``
    requests."""
    if not records:
        return []
    by = {r.req.rid: r.req for r in records}
    longest = max(by, key=lambda i: (by[i].prompt.size + by[i].max_new, i))
    picked, n = [longest], by[longest].max_new
    for i in traffic.rng_for(seed, 2).permutation(sorted(by)):
        if n >= SAMPLE_TOKENS or len(picked) >= SAMPLE_MAX:
            break
        if i != longest:
            picked.append(int(i))
            n += by[i].max_new
    return picked


def served_in_window(window, finished: dict) -> list:
    """Records of the finished requests that the window served: a token
    of theirs reached the host inside it."""
    return [r for i, r in window.records.items() if i in finished
            and any(window.start <= t <= window.end for t in r.token_times)]


def served(records: dict, finished: dict, rids) -> list:
    """(prompt, served tokens) of the picked requests that finished."""
    return [(records[i].req.prompt, np.asarray(finished[i], np.int32))
            for i in rids if i in finished]


def prepare(cell, *, require_tpu: bool = True, compile_cache: bool = True):
    """Device check, compile cache, and the program's model config.
    Returns (program config, shapes, devices)."""
    import jax

    devices = check_device(cell.chips) if require_tpu else jax.devices()
    if compile_cache:
        log(f"compile cache: {enable_compile_cache()}")
    from repro.kernels.tiled_matmul.ops import kernel_mode
    if require_tpu and kernel_mode() != "pallas":
        raise NoDevice(f"kernel mode {kernel_mode()!r}, not 'pallas'")
    return program_config(cell.config), costs.Shapes.of(cell.config), devices


def scheduler(cell, cfg, shapes, seed: int):
    """The seed's weights, built on the device, behind a fresh scheduler."""
    t = serve.clock()
    params = weights.program_params(weights.build(seed, shapes), shapes)
    log(f"weights built in {serve.clock() - t:.3f} s")
    return serve.make_scheduler(params, cfg, cell.config["serving"])


def kv_bits_below_config(cell, sched) -> int:
    """Bits by which the K/V pool the window ran on is stored below the
    configuration's ``kv_dtype`` (0 when it is as stated or wider)."""
    import jax.numpy as jnp

    stated = jnp.dtype(cell.config["serving"]["kv_dtype"]).itemsize * 8
    pools = [v for k, v in sched.cache.items()
             if k in ("k_pages", "v_pages", "k", "v")]
    return max(stated - min(v.dtype.itemsize * 8 for v in pools), 0)


def judge(cell, gap: float, unfinished: int, kv_bits: int,
          n_seqs: int) -> tuple[dict, bool]:
    """The numbers ``correct`` compares, each with its limit, and
    ``correct``: every number within its limit, with a sample read."""
    compared = {
        "max_logit_gap": {"value": gap,
                          "limit": cell.limits["max_logit_gap"]["limit"]},
        "due_without_first_token": {"value": unfinished, "limit": 0},
        "kv_bits_below_config": {"value": kv_bits, "limit": 0},
    }
    ok = n_seqs > 0 and all(c["value"] <= c["limit"]
                            for c in compared.values())
    return compared, ok


def with_serving(cell, **serving):
    """``cell`` with its serving geometry changed (the controls)."""
    config = dict(cell.config, serving={**cell.config["serving"],
                                        **serving})
    return dataclasses.replace(cell, config=config)


def check(cell, seed: int, shapes, seqs, controls=()) -> dict:
    """The reference's readings over ``seqs``, with fresh weights from the
    seed (the program's are freed by now)."""
    ref = spec.reference_module(cell)
    w = weights.build(seed, shapes)
    try:
        return ref.readings(w, cell.config["shapes"],
                            cell.config["serving"]["max_len"], seqs,
                            controls)
    finally:
        del w
        gc.collect()


def compile_counter():
    """A counter of programs lowered (compiled or loaded) from now on."""
    from jax import monitoring

    box = {"n": 0, "on": True}

    def listen(name, _secs, **_kw):
        if box["on"] and name.endswith("jaxpr_to_mlir_module_duration"):
            box["n"] += 1

    monitoring.register_event_duration_secs_listener(listen)
    return box


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, *, root=spec.ROOT, bench_dir=None, **kw) -> dict:
    """One run of the cell ``workload``; returns the result line's
    object."""
    return run_cell(spec.load_cell(workload, root, bench_dir), seed,
                    seconds, trace, t_start, root=root, **kw)


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, root=spec.ROOT, require_tpu: bool = True,
             compile_cache: bool = True, out_dir: str | None = None,
             controls=()) -> dict:
    """One run of ``cell``.  With ``controls`` (reference variants), the
    result also holds under ``controls`` each control's reading, put in
    the program's place and judged as the program's is."""
    import jax

    cfg, shapes, devices = prepare(cell, require_tpu=require_tpu,
                                   compile_cache=compile_cache)
    sched = scheduler(cell, cfg, shapes, seed)
    took = serve.warm_up(sched, cell.traffic, cell.config["serving"],
                         shapes.vocab, seed)
    log(f"warm-up {took:.3f} s")
    dev = devices[0]
    peaks = (spec.peaks(cell.bench_dir, dev.device_kind) if require_tpu
             else None)
    reqs = traffic.generate(cell.traffic, seconds, seed, shapes.vocab)

    trace_dir = os.path.join(out_dir or os.path.join(str(root),
                                                     ".bench_out"),
                             "trace")
    trace_from = None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_from = seconds - min(TRACE_SECONDS, seconds / 2)
    compiles = compile_counter()
    window = serve.drive(sched, reqs, cell.traffic, seconds,
                         trace_from=trace_from,
                         start_trace=lambda: jax.profiler.start_trace(
                             trace_dir))
    compiles["on"] = False
    setup_s = window.start - t_start
    if window.trace is not None:
        jax.profiler.stop_trace()
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    picked = pick(served_in_window(window, sched.finished), seed)
    seqs = served(window.records, sched.finished, picked)
    kv_bits = kv_bits_below_config(cell, sched)
    del sched
    gc.collect()

    run_ = stats.Run(cell, shapes, peaks, seconds, setup_s, window)
    if trace:
        run_.events = devtrace.load(trace_dir)
        run_.trace_bounds = devtrace.bounds(run_.events)
        run_.trace_host = window.trace
        shutil.rmtree(trace_dir, ignore_errors=True)

    due = stats.due_in_window(run_)
    lat = np.asarray(window.lateness)
    log(f"window: {len(due)} requests due, {window.unfinished} without a "
        f"first token; {stats.tokens_in_window(run_)} tokens, "
        f"{len(stats.gaps_in_window(run_))} gaps, {len(window.ticks)} "
        f"decode ticks; generator late by median "
        f"{np.median(lat) * 1e3:.3f} ms, max {lat.max() * 1e3:.3f} ms; "
        f"programs lowered after warm-up: {compiles['n']}")

    readings = check(cell, seed, shapes, seqs, controls)
    log(f"reference: {len(seqs)} finished requests, {readings['tokens']} "
        f"served "
        f"tokens, widest gap per request {readings['per_request']}")
    compared, correct = judge(cell, readings["served"], window.unfinished,
                              kv_bits, len(seqs))

    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = spec.metric_reader(cell.bench_dir, m["name"])(run_)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": bool(correct), "attempted": len(due),
              "failed": int(window.unfinished), "metrics": metrics,
              "device": device}
    if trace and run_.trace_bounds:
        lo, hi = run_.trace_bounds
        device["busy_s"] = devtrace.busy_seconds(run_.events, lo, hi)
        device["window_s"] = hi - lo
        result["breakdown"] = {
            "device_ops": devtrace.top_ops(run_.events, lo, hi),
            "idle_gaps": devtrace.longest_gaps(run_.events, lo, hi)}
    if controls:
        # each control in the program's place, judged as the program is
        result["controls"] = {}
        for c in controls:
            cmp_c, ok_c = judge(cell, readings[c], window.unfinished,
                                kv_bits, len(seqs))
            result["controls"][c] = {"value": readings[c], "correct": ok_c,
                                     "compared": cmp_c}
    result["compared"] = compared
    return result
