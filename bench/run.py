"""Run one cell of BENCHMARK.json once on this machine's TPU.

    python3 bench/run.py --workload qwen2-1.5b.chat --seed 7 --seconds 51 --trace 0

Loads the cell's configuration and mix, makes the weights from the seed,
builds the engine, warms up every program the mix can ask for (all of it
``setup_s``), measures ``--seconds`` of open-loop traffic, then checks a
sample of what was served against the plain reference.  With
``--trace 1`` the first ``TRACE_SECONDS`` of the window are traced and
the per-layer metrics are read from the trace, the program's counters
and the harness's stamps; with ``--trace 0`` the end-to-end metrics are
printed.

The last line of stdout is one JSON object; the last lines of stderr
give each number the correctness check compared, beside its limit.  Off
a TPU, or with fewer chips than the cell asks for, it prints no result
and exits with 3; without the program beside it, with 2.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
# a traced run traces the first seconds of its window: enough steps for
# the device's shares, and a trace that the run can still read in time
TRACE_SECONDS = 12.0


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, for every program however small, so that a cell's second
    run compiles nothing."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileLog:
    """Backend compiles, stamped on the host clock as they end."""

    def __init__(self):
        self.ends: list[tuple[float, float]] = []
        self.cache_hits = 0

    def duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.ends.append((time.perf_counter(), duration))

    def event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t < t1 for t, _ in self.ends)


def _percentile(values, q):
    import numpy as np
    if not values:
        raise ValueError("the window holds no sample of this latency; "
                         "lengthen --seconds")
    return float(np.percentile(np.asarray(values, float), q))


def end_to_end(reqs, win, seconds) -> dict:
    """The cell's end-to-end readings from the token stamps."""
    times = [t for r in reqs for t in r.tokens.times]
    out = sum(win.inside(t) for t in times)
    first = [r.tokens.times[0] - r.t_submit for r in reqs
             if r.tokens.times and win.inside(r.tokens.times[0])]
    tpot = [(r.tokens.times[-1] - r.tokens.times[0])
            / (len(r.tokens.times) - 1) for r in reqs
            if len(r.tokens.times) > 1 and win.inside(r.tokens.times[-1])
            and r.state == "finished"]
    return {"tok_s": out / seconds,
            "ttft_p95_ms": 1e3 * _percentile(first, 95),
            "tpot_p95_ms": 1e3 * _percentile(tpot, 95),
            "_counts": (out, len(first), len(tpot))}


def _served(r) -> dict:
    import numpy as np
    return {"prompt": np.asarray(r.prompt), "tokens": list(r.tokens),
            "H": list(r.H), "SE": list(r.SE), "MI": list(r.MI),
            "p_max": list(r.p_max)}


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             log=print, controls: tuple = ()) -> dict:
    """Every step of a run after the look for a chip; returns the result
    object (its ``checks`` key last) and writes progress through ``log``.

    ``controls`` names reference precisions to read at the same served
    prompts and tokens in the program's place (``bench/control.py``);
    their numbers go under ``_controls``."""
    import jax
    from bench import check, serve, spec, traffic, weights
    from bench import trace as tr

    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles.duration)
    jax.monitoring.register_event_listener(compiles.event)
    config, mix = cell.config, cell.traffic
    eng = config["engine"]
    chunk = eng["decode_chunk"]
    vocab = config["vocab_size"]
    used = devices[:cell.chips]

    cfg, params = serve.program(config, seed, cell.root)
    jax.block_until_ready(params)
    engine = serve.build_engine(config, mix, params, cfg, seed)
    t0 = time.perf_counter()
    engine.run(serve.warmup_requests(config, mix, vocab, seed))
    t_warm = time.perf_counter() - t0
    period = serve.calibrate(engine, config, mix, vocab, seed)
    items = traffic.schedule(mix, vocab, seed)
    opening = traffic.opening_size(mix)
    lead = chunk * sum(-(-len(it.prompt) // eng["prefill_chunk"])
                       for it in items[:opening])
    # arrivals for the window and a tenth more: those that join after
    # the close keep the load steady until it and are cut at their first
    # token (``serve.Window.cut``), so the run ends soon after the close
    n = traffic.requests_for(
        mix, lead + (1.1 * seconds + 2.0) * chunk / period)
    log(f"setup: warm-up {t_warm:.2f} s, {len(compiles.ends)} compiles "
        f"({compiles.cache_hits} persistent-cache hits); "
        f"{period * 1e3:.2f} ms per engine iteration; schedule of {n} "
        f"requests ({opening} opening)")

    tdir = tempfile.TemporaryDirectory(prefix="bench-trace-") \
        if trace else None
    spans = []

    def trace_on():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir.name, profiler_options=opts)
        # the window's span in the trace, for the idle gaps' labels
        spans.append(jax.profiler.TraceAnnotation("engine.run window"))
        spans[0].__enter__()

    def trace_off():
        spans[0].__exit__(None, None, None)
        jax.profiler.stop_trace()

    win = serve.Window(seconds, set(range(opening)), chunk=chunk,
                       on_open=trace_on if trace else None,
                       mark=min(TRACE_SECONDS, seconds) if trace else None,
                       on_mark=trace_off if trace else None)
    reqs = serve.window_requests(items, n, win)
    # everything set-up made is long-lived: keep the collector's full
    # passes inside the window from walking it
    gc.collect()
    gc.freeze()
    t_run = time.perf_counter()
    results = engine.run(reqs)
    t_end = time.perf_counter()
    if win.t_close is None:
        # the schedule ran out before the window closed
        win.t_close = win.t_open + seconds
    if trace and win.t_mark is None:
        win.t_mark = time.perf_counter()
        trace_off()
    # a CPU backend (the tests) keeps no memory statistics
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    setup_s = win.t_open - T_START
    e2e = end_to_end(reqs, win, seconds)
    in_window = compiles.between(win.t_open, win.t_close)
    attempted = [r for r in reqs if win.inside(r.t_submit)]
    # a request that joined in the window and was still decoding a
    # minute after the close was cut there: it failed
    failed = sum(r.state != "finished" or not len(r.tokens)
                 or r.rid in win.cut_late for r in attempted)
    out, n_first, n_tpot = e2e.pop("_counts")
    log(f"window: {seconds:g} s from {win.t_open - t_run:.2f} s into "
        f"engine.run; the run went {t_end - win.t_close:.2f} s past it; "
        f"{len(attempted)} requests joined the queue in it, {n_first} "
        f"first tokens, {n_tpot} finished, {out} tokens; "
        f"{len(win.cut_late)} cut {win.grace:g} s after it; "
        f"{in_window} backend compiles inside it")
    if t_end < win.t_close:
        log(f"WARNING: the schedule ended {win.t_close - t_end:.2f} s "
            f"before the window closed")

    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": int(peak)}
    result = {"correct": False, "attempted": len(attempted),
              "failed": int(failed)}
    if trace:
        red = tr.reduce(tdir.name, n_chips=len(used))
        tdir.cleanup()
        ctx = {"config": config, "mix": mix, "engine": eng,
               "requests": reqs, "window": (win.t_open, win.t_close),
               "harvests": win.decode_rows,
               "traced": (win.t_open, win.t_mark),
               "results": results, "trace": red,
               "peak": spec.peak(used[0].device_kind)}
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"], cell.root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = red["breakdown"]
    else:
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = device

    # the reference runs once the program's state is freed
    served = check.sample([r for r in reqs if r.tokens.times
                           and win.inside(r.tokens.times[0])]
                          or [r for r in reqs if len(r.tokens)], seed,
                          int(mix["check_requests"]))
    served = [_served(r) for r in served]
    del engine, params, reqs, results, items
    gc.unfreeze()
    gc.collect()
    t0 = time.perf_counter()
    ref = spec.reference_module(config["reference"], cell.root)
    w = weights.generate(config, seed, cell.root)
    per_pos = check.readings(ref, w, config, served, seed)
    nums = check.numbers(per_pos)
    ok, lines = check.judge(nums, cell.limits)
    log(f"check: {len(served)} requests, {len(per_pos['gap'])} served "
        f"tokens against the reference in {time.perf_counter() - t0:.1f} s; "
        "readings " + ", ".join(f"{k} {v:.6g}" for k, v in nums.items()))
    result["_numbers"] = nums
    result["_controls"] = {
        p: check.numbers(check.readings(ref, w, config, served, seed,
                                        stand_in=p)) for p in controls}
    result["correct"] = bool(ok)
    result["checks"] = {k: {"value": nums[k], "limit": spec["limit"]}
                        for k, spec in cell.limits.items()}
    result["_lines"] = lines
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"run: the program (src/repro) is not beside {ROOT / 'bench'}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import spec
    cell = spec.load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"sees {len(devices)} {devices[0].platform} device(s). "
              f"Nothing was run.", file=sys.stderr)
        return 3
    use_compile_cache()

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    lines = result.pop("_lines")
    result.pop("_controls")
    result.pop("_numbers")
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
