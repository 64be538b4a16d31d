"""Sweep a cell's arrival rate to find its knee (run by hand, on a TPU).

    python3 bench/knee.py --workload qwen2-1.5b.chat --seed 7 \
        --rates 0.02,0.03,0.04,0.05 --steps 1500

Builds and warms the cell's engine once, then serves the cell's mix at
each rate (requests per decode step) for about ``--steps`` decode steps
and prints, per rate, the backlog (requests that joined the queue and
have no first token yet) averaged over the second and the last third of
the run, and the time to first token.  The knee is the highest rate at
which the backlog does not grow from the one third to the next.
Arrivals are counted in decode steps, so the knee is a property of the
engine's scheduling and holds for any step time.  Once the last request
has joined the queue, every request is cut at its next token: the
backlog is read up to that moment, and the drain after it is not
needed.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def sweep_window(serve):
    """A ``serve.Window`` that cuts every request once ``last`` (set
    after the requests are made) has joined the queue."""

    class Sweep(serve.Window):
        last = None

        def token(self, rid, first, t):
            pass

        def cut(self, req, t):
            return self.last is not None and self.last.t_submit > 0

    return Sweep(0.0, set())


def backlog(reqs, t: float) -> int:
    return sum(r.t_submit <= t and not (r.tokens.times
                                        and r.tokens.times[0] <= t)
               for r in reqs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--steps", type=float, default=1500)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import numpy as np
    from bench import run, serve, spec, traffic
    if jax.devices()[0].platform != "tpu":
        print("knee: no TPU", file=sys.stderr)
        return 3
    run.use_compile_cache()
    cell = spec.load_cell(args.workload)
    config, vocab = cell.config, cell.config["vocab_size"]
    cfg, params = serve.program(config, args.seed)
    engine = serve.build_engine(config, cell.traffic, params, cfg, args.seed)
    engine.run(serve.warmup_requests(config, cell.traffic, vocab, args.seed))
    for rate in (float(r) for r in args.rates.split(",")):
        mix = json.loads(json.dumps(cell.traffic))
        mix["arrivals"]["rate_per_step"] = rate
        items = traffic.schedule(mix, vocab, args.seed)
        n = traffic.requests_for(mix, args.steps)
        win = sweep_window(serve)
        reqs = [serve.make_request(i, it.prompt, it.max_new_tokens,
                                   it.arrival_step, win)
                for i, it in enumerate(items[:n])]
        win.last = max(reqs, key=lambda r: r.arrival_step)
        engine.run(reqs)
        t0 = min(r.t_submit for r in reqs)
        t1 = max(r.t_submit for r in reqs)
        thirds = [t0 + (t1 - t0) * f for f in np.linspace(1 / 3, 1, 7)]
        mid = np.mean([backlog(reqs, t) for t in thirds[:3]])
        last = np.mean([backlog(reqs, t) for t in thirds[3:]])
        ttft = [1e3 * (r.tokens.times[0] - r.t_submit) for r in reqs]
        print(json.dumps({
            "rate_per_step": rate, "requests": n,
            "seconds": round(t1 - t0, 2),
            "backlog_second_third": float(mid),
            "backlog_last_third": float(last),
            "ttft_p50_ms": float(np.percentile(ttft, 50)),
            "ttft_p95_ms": float(np.percentile(ttft, 95))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
