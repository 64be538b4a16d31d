"""Read a cell's correctness numbers for the program and for the control.

    python3 bench/control.py --workload qwen2-1.5b.chat --seeds 11,12,13 \
        --seconds 15

For each seed, in one process: a run of the cell as ``bench/run.py``
makes it (a shorter window is enough to serve the sample), then the
numbers of the served sample against the reference, and the same
numbers with the reference standing in for the program at the same
prompts and tokens: the control (a float8_e4m3fn body and a bfloat16
head) and the head's control (a float32 body and a bfloat16 head).  The
limits in ``bench/limits/`` lie between the program's largest readings
and the control's smallest.  Prints one JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import run, spec
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 3
    run.use_compile_cache()
    cell = spec.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(cell, seed, args.seconds, False, jax.devices(),
                           log=lambda m: print(m, file=sys.stderr),
                           controls=("control", "head_control"))
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "program": out["_numbers"], **out["_controls"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
