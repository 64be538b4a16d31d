"""Sharded-vs-unsharded serving parity checker + mesh scaling bench.

Runs the SAME staggered mixed-length traffic through an unsharded
``ServeEngine`` and a ``--mesh``-sharded one, in operand-entropy mode,
per attention family, and requires identical token ids and (H, SE, MI,
p_max) within ``FLOAT_ATOL``.  Serve-TP is all-gather-only
(sharding/partition.py): no cross-shard partial sum is ever reduced.
On XLA:CPU the floats are still not bitwise under jax 0.9, because it
picks a dot's f32 accumulation order from its shape: a column shard is
a narrower local dot than the unsharded one (an (…, 128) x (128, 8) dot
differs from the matching columns of the (128, 32) dot in the last
bits), so the two runs round the same sums differently.  On a TPU v5e
the sharded runner is bitwise (``chip_smoke.py --mesh 1x4``).

CPU needs forced devices (set BEFORE jax imports — hence a fresh
process):

  XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \
      python -m repro.launch.engine.mesh_check --families dense,moe

``--bench`` additionally measures decode tok/s at 1 device vs the
mesh (the ``mesh_scaling`` row of BENCH_serve.json); ``--json`` prints
a machine-readable result.  Exit code is non-zero on any mismatch.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import numpy as np

from repro.configs.registry import get_config, reduced
from repro.data.synthetic import TokenStreamState, token_batch

# one representative arch per attention family (all four serve paths:
# dense GQA, dropless MoE, hybrid ssm+attention, encdec
# cross-attention); dense additionally runs with the prefix cache on
FAMILIES = {
    "dense": "qwen2_1_5b",
    "moe": "deepseek_moe_16b",
    "hybrid": "zamba2_7b",
    "encdec": "seamless_m4t_medium",
}

# staggered mixed-length traffic: admissions, evictions, grants and
# (on dense) prefix hits all land at different chunks, so the sharded
# engine must reproduce the reference under a non-trivial schedule
PROMPTS = (9, 17, 5, 24, 12)
GENS = (6, 9, 5, 8, 7)
SHARED = 8          # dense: requests 1 and 3 reuse request 0's opening
                    # block (one kv_block) to exercise cached-hit decode


def make_traffic(cfg, family: str):
    from repro.launch.engine import Request
    reqs = []
    base = None
    for i, (p, g) in enumerate(zip(PROMPTS, GENS)):
        toks, _ = token_batch(
            TokenStreamState(seed=100 + i, host=0, num_hosts=1),
            1, p, cfg.vocab_size)
        prompt = np.asarray(toks, np.int32)[0].copy()
        if i == 0:
            base = prompt
        elif family == "dense" and i in (1, 3):
            prompt[:SHARED] = base[:SHARED]
        reqs.append(Request(rid=i, prompt=prompt, max_new_tokens=g))
    return reqs


def run_engine(params, cfg, family: str, mesh):
    from repro.launch.engine import ServeEngine
    eng = ServeEngine(
        params, cfg, num_slots=2, max_len=32, chunk=4,
        kv_layout="paged", kv_block=8, kv_blocks=12,
        prefill_mode="chunked", prefill_chunk=8,
        prefix_cache=family == "dense", trace_every=4, mesh=mesh)
    return eng, eng.run(make_traffic(cfg, family))


# Largest |sharded - unsharded| admitted on H/SE/MI (nats) and p_max:
# two f32 roundings of sums whose terms agree, far below the 1e-2 scale
# on which the MI gate and the epistemic/aleatoric flags act.
FLOAT_ATOL = 1e-4


def compare(ref: dict, got: dict) -> tuple[list[str], float]:
    """Field-by-field diff of two runs' request streams: tokens and flag
    counts exactly, floats within ``FLOAT_ATOL``.  Returns the errors
    and the largest float difference seen."""
    errs = []
    worst = 0.0
    for a, b in zip(ref["requests"], got["requests"]):
        if a.tokens != b.tokens:
            errs.append(f"request {a.rid}: tokens diverge "
                        f"({a.tokens} vs {b.tokens})")
            continue
        for name in ("H", "SE", "MI", "p_max"):
            diff = float(np.max(np.abs(np.subtract(getattr(a, name),
                                                   getattr(b, name)))))
            worst = max(worst, diff)
            if not diff <= FLOAT_ATOL:
                errs.append(f"request {a.rid}: {name} differs by {diff:.3g}"
                            f" > {FLOAT_ATOL:g}")
        if (a.epistemic_flags, a.aleatoric_flags) \
                != (b.epistemic_flags, b.aleatoric_flags):
            errs.append(f"request {a.rid}: flag counts diverge")
    return errs, worst


def check(families: str, mesh_spec: str = "1x4",
          bench: bool = False) -> dict:
    """Run every family in ``families`` (comma list of ``FAMILIES``)
    unsharded and on the ``mesh_spec`` mesh in this process; returns
    the result record (``ok`` False on any mismatch)."""
    import jax
    from repro.launch.engine import resolve_mesh
    from repro.models import registry as M

    mesh = resolve_mesh(mesh_spec)
    out = {"bench": "mesh_scaling", "mesh": mesh_spec,
           "devices": jax.device_count(),
           "mesh_devices": int(mesh.devices.size), "families": {}}
    failed = False
    for family in families.split(","):
        cfg = reduced(get_config(FAMILIES[family]))
        # operand entropy: the seeded per-(slot, depth) noise stream the
        # parity contract is defined over
        cfg = dataclasses.replace(cfg, head_entropy="operand")
        params = M.init_params(jax.random.key(0), cfg)
        ref_eng, ref = run_engine(params, cfg, family, mesh=None)
        eng, got = run_engine(params, cfg, family, mesh=mesh)
        errs, worst = compare(ref, got)
        failed |= bool(errs)
        out["families"][family] = {
            "arch": FAMILIES[family],
            "tokens_equal": all(a.tokens == b.tokens for a, b in
                                zip(ref["requests"], got["requests"])),
            "max_float_diff": worst,
            "float_atol": FLOAT_ATOL,
            "errors": errs,
            "gen_tokens": ref["gen_tokens"],
            "prefill_mode": ref["prefill_mode"],
            "prefix_cache_hits": ref["prefix_cache"]["hits"],
        }
        if bench and family == "dense":
            # steady-state decode rate, compile excluded: re-run the
            # same traffic on the already-compiled engines
            ref2 = ref_eng.run(make_traffic(cfg, family))
            got2 = eng.run(make_traffic(cfg, family))
            out["tok_per_s_1dev"] = ref2["decode_tok_per_s"]
            out["tok_per_s_mesh"] = got2["decode_tok_per_s"]
            out["mesh_speedup"] = (got2["decode_tok_per_s"]
                                   / max(ref2["decode_tok_per_s"], 1e-9))
    out["ok"] = not failed
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--families", default="dense,moe,hybrid,encdec",
                    help="comma list of " + ",".join(FAMILIES))
    ap.add_argument("--mesh", default="1x4",
                    help="DxM debug-mesh shape for the sharded run")
    ap.add_argument("--bench", action="store_true",
                    help="also measure decode tok/s unsharded vs mesh "
                         "(the mesh_scaling bench row)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="print a machine-readable result")
    args = ap.parse_args(argv)
    out = check(args.families, args.mesh, bench=args.bench)
    if args.as_json:
        print(json.dumps(out))
    else:
        for family, r in out["families"].items():
            status = "OK" if not r["errors"] else "MISMATCH"
            print(f"{family:8s} ({r['arch']}): {status}  "
                  f"[{r['gen_tokens']} tokens, "
                  f"prefill={r['prefill_mode']}, max float diff "
                  f"{r['max_float_diff']:.3g}]")
            for e in r["errors"]:
                print(f"  {e}")
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
