"""The grouped-matmul expert kernels' share of their roofline, in %.

The least time the routed experts' calls in the traced part of the
window need at the chip's peaks, from the program's counters of those
calls (``moe.rows`` and ``moe.experts_hit`` of the decode chunks
harvested and the prompt chunks dispatched in it; FLOPs and bytes from
the configuration's family, ``expert_work``), over the device time of
the kernels (operations named ``gmm``).  None where the program keeps
no such counters or runs no such kernel."""

from bench import spec, work

KERNEL = "gmm"


def read(ctx):
    from bench import trace
    secs, _ = trace.operations(ctx["trace"], KERNEL)
    records = (ctx["results"].get("counters") or {}).get("records")
    if not secs or not records:
        return None
    t0, t1 = ctx["traced"]
    rows = hit = 0
    for r in records:
        if t0 <= r.t < t1:
            rows += r.values.get("moe.rows", 0)
            hit += r.values.get("moe.experts_hit", 0)
    if not rows:
        return None
    config = ctx["config"]
    family = spec.family_module(config["reference"])
    need = work.seconds_at_peak(*family.expert_work(config, rows, hit),
                                ctx["peak"])
    return 100.0 * need / secs
