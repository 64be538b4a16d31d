"""(token, k) assignments routed to this chip's experts per decode step
per MoE layer, over the decode chunks harvested in the measured window
(the program's ``moe.rows`` counter, which counts the slots decoding).
None where the program keeps no such counter."""

from bench import spec


def read(ctx):
    records = (ctx["results"].get("counters") or {}).get("records")
    if not records:
        return None
    t0, t1 = ctx["window"]
    rows = steps = 0
    for r in records:
        if r.kind == "decode" and t0 <= r.t < t1:
            rows += r.values.get("moe.rows", 0)
            steps += r.steps
    if not steps:
        return None
    config = ctx["config"]
    layers = spec.family_module(config["reference"]).moe_layers(config)
    return rows / (steps * layers)
