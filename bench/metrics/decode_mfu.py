"""The decode step's share of the chip's peak FLOP/s, in %.

FLOPs that the tokens served inside the window required (the body, the
attention at each token's depth, the head's row with its S draws;
``bench.work.decode_token_flops``) over the decode-scan program's device
time at the bf16 peak."""

from bench import trace, work
from bench.metrics._served import depths

DECODE_PROGRAM = "scan_decode"


def read(ctx):
    secs, _ = trace.programs(ctx["trace"], named=DECODE_PROGRAM)
    ds = depths(ctx)
    if not secs or not ds:
        return None
    flops = sum(work.decode_token_flops(ctx["config"], d) for d in ds)
    return 100.0 * flops / (secs * ctx["peak"]["bf16_flops"])
