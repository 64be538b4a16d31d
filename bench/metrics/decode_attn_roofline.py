"""The paged decode-attention kernel's share of its roofline, in %.

The K and V bytes that the tokens served inside the window had to read
(each at its own depth, ``bench.work.decode_attention``) at the chip's
bandwidth, over the device time of the kernel's operations."""

from bench import trace, work
from bench.metrics._served import depths

DECODE_ATTN_KERNEL = "paged_decode_attention"


def read(ctx):
    secs, _ = trace.operations(ctx["trace"], DECODE_ATTN_KERNEL)
    ds = depths(ctx)
    if not secs or not ds:
        return None
    need = sum(work.seconds_at_peak(
        *work.decode_attention(ctx["config"], d), ctx["peak"]) for d in ds)
    return 100.0 * need / secs
