"""The Bayesian head kernel's share of its roofline, in %.

The least time its calls need at the chip's peaks (mu and sigma read
once per call, the mean and variance products and the S draws over the
rows that held a decoding request; ``bench.work.head``), over the
device time of its operations.  One call per decode step; the rows
served per step are the traced part of the window's tokens over its
decode steps.  The padded rows of idle slots are not required work."""

from bench import trace, work

HEAD_KERNEL = "uncertainty_head"
DECODE_PROGRAM = "scan_decode"


def read(ctx):
    secs, _ = trace.operations(ctx["trace"], HEAD_KERNEL)
    _, scans = trace.programs(ctx["trace"], named=DECODE_PROGRAM)
    calls = scans * ctx["engine"]["decode_chunk"]
    tokens, harvests = ctx["harvests"](*ctx["traced"])
    if not secs or not calls or not harvests:
        return None
    rows = tokens / (harvests * ctx["engine"]["decode_chunk"])
    need = work.seconds_at_peak(*work.head(ctx["config"], rows), ctx["peak"])
    return 100.0 * calls * need / secs
