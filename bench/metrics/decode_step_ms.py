"""Device time of one decode step, in ms: the decode-scan program's time
in the trace over the steps it ran (``decode_chunk`` per execution)."""

from bench import trace

DECODE_PROGRAM = "scan_decode"


def read(ctx):
    secs, n = trace.programs(ctx["trace"], named=DECODE_PROGRAM)
    return 1e3 * secs / (n * ctx["engine"]["decode_chunk"]) if n else None
