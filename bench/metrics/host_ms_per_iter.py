"""Host time of one engine iteration inside the measured window, in ms.

The engine's iteration records (``results["spans"]["iterations"]``):
the mean, over the iterations that began in the window, of the
iteration's length less the time it spent waiting for the device
(``serve.prefill_wait`` and ``serve.decode_wait``).  The iteration in
which the harness stopped its profiler is left out: the stop runs
inside it.  None where the program records no iterations."""


def read(ctx):
    spans = ctx["results"].get("spans")
    if not spans:
        return None
    t0, t1 = ctx["window"]
    mark = (ctx.get("traced") or (None, None))[1]
    host = [it.t1 - it.t0 - it.wait_s for it in spans["iterations"]
            if t0 <= it.t0 < t1
            and not (mark is not None and it.t0 < mark <= it.t1)]
    return 1e3 * sum(host) / len(host) if host else None
