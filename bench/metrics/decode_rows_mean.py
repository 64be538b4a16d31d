"""Slots decoding in each decode scan the engine dispatched inside the
measured window (``results["spans"]["iterations"]``, the ``rows`` of
the iterations that began in the window and dispatched a scan).  None
where the program records no iterations."""


def read(ctx):
    spans = ctx["results"].get("spans")
    if not spans:
        return None
    t0, t1 = ctx["window"]
    rows = [it.rows for it in spans["iterations"]
            if t0 <= it.t0 < t1 and it.rows]
    return sum(rows) / len(rows) if rows else None
