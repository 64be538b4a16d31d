"""95th percentile of the wait for a first prefill chunk, in ms.

``Request.prefill_wait_s`` (from admission to a slot to the dispatch of
that admission's first prompt chunk: the chunked-prefill queue) of
every request admitted inside the window, the requests that
``queue_wait_p95_ms`` reads.  None where the program keeps no such
stamp."""

import numpy as np


def read(ctx):
    t0, t1 = ctx["window"]
    waits = [getattr(r, "prefill_wait_s", None) for r in ctx["requests"]
             if any(s == "prefilling" and t0 <= t < t1
                    for s, t in r.history)]
    if not waits or None in waits:
        return None
    return 1e3 * float(np.percentile(waits, 95))
