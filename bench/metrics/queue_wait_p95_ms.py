"""95th percentile of the scheduler's queue wait, in ms.

``Request.queue_time_s`` (stamped by the request lifecycle between
joining the queue and being admitted to a slot) of every request
admitted inside the window."""

import numpy as np


def read(ctx):
    t0, t1 = ctx["window"]
    waits = [r.queue_time_s for r in ctx["requests"]
             if any(s == "prefilling" and t0 <= t < t1
                    for s, t in r.history)]
    return 1e3 * float(np.percentile(waits, 95)) if waits else None
