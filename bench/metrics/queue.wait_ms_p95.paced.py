"""95th percentile, in ms, of the time from a request's due time to the
moment its batch left the queue.

The due time is the generator's; the moment of leaving is the end of
the request's ``bucket_wait`` span in the program's lifecycle trace
(`repro.obs.Tracer`), on the same host clock. Every request due in the
window counts.
"""

import math


def read(rec):
    out, due = rec.get("lifecycle"), rec["due_by_rid"]
    if not out or not due:
        return None
    waits = sorted(out[r] - due[r] for r in due if r in out)
    if not waits:
        return None
    return 1e3 * waits[max(0, math.ceil(0.95 * len(waits)) - 1)]
