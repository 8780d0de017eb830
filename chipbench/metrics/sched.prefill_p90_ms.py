"""Chunked prefill time (``Request.stats["prefill_s"]``: from slot
admission to the first emitted token, on the program's monotonic clock),
90th percentile over the window's requests.  Nothing where the program
keeps no such counter."""
import numpy as np


def read(ctx):
    vals = []
    for r in ctx["records"]:
        req = ctx["served"].get(tuple(r["prompt"]))
        if req is not None and "prefill_s" in req.stats:
            vals.append(req.stats["prefill_s"])
    return 1e3 * float(np.percentile(vals, 90)) if vals else None
