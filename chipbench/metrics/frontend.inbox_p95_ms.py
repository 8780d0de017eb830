"""The engine service's inbox wait (``Request.stats["inbox_s"]``: from the
frontend's ``try_submit`` to the scheduler's ``submit``, on the program's
monotonic clock), 95th percentile over the window's requests.  Nothing
where the program keeps no such counter."""
import numpy as np


def read(ctx):
    vals = []
    for r in ctx["records"]:
        req = ctx["served"].get(tuple(r["prompt"]))
        if req is not None and "inbox_s" in req.stats:
            vals.append(req.stats["inbox_s"])
    return 1e3 * float(np.percentile(vals, 95)) if vals else None
