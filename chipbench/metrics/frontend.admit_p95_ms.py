"""From the time a request fell due to the time the scheduler took it in
(``Request.submitted_at``): the client's connection, the HTTP frontend and
the engine service's inbox.  95th percentile over the window's requests;
both times are on this process's monotonic clock."""
import numpy as np


def read(ctx):
    vals = []
    for r in ctx["records"]:
        req = ctx["served"].get(tuple(r["prompt"]))
        if req is not None:
            vals.append(req.submitted_at - r["due"])
    return 1e3 * float(np.percentile(vals, 95)) if vals else None
