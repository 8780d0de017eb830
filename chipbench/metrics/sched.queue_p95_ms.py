"""Scheduler queue wait (``request_summary``'s ``queue_s``: submission to
slot admission), 95th percentile over the window's requests."""
import numpy as np


def read(ctx):
    vals = []
    for r in ctx["records"]:
        req = ctx["served"].get(tuple(r["prompt"]))
        if req is not None and "queue_s" in req.stats:
            vals.append(req.stats["queue_s"])
    return 1e3 * float(np.percentile(vals, 95)) if vals else None
