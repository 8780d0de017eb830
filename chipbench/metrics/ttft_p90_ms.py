"""Time to first token, 90th percentile, over every request due in the
window, from the time it fell due (open loop) to its first token at the
client.  A request with no first token is left to ``failed``."""
import numpy as np


def read(ctx):
    vals = [r["times"][0] - r["due"] for r in ctx["records"] if r["times"]]
    return 1e3 * float(np.percentile(vals, 90)) if vals else None
