"""Gaps between streamed tokens, 95th percentile, over every gap that
ended in the window, of every request (those of the lead-in too), as the
client sees them."""
import numpy as np

from client import window_gaps


def read(ctx):
    gaps = window_gaps(ctx["all_records"], ctx["t0"], ctx["t1"])
    return 1e3 * float(np.percentile(gaps, 95)) if gaps else None
