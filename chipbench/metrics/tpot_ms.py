"""Time per output token: the sum of the gaps between streamed tokens
that ended in the window, of every request, over their count."""
from client import window_gaps


def read(ctx):
    gaps = window_gaps(ctx["all_records"], ctx["t0"], ctx["t1"])
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
