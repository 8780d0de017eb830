"""Share of the traced window in which no operation ran on the device
(1 - union of operation intervals / window), averaged over chips."""
import xplane


def read(ctx):
    tr, (lo, hi) = ctx["trace"], ctx["trace_window"]
    if not tr.devices or hi <= lo:
        return None
    busy = sum(xplane.busy(d.ops, lo, hi) for d in tr.devices)
    return 100.0 * (1.0 - busy / len(tr.devices) / (hi - lo))
