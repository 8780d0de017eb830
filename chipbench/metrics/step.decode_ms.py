"""Device time per decode step in the traced window: the decode-block
programs' device time over the steps they ran, averaged over chips.
Nothing when the traced window holds no decode block."""
import devtrace


def read(ctx):
    steps = [s for s in devtrace.steps(ctx) if s["dispatch"]["kind"] == "decode"]
    n = sum(s["dispatch"]["n"] for s in steps)
    return 1e3 * sum(s["program"].dur for s in steps) / n if n else None
