"""Device time per mixed (prefill chunk + decode) program in the traced
window, averaged over chips."""
import devtrace


def read(ctx):
    steps = [s for s in devtrace.steps(ctx) if s["dispatch"]["kind"] == "mixed"]
    if not steps:
        return None
    return 1e3 * sum(s["program"].dur for s in steps) / len(steps)
