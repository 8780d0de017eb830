"""Model flops of the tokens the served steps processed in the traced
window (decoded tokens and prefilled chunk tokens, each with its attention
context) over window seconds x chips x peak bf16 flops, as a share."""
import devtrace
import flops


def read(ctx):
    cfg, chips = ctx["cfg"], ctx["chips"]
    total = 0
    for s in devtrace.steps(ctx):
        if s["chip"]:
            continue                  # the work is counted once, not per chip
        d = s["dispatch"]
        total += sum(flops.token_flops(cfg, c)
                     for live in d["decode_ctx"] for c in live)
        for start, clen in d["chunks"]:
            total += sum(flops.token_flops(cfg, start + q + 1)
                         for q in range(clen))
    lo, hi = ctx["trace_window"]
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * total / ((hi - lo) * chips * peak) if total else None
