"""The mixed (prefill chunk + decode) step's share of the chip's peak:
model flops of the tokens its programs processed in the traced window
(prefilled chunk tokens and decoded tokens, each with its attention
context) over those programs' device time x peak bf16 flops."""
import devtrace
import flops


def read(ctx):
    cfg = ctx["cfg"]
    total = spent = 0.0
    for s in devtrace.steps(ctx):
        d = s["dispatch"]
        if d["kind"] != "mixed":
            continue
        spent += s["program"].dur
        if s["chip"]:
            continue                  # the work is counted once, not per chip
        total += sum(flops.token_flops(cfg, c)
                     for live in d["decode_ctx"] for c in live)
        for start, clen in d["chunks"]:
            total += sum(flops.token_flops(cfg, start + q + 1)
                         for q in range(clen))
    if not spent:
        return None
    # ``spent`` sums every chip's programs: chips x seconds
    return 100.0 * total / (spent * ctx["peaks"]["bf16_flops_per_s"])
