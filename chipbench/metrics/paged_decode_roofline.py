"""Paged decode attention kernel: least time over kernel device time, as a
share.  A call serves one layer of one step for every decoding slot; its
least time is the larger of its flops over peak flops and its bytes over
peak bandwidth, counting the keys and values of live tokens only, split
over the chips that share the heads.  Summed over the traced window."""
import devtrace
import flops


def read(ctx):
    cfg, peaks, tp = ctx["cfg"], ctx["peaks"], ctx["chips"]
    layers = cfg["num_hidden_layers"]
    least = spent = 0.0
    for s in devtrace.steps(ctx):
        kern = [o for o in s["ops"] if devtrace.kind_of(o.name, "paged_decode")]
        if not kern:
            continue
        for live in s["dispatch"]["decode_ctx"]:
            work = [flops.decode_attention(cfg, c) for c in live]
            t, _ = flops.least_time(sum(w[0] for w in work) / tp,
                                    sum(w[1] for w in work) / tp, peaks)
            least += t * layers
        spent += sum(o.dur for o in kern)
    return 100.0 * least / spent if spent else None
