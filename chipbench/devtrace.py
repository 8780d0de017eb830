"""Device programs of a traced window, matched to the dispatches that the
benchmark's host spans record, plus the run's breakdown.

A served step is one dispatch (``chipbench.decode#i`` or
``chipbench.mixed#i`` host span) and one program on each chip.  The
scheduler waits for a step's tokens before it dispatches the next, so the
longest program that starts on a chip between dispatch i and dispatch i+1
is step i's.  A step counts when its program starts inside the window.

Kernels are found by the names the trace gives their operations."""
from __future__ import annotations

import bisect
from typing import Dict, List

import xplane

# operation names as the trace gives them: a Pallas kernel's custom call is
# named after the function that wraps its pallas_call
KERNELS = {
    "paged_decode": ("paged_decode_attention_partial",),
    "paged_prefill": ("paged_flash_prefill",),
    "all_reduce": ("all-reduce",),
}


def kind_of(op_name: str, kernel: str) -> bool:
    return op_name.startswith(KERNELS[kernel])


def steps(ctx) -> List[Dict]:
    """Per chip and step: {"chip", "dispatch", "program", "ops"}; cached."""
    if "steps" in ctx:
        return ctx["steps"]
    tr, (lo, hi) = ctx["trace"], ctx["trace_window"]
    spans = [s for s in tr.spans
             if s.name.startswith(("chipbench.decode#", "chipbench.mixed#"))]
    records = ctx["recorder"].dispatches
    out = []
    for chip, dev in enumerate(tr.devices):
        starts = [m.start for m in dev.modules]
        op_starts = [o.start for o in dev.ops]
        for i, span in enumerate(spans):
            end = spans[i + 1].start if i + 1 < len(spans) else hi
            a, b = (bisect.bisect_left(starts, span.start),
                    bisect.bisect_left(starts, end))
            if a == b:
                continue
            prog = max(dev.modules[a:b], key=lambda m: m.dur)
            if not lo <= prog.start < hi:
                continue
            ia, ib = (bisect.bisect_left(op_starts, prog.start),
                      bisect.bisect_left(op_starts, prog.end))
            idx = int(span.name.split("#")[1])
            out.append({"chip": chip, "dispatch": records[idx],
                        "program": prog, "ops": dev.ops[ia:ib]})
    ctx["steps"] = out
    return out


def summarize(ctx, top: int = 10) -> Dict:
    """busy_s and window_s (averaged over chips), the device operations
    (innermost, named program/operation) that took most time, and the
    longest idle gaps by the host span that covered them."""
    tr, (lo, hi) = ctx["trace"], ctx["trace_window"]
    n = max(1, len(tr.devices))
    busy = sum(xplane.busy(d.ops, lo, hi) for d in tr.devices) / n
    by_name: Dict[str, float] = {}
    for d in tr.devices:
        starts = [m.start for m in d.modules]
        for o in xplane.leaves(d.ops):
            if not lo <= o.start < hi:
                continue
            i = bisect.bisect_right(starts, o.start) - 1
            prog = d.modules[i].name.split("(")[0] if i >= 0 else "?"
            key = f"{prog}/{o.name}"
            by_name[key] = by_name.get(key, 0.0) + o.dur / n
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    host = [s for s in tr.spans if s.name != "chipbench.window"]
    gaps = []
    if tr.devices:
        for s, e in xplane.gaps(tr.devices[0].ops, lo, hi):
            gaps.append((xplane.innermost(host, (s + e) / 2,
                                          "no benchmark host span"), e - s))
    gaps.sort(key=lambda g: -g[1])
    return {"busy_s": busy, "window_s": hi - lo,
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps[:top]]}
