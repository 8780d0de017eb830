#!/usr/bin/env python3
"""Tools for setting a cell's numbers on the chip: the knee of its traffic,
the readings that the limits of ``correct`` are set from, and the names a
trace gives the device's work.  The benchmark's own runs never call this.

    python3 chipbench/calibrate.py sweep --workload W --rates 0.1,0.2 --seconds S
        One process, one set-up, one server: the cell's traffic at each
        rate in turn, S seconds each, with no pause between them, so each
        rate starts from the backlog the one before left.  The scheduler's
        queue and busy slots are sampled every second.  Prints per rate the
        queue's growth, the slots in use, the TTFT percentiles and the
        request lifetimes, to find the knee: the highest rate whose queue
        does not grow.
    python3 chipbench/calibrate.py limits --workload W --seeds 1,2,3 --seconds S
        One process: for each seed new weights and requests, the cell's
        lead-in and a window, and the numbers compared for the served tokens beside the control's
        (the reference with int8 and with fp8 weights) on the same sample.
    python3 chipbench/calibrate.py probe --workload W --seed N --seconds S
        One traced run; writes the trace's plane, line and operation names
        with their times to <out>/probe-<W>.txt, and a small trace of a few
        steps to <out>/small.xplane.pb (``--out``, default .chipbench/out)."""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import threading
import time

import run  # sets the import path first

import numpy as np  # noqa: E402

import client  # noqa: E402
from traffic import generate as G  # noqa: E402


def _setup(workload, trace=False):
    import jax
    run.require_chips(jax, 1)
    cell = run.load_cell(workload, trace)
    import server as S
    mesh = S.make_mesh(cell.cfg)
    return jax, cell, S, mesh


def _summary(ctx):
    recs = ctx["records"]
    ttft = [r["times"][0] - r["due"] for r in recs if r["times"]]
    gaps = client.window_gaps(ctx["all_records"], ctx["t0"], ctx["t1"])
    out = {"attempted": len(recs), "ok": sum(map(run.is_ok, recs))}
    if ttft:
        out.update(ttft_p50_ms=1e3 * float(np.percentile(ttft, 50)),
                   ttft_p90_ms=1e3 * float(np.percentile(ttft, 90)))
    if gaps:
        out.update(itl_p95_ms=1e3 * float(np.percentile(gaps, 95)),
                   tpot_ms=1e3 * float(np.mean(gaps)))
    return out


def sweep(args):
    jax, cell, S, mesh = _setup(args.workload)
    cfg, level = cell.cfg, args.seconds
    params = S.draw_weights(cfg, args.seed, mesh)
    sched = S.make_scheduler(S.build_engine(cfg, params, mesh), cfg)
    S.warm_up(sched, cfg["vocab_size"])
    rates = [float(r) for r in args.rates.split(",")]
    reqs = []
    for k, rate in enumerate(rates):
        mix = dict(cell.mix, lead_s=0.0,
                   arrivals=dict(cell.mix["arrivals"], rate_per_s=rate))
        plan = G.generate(mix, args.seed + k, level, cfg["vocab_size"],
                          cfg["serving"]["max_len"], cell.root / "traffic")
        reqs += [G.Request(r.prompt, r.max_tokens, r.due_s + k * level)
                 for r in plan.requests]
    plan = G.Plan(reqs, 0.0, 0.0, cell.mix)
    samples, stop = [], threading.Event()

    def sample():
        while not stop.wait(1.0):
            samples.append((time.monotonic(), len(sched.queue),
                            sum(s.req is not None for s in sched.slots)))

    sampler = threading.Thread(target=sample, daemon=True)
    sampler.start()
    ctx = run.serve_window(jax, sched, plan, level * len(rates))
    stop.set()
    sampler.join()
    for k, rate in enumerate(rates):
        a = ctx["t0"] + k * level
        b = a + level
        recs = [r for r in ctx["all_records"] if a <= r["due"] < b]
        q = [(t - a, n, busy) for t, n, busy in samples if a <= t < b]
        t, n, busy = (np.asarray(x, float) for x in zip(*q))
        life = [r["times"][-1] - r["due"] for r in recs
                if run.is_ok(r) and r["finish"] != "timeout"]
        row = {"rate": rate, "queue_slope_per_s": float(np.polyfit(t, n, 1)[0]),
               "queue_mean": float(n.mean()), "queue_last": int(n[-1]),
               "busy_slots_mean": float(busy.mean()),
               "lifetime_mean_s": float(np.mean(life)) if life else None,
               "lifetime_max_s": float(np.max(life)) if life else None,
               "finished": len(life),
               **_summary(dict(ctx, records=recs, t0=a, t1=b))}
        print(json.dumps(row), flush=True)


def _off_reference(rec, gaps):
    """Where one request's served tokens leave the reference's best: how
    many, at how many distinct tokens, and how repetitive the stream is."""
    served = np.asarray(rec["tokens"])
    off = gaps > 0
    return {"served": int(served.size), "distinct_served":
            int(np.unique(served).size), "off": int(off.sum()),
            "distinct_off": int(np.unique(served[off]).size),
            "first_off": int(np.argmax(off)) if off.any() else None,
            "max_gap": float(gaps.max()) if gaps.size else None}


def limits(args):
    jax, cell, S, mesh = _setup(args.workload)
    import check
    cfg = cell.cfg
    eng = None
    for seed in [int(s) for s in args.seeds.split(",")]:
        params = S.draw_weights(cfg, seed, mesh)
        if eng is None:
            eng = S.build_engine(cfg, params, mesh)
        eng.params = params
        sched = S.make_scheduler(eng, cfg)
        S.warm_up(sched, cfg["vocab_size"])
        plan = G.generate(cell.mix, seed, args.seconds, cfg["vocab_size"],
                          cfg["serving"]["max_len"], cell.root / "traffic")
        ctx = run.serve_window(jax, sched, plan, args.seconds)
        summ = _summary(ctx)
        summ["attempted"] = len(ctx["all_records"])
        summ["ok"] = sum(map(run.is_ok, ctx["all_records"]))
        sched.caches = None
        del sched
        gc.collect()
        finished = [r for r in ctx["all_records"] if run.is_ok(r)]
        sample = check.pick(finished, G.rng_for(seed),
                            cfg["correct"]["tokens_compared"])
        row = {"seed": seed, "attempted": summ["attempted"],
               "ok": summ["ok"]}
        t = time.monotonic()
        prog = [check.gaps(params, cfg, rec) for rec in sample]
        row["program"] = check.summarize(prog)
        row["reference_s"] = time.monotonic() - t
        row["program_requests"] = [_off_reference(rec, g)
                                   for rec, g in zip(sample, prog)]
        for quant in args.controls.split(","):
            row[quant] = check.compare(params, cfg, sample, quant)
        print(json.dumps(row), flush=True)
        eng.params = None
        del ctx, params
        gc.collect()


def probe(args):
    import xplane
    out = run.CHECKOUT / args.out
    out.mkdir(parents=True, exist_ok=True)

    def keep(path):
        with open(out / f"probe-{args.workload}.txt", "w") as f:
            names(argparse.Namespace(path=path, top=60), out=f)

    result = run.run_cell(args.workload, args.seed, args.seconds, True,
                          keep_trace=keep)
    print(json.dumps(result), flush=True)
    # a second, small trace for the reducer's tests
    import jax
    import jax.numpy as jnp
    d = run.CHECKOUT / ".chipbench" / "small"
    shutil.rmtree(d, ignore_errors=True)
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(d))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation(f"chipbench.decode#{i}"):
                y = f(x)
            y.block_until_ready()
            time.sleep(0.002)
    jax.profiler.stop_trace()
    shutil.copy(xplane.find(str(d)), out / "small.xplane.pb")


def names(args, out=sys.stdout):
    """Plane, line and event names of a trace file, with total times, and
    the stats of the longest event of each line."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(args.path)
    for plane in data.planes:
        print("PLANE", plane.name, file=out)
        for line in plane.lines:
            tot, cnt, longest = {}, {}, None
            for e in line.events:
                tot[e.name] = tot.get(e.name, 0) + e.duration_ns
                cnt[e.name] = cnt.get(e.name, 0) + 1
                if longest is None or e.duration_ns > longest.duration_ns:
                    longest = e
            print("  LINE", line.name, len(cnt), "names", file=out)
            for k in sorted(tot, key=lambda k: -tot[k])[:args.top]:
                print(f"    {tot[k] / 1e6:12.3f} ms {cnt[k]:7d}  {k[:200]}",
                      file=out)
            if longest is not None:
                stats = {k: str(v)[:200] for k, v in longest.stats}
                print(f"    longest: {longest.name[:200]} {stats}", file=out)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    p = sub.add_parser("sweep")
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seed", type=int, default=1)
    p = sub.add_parser("limits")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--controls", default="int8,fp8")
    p = sub.add_parser("probe")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out", default=".chipbench/out")
    p = sub.add_parser("names")
    p.add_argument("path")
    p.add_argument("--top", type=int, default=40)
    args = ap.parse_args(argv)
    run.use_checkout_cache()
    {"sweep": sweep, "limits": limits, "probe": probe,
     "names": names}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
