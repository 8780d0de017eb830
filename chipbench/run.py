#!/usr/bin/env python3
"""Runs one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``),
whose Hugging Face ``model_type`` names its architecture
(``arch/<model_type>.py``: program config, reference and counts), and a
traffic mix (``traffic/<name>.json``).  The run draws the weights and
the requests from ``--seed`` and serves them through the program's HTTP
frontend on the chip: the mix's lead-in arrivals, untimed, bring the
server to its steady state, then the window measures for ``--seconds``.
Set-up (``setup_s``) ends when the first request is sent.  With ``--trace 0``
it reports the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics (each read by ``metrics/<name>.py``) from a profiler trace of part
of the window.  Then it checks the served tokens against the float32
reference and prints one JSON line as the last line of standard output.

It fails, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for, or when a file the cell names, or its configuration's
architecture, is missing."""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent
sys.path[:0] = [str(HERE), str(CHECKOUT / "src")]

import archs  # noqa: E402
from traffic import generate as G  # noqa: E402

TRACE_MAX_S = 20.0          # longest traced part of the window
START_S = 0.5               # from the end of set-up to the first arrival
JOIN_SLACK_S = 60.0         # wait past the deadline for the last responses


def use_checkout_cache():
    """The benchmark gives the program its compile cache: a fixed path
    inside the checkout, so that only a cell's first run there compiles.
    Called before JAX is imported; every program is cached, however fast
    it compiled."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"


class BenchError(Exception):
    """The run cannot produce a result; the message says why."""


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path}")
    return json.loads(path.read_text())


def load_reader(name: str, root: Path = HERE):
    path = root / "metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no reader for metric {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: dict
    root: Path
    metrics: list          # entries of BENCHMARK.json this run reports


def load_cell(workload: str, trace: bool, root: Path = HERE) -> Cell:
    bench = load_json(root.parent / "BENCHMARK.json")
    cells = [c for c in bench["workloads"] if c["name"] == workload]
    if not cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[0]
    files = [c["file"] for c in bench["configs"]
             if c["name"] == cell["config"]]
    if not files:
        raise BenchError(f"no configuration {cell['config']!r}")
    cfg = load_json(root.parent / files[0])
    try:
        archs.find(cfg.get("model_type"))
    except LookupError as e:
        raise BenchError(f"configuration {files[0]}: {e}") from None
    mix = G.load_mix(cell["traffic"], root / "traffic")
    group = bench["per_layer"] if trace else bench["end_to_end"]
    metrics = [m for m in group if workload in m.get("workloads", [workload])]
    return Cell(workload, cell["chips"], cfg, mix, root, metrics)


def require_chips(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")


def device_record(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts programs built (compiled or loaded from the cache)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.n += 1


def traced_window(jax, trace_dir: Path, start: float, seconds: float):
    """Profiles ``seconds`` from monotonic time ``start``; the window is the
    host span ``chipbench.window``."""
    time.sleep(max(0.0, start - time.monotonic()))
    jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation("chipbench.window"):
            time.sleep(seconds)
    finally:
        jax.profiler.stop_trace()


def serve_window(jax, sched, plan, seconds: float, trace_dir=None,
                 counter=None, t_setup=None, log=print) -> dict:
    """Serves ``plan`` through the HTTP frontend over ``sched``: its lead-in
    arrivals, untimed, then a measured window of ``seconds``; waits until
    every request has ended (by its deadline at the latest)."""
    import server as S
    from client import Load

    srv = S.Server(sched)
    port = srv.start()
    t_start = time.monotonic() + START_S
    t0 = t_start + plan.lead_s
    t1 = t0 + seconds
    deadline = t1 + plan.grace_s
    out = {"t0": t0, "t1": t1, "seconds": seconds,
           "setup_s": None if t_setup is None else t_start - t_setup}
    load = Load(port, plan, t0, deadline)
    tracer = None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        span = min(TRACE_MAX_S, seconds)
        tracer = threading.Thread(
            target=traced_window,
            args=(jax, trace_dir, t0 + (seconds - span) / 2, span),
            daemon=True)
        tracer.start()
    n = counter.n if counter else 0
    load.start()
    for phase, until in (("lead-in", t0), ("window", t1)):
        time.sleep(max(0.0, until - time.monotonic()))
        m = counter.n if counter else 0
        log(f"programs compiled or loaded in the {phase}: {m - n}")
        n = m
    load.join(max(0.0, deadline - time.monotonic()) + JOIN_SLACK_S)
    if tracer is not None:
        tracer.join(120)
    srv.stop()
    out["all_records"] = load.records
    out["records"] = [r for r in load.records if t0 <= r["due"] < t1]
    out["served"] = {tuple(r.prompt.tolist()): r for r in sched.done}
    return out


def is_ok(r: dict) -> bool:
    """Served, in full or up to the run's deadline, without an error."""
    return (not r["error"] and r["status"] == 200 and len(r["tokens"]) > 0
            and (len(r["tokens"]) == r["max_tokens"]
                 or r["finish"] == "timeout"))


def read_metrics(cell: Cell, ctx: dict) -> dict:
    out = {}
    for m in cell.metrics:
        if m["name"] == "setup_s":
            out["setup_s"] = {"value": ctx["setup_s"], "unit": m["unit"]}
            continue
        value = load_reader(m["name"], cell.root).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def check_served(params, cfg: dict, records, seed: int, log=print) -> dict:
    """The numbers compared, each beside its limit."""
    import check
    limits = cfg["correct"]
    finished = [r for r in records if is_ok(r)]
    sample = check.pick(finished, G.rng_for(seed), limits["tokens_compared"])
    found = check.compare(params, cfg, sample)
    log(f"reference readings: {json.dumps(found)}")
    return {k: {"value": found[k], "limit": limits[k]} for k in limits}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: Path = HERE, require_chip: bool = True,
             log=print, keep_trace=None) -> dict:
    cell = load_cell(workload, trace, root)
    cfg, s = cell.cfg, cell.cfg["serving"]
    peaks_all = load_json(root / "peaks.json")

    import jax
    if require_chip:
        require_chips(jax, cell.chips)
    kind = jax.devices()[0].device_kind
    if require_chip and kind not in peaks_all:
        raise BenchError(f"device {kind!r} is not in peaks.json")
    # off the chip (tests) the first table entry stands in
    peaks = peaks_all.get(kind) or next(iter(peaks_all.values()))
    counter = CompileCounter(jax)

    import server as S
    plan = G.generate(cell.mix, seed, seconds, cfg["vocab_size"],
                      s["max_len"], root / "traffic")
    mesh = S.make_mesh(cfg)
    log(f"set-up: imports and devices {time.monotonic() - T_PROCESS:.2f} s")
    t = time.monotonic()
    params = jax.block_until_ready(S.draw_weights(cfg, seed, mesh))
    log(f"set-up: weights {time.monotonic() - t:.2f} s")
    eng = S.build_engine(cfg, params, mesh)
    sched = S.make_scheduler(eng, cfg)
    rec = None
    if trace:
        rec = S.Recorder()
        rec.wrap_engine(eng)
        S.Recorder.wrap_scheduler(sched)
    t = time.monotonic()
    S.warm_up(sched, cfg["vocab_size"])
    jax.block_until_ready(sched.caches)
    log(f"set-up: engine, KV pool and warm-up {time.monotonic() - t:.2f} s")
    trace_dir = (root.parent / ".chipbench" / "trace" / f"{workload}-{seed}"
                 if trace else None)
    ctx = serve_window(jax, sched, plan, seconds, trace_dir, counter,
                       T_PROCESS, log)
    log("time to first token of the window's requests, ms: " + " ".join(
        f"{1e3 * (r['times'][0] - r['due']):.1f}" for r in ctx["records"]
        if r["times"]))
    device = (device_record(jax, cell.chips) if require_chip else
              {"platform": jax.devices()[0].platform, "kind": kind,
               "count": cell.chips, "memory_peak_bytes": 0})
    ctx.update(cfg=cfg, peaks=peaks, chips=cell.chips, recorder=rec)
    result = {}
    if trace:
        import devtrace
        import xplane
        path = xplane.find(str(trace_dir))
        ctx["trace"] = xplane.load(path)
        if keep_trace is not None:
            keep_trace(path)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace_window"] = xplane.window(ctx["trace"], "chipbench.window")
        bd = devtrace.summarize(ctx)
        device.update(busy_s=bd["busy_s"], window_s=bd["window_s"])
        result["breakdown"] = {"device_ops": bd["device_ops"],
                               "idle_gaps": bd["idle_gaps"]}
    metrics = read_metrics(cell, ctx)
    records = ctx["all_records"]
    failed = [r for r in records if not is_ok(r)]
    # the program's state goes before the reference runs
    del sched, eng, ctx
    gc.collect()
    checks = check_served(params, cfg, records, seed, log)
    checks["failed_requests"] = {"value": len(failed), "limit": 0}
    # tokens_compared is a least count; every other number a most
    correct = all((c["value"] >= c["limit"]) if k == "tokens_compared"
                  else (c["value"] <= c["limit"]) for k, c in checks.items())
    return {"correct": bool(correct), "attempted": len(records),
            "failed": len(failed), "metrics": metrics, "device": device,
            **result, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    use_checkout_cache()
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace),
                          log=lambda m: print(m, file=sys.stderr, flush=True))
    except BenchError as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
