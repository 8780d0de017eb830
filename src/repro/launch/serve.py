"""Serving driver: ``python -m repro.launch.serve --arch yi-9b --requests 8``.

Runs the batched-request serving example on a local mesh with the paper's
optimizations on; reports per-token latency (the paper's §3 metric) and
per-request stats from the selected scheduler.

Schedulers (``--scheduler``):

  wave        drain-and-restart baseline: waves of ``--batch`` requests pad
              to the longest prompt and decode to the wave's max ``--max-new``.
  continuous  slot engine: ``--slots`` fixed slots, per-slot positions,
              finished slots masked in-program, arrivals admitted in-flight
              by prefilling into free slots (no batch restart).  Extra knobs:
              ``--block-steps`` fused masked decode steps per host round
              trip, ``--arrival-every`` staggers request arrivals on the
              virtual decode-step clock, ``--max-new-spread`` draws each
              request's budget from [max_new/spread, max_new] to create the
              straggler-heavy mix continuous batching wins on.
  paged       slot engine over the paged KV pool: ``--kv-block-size`` tokens
              per block, ``--kv-pool-blocks`` total pool blocks (0 = the
              dense n_slots x max_len footprint; shrink to overcommit —
              allocator exhaustion preempts the youngest request instead of
              failing), ``--no-prefix-cache`` disables shared-prefix block
              reuse, ``--shared-prefix N`` prepends one common N-token
              system prompt to every request so the reuse path is visible.

  disagg      paged slot engine with the data axis split into a prefill
              pool and a decode pool (``--prefill-shards`` of ``--dp``):
              prompts chunk-prefill on the prefill shards only, finished KV
              blocks migrate to the decode shards in batched jitted copy
              steps, and decode never shares a dispatch with admission.
              Reports per-pool stats: occupancy, migrated blocks/bytes,
              decode-side prefix hits that skipped the copy, and
              migration-wait percentiles.  Needs ``--dp >= 2`` and an arch
              whose capability record supports the disaggregated path
              (``--list-archs`` prints the matrix).

All continuous schedulers also take ``--spec-k N`` (speculative decoding:
n-gram prompt-lookup drafts + fused multi-token verify, emitting 1..N+1
tokens per step; ``--spec-ngram`` caps the lookup n-gram length and
``--no-spec-decode`` forces plain decode) — the stats block then reports
acceptance rate and tokens/step.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import ParallelConfig, SamplingConfig, get_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.runtime.engine import Engine
from repro.runtime.scheduler import (ContinuousScheduler, DisaggScheduler,
                                     PagedContinuousScheduler, WaveScheduler)


def build_engine(args, cfg=None):
    """Engine for ``args``; ``cfg`` overrides the config ``--arch`` /
    ``--full`` would pick (e.g. a depth cut at published widths)."""
    if cfg is None:
        cfg = get_config(args.arch)
        if not args.full:
            cfg = cfg.reduced()
    mesh = make_local_mesh(args.dp, args.tp)
    par = ParallelConfig(tp=args.tp, dp=args.dp, remat=False,
                         topk_sync=not args.no_topk_sync,
                         kv_block_size=args.kv_block_size,
                         kv_pool_blocks=args.kv_pool_blocks,
                         prefill_chunk=args.prefill_chunk,
                         flash_prefill=not args.no_flash_prefill,
                         spec_k=0 if args.no_spec_decode else args.spec_k,
                         spec_ngram=args.spec_ngram,
                         weight_quant=args.weight_quant,
                         wq_group_size=args.wq_group_size,
                         overlap_decode=args.overlap,
                         fault_plan=args.fault_plan,
                         max_step_retries=args.max_step_retries,
                         retry_backoff_s=args.retry_backoff_s,
                         slo_interactive_s=args.slo_interactive,
                         slo_standard_s=args.slo_standard,
                         slo_batch_s=args.slo_batch,
                         interactive_reserve_slots=args.interactive_reserve_slots,
                         interactive_reserve_blocks=args.interactive_reserve_blocks,
                         overload_degrade=args.overload_degrade,
                         overload_queue_hi=args.overload_queue_hi,
                         overload_queue_lo=args.overload_queue_lo,
                         overload_patience=args.overload_patience,
                         overload_cooldown=args.overload_cooldown,
                         disagg_prefill_shards=(args.prefill_shards
                                                if args.scheduler == "disagg"
                                                else 0))
    return Engine(cfg=cfg, parallel=par,
                  sampling=SamplingConfig(top_k=args.top_k),
                  mesh=mesh, max_len=args.max_len,
                  wq_cache=args.wq_cache)


def make_scheduler(eng, args):
    if args.scheduler == "disagg":
        return DisaggScheduler(
            eng, n_slots=args.slots, block_steps=args.block_steps,
            responsive_blocks=args.responsive_blocks,
            prefix_cache=not args.no_prefix_cache)
    if args.scheduler == "paged":
        # block-size / pool-size ride on ParallelConfig (build_engine); the
        # scheduler reads them as its defaults
        return PagedContinuousScheduler(
            eng, n_slots=args.slots, block_steps=args.block_steps,
            responsive_blocks=args.responsive_blocks,
            prefix_cache=not args.no_prefix_cache)
    if args.scheduler == "continuous":
        return ContinuousScheduler(eng, n_slots=args.slots,
                                   block_steps=args.block_steps,
                                   responsive_blocks=args.responsive_blocks)
    return WaveScheduler(eng, batch_size=args.batch)


def parse_class_mix(spec):
    """Parse ``interactive=0.25,standard=0.5,batch=0.25`` into
    ``(classes, probabilities)``; None for an empty spec.  Weights are
    normalized, so integer ratios (``interactive=1,batch=3``) work too."""
    if not spec:
        return None
    classes, weights = [], []
    for item in spec.split(","):
        k, _, v = item.strip().partition("=")
        if k not in ("interactive", "standard", "batch"):
            raise ValueError(f"unknown priority class {k!r} in class mix")
        classes.append(k)
        weights.append(float(v) if v else 1.0)
    total = sum(weights)
    if total <= 0:
        raise ValueError("class mix weights must sum to > 0")
    return classes, [w / total for w in weights]


def submit_workload(sched, cfg, args):
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size, args.shared_prefix).astype(np.int32)
    mix = parse_class_mix(getattr(args, "class_mix", ""))
    # the class draw uses its own rng stream so --class-mix never perturbs
    # the prompt/budget sequence of an existing workload
    cls_rng = np.random.default_rng(0xC1A55) if mix else None
    for i in range(args.requests):
        plen = int(rng.integers(4, args.prompt_len + 1))
        shape = (plen,) if cfg.n_codebooks == 1 else (plen, cfg.n_codebooks)
        max_new = args.max_new
        if args.max_new_spread > 1:
            max_new = int(rng.integers(max(1, args.max_new // args.max_new_spread),
                                       args.max_new + 1))
        prompt = rng.integers(0, cfg.vocab_size, shape).astype(np.int32)
        if args.shared_prefix and cfg.n_codebooks == 1:
            prompt = np.concatenate([shared, prompt])
        priority = (mix[0][int(cls_rng.choice(len(mix[0]), p=mix[1]))]
                    if mix else "standard")
        sched.submit(prompt, max_new=max_new,
                     arrival_step=i * args.arrival_every,
                     priority=priority)


def build_parser(ap=None):
    """Engine/scheduler argument set, shared with the async frontend
    (``repro.launch.frontend`` adds its server flags on top)."""
    if ap is None:
        ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--list-archs", action="store_true",
                    help="print the architecture capability matrix (which "
                         "serving paths each registered arch supports, and "
                         "what blocks the rest) and exit")
    ap.add_argument("--scheduler",
                    choices=("wave", "continuous", "paged", "disagg"),
                    default="wave")
    ap.add_argument("--prefill-shards", type=int, default=1,
                    help="disagg scheduler: the first N data shards form "
                         "the prefill pool (prompts admit and chunk-prefill "
                         "there; finished KV blocks migrate to the decode "
                         "pool); needs dp >= 2")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4,
                    help="wave scheduler: requests per wave")
    ap.add_argument("--slots", type=int, default=4,
                    help="continuous scheduler: fixed slot count")
    ap.add_argument("--block-steps", type=int, default=8,
                    help="continuous scheduler: fused decode steps per round trip")
    ap.add_argument("--responsive-blocks", action="store_true",
                    help="end fused blocks at the shortest active budget while "
                         "requests wait (fewer total steps, more dispatches)")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="paged scheduler: tokens per KV block")
    ap.add_argument("--kv-pool-blocks", type=int, default=0,
                    help="paged scheduler: total pool blocks "
                         "(0 = dense-equivalent footprint)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="paged scheduler: disable shared-prefix block reuse")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend one common N-token system prompt to every "
                         "request (makes prefix reuse visible)")
    ap.add_argument("--prefill-chunk", type=int, default=256,
                    help="continuous/paged: prompts longer than this many "
                         "tokens are admitted chunk-by-chunk through the "
                         "fused mixed prefill/decode step (decode advances "
                         "every step during admission); 0 = whole-prompt "
                         "admission only.  Gated by the capability registry "
                         "(--list-archs): recurrent and modality-prefix "
                         "archs clamp to whole-prompt admission")
    ap.add_argument("--no-flash-prefill", action="store_true",
                    help="keep prefill attention on the pure-JAX scan even "
                         "when Pallas kernels are enabled")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="continuous/paged: speculative decoding — propose "
                         "N draft tokens per active slot from the n-gram "
                         "prompt-lookup drafter and verify all of them in "
                         "one fused multi-token step (emits 1..N+1 tokens "
                         "per step); 0 = plain one-token decode.  Gated by "
                         "the capability registry (--list-archs): recurrent "
                         "and modality-prefix archs clamp to plain decode")
    ap.add_argument("--no-spec-decode", action="store_true",
                    help="force plain one-token decode even when --spec-k "
                         "is set")
    ap.add_argument("--spec-ngram", type=int, default=3,
                    help="longest n-gram the prompt-lookup drafter matches "
                         "against each request's history")
    ap.add_argument("--weight-quant", choices=("none", "int8", "int4"),
                    default="none",
                    help="weight-only quantization (quantize-at-load): "
                         "int8 = per-output-channel scales, int4 = "
                         "group-wise scales — shrinks the per-token weight "
                         "sweep, the dominant decode bandwidth on CPUs")
    ap.add_argument("--wq-group-size", type=int, default=128,
                    help="int4 group length along the reduction dim "
                         "(clamped per tensor so groups stay TP-shard-local)")
    ap.add_argument("--wq-cache", default=None,
                    help="path for the packed QuantWeight checkpoint: load "
                         "it when present (72B-scale starts skip bf16 "
                         "materialization), else save after quantize-at-load")
    ap.add_argument("--arrival-every", type=int, default=0,
                    help="stagger arrivals by N decode steps per request")
    ap.add_argument("--max-new-spread", type=int, default=1,
                    help=">1 draws per-request max_new from [max_new/spread, max_new]")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--top-k", type=int, default=40)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--no-topk-sync", action="store_true",
                    help="disable paper §2.1b (baseline full-vocab gather)")
    ap.add_argument("--overlap", action="store_true",
                    help="continuous schedulers: overlapped host/device "
                         "engine loop — dispatch decode block N+1 against "
                         "block N's device futures while N's tokens land on "
                         "the host (greedy streams stay bit-identical to "
                         "the blocking loop)")
    ap.add_argument("--fault-plan", default="", metavar="SPEC",
                    help="deterministic fault injection for chaos runs "
                         "(continuous schedulers): ';'-separated clauses, "
                         "e.g. 'step:at=12;poison:slot=1,at=20;"
                         "migrate:handoff=0;alloc:at=8;delay:at=4,s=0.5' — "
                         "see repro.runtime.faults for the grammar.  "
                         "Injured requests are quarantined "
                         "(finish_reason=error); survivors' greedy streams "
                         "stay bit-identical to a clean run")
    ap.add_argument("--max-step-retries", type=int, default=3,
                    help="transient step failures are retried this many "
                         "times (exponential backoff) from the exact "
                         "pre-dispatch state before the blamed request is "
                         "quarantined")
    ap.add_argument("--retry-backoff-s", type=float, default=0.05,
                    help="base backoff before a step retry; doubles per "
                         "consecutive failure")
    ap.add_argument("--class-mix", default="", metavar="SPEC",
                    help="per-request priority classes for the synthetic "
                         "workload, drawn from a weighted mix, e.g. "
                         "'interactive=0.25,standard=0.5,batch=0.25' "
                         "(empty = everything 'standard')")
    ap.add_argument("--slo-interactive", type=float, default=0.0,
                    metavar="S", help="interactive-class per-token SLO "
                    "target in seconds (0 = unset); reported as "
                    "slo_attainment per class and consulted by the "
                    "overload controller's latency signal")
    ap.add_argument("--slo-standard", type=float, default=0.0, metavar="S",
                    help="standard-class per-token SLO target (seconds)")
    ap.add_argument("--slo-batch", type=float, default=0.0, metavar="S",
                    help="batch-class per-token SLO target (seconds)")
    ap.add_argument("--interactive-reserve-slots", type=int, default=0,
                    help="decode slots held back for interactive requests: "
                         "non-interactive admission stops once free slots "
                         "drop to this reserve")
    ap.add_argument("--interactive-reserve-blocks", type=int, default=0,
                    help="paged/disagg: KV pool blocks held back for "
                         "interactive admissions")
    ap.add_argument("--overload-degrade", action="store_true",
                    help="enable the adaptive degradation ladder (shed "
                         "batch -> suspend spec decode -> tighten "
                         "admission), walked with hysteresis from queue "
                         "depth + landed inter-token latency; see "
                         "repro.runtime.overload")
    ap.add_argument("--overload-queue-hi", type=int, default=0,
                    help="queue depth that counts as pressure "
                         "(0 = auto: 2x slots)")
    ap.add_argument("--overload-queue-lo", type=int, default=0,
                    help="queue depth that counts as clear "
                         "(0 = auto: slots/2)")
    ap.add_argument("--overload-patience", type=int, default=3,
                    help="consecutive pressured rounds before escalating "
                         "one ladder level")
    ap.add_argument("--overload-cooldown", type=int, default=6,
                    help="consecutive clear rounds before restoring one "
                         "ladder level")
    ap.add_argument("--stats-json", default=None, metavar="PATH",
                    help="dump the scheduler's full request_summary() and "
                         "raw stats counters (incl. overlap metrics: "
                         "host-overlap fraction, dispatch-ahead depth, shed "
                         "count) as JSON to PATH")
    return ap


def _jsonable(obj):
    """Recursively coerce numpy scalars/arrays so json.dump accepts the
    stats dicts."""
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, np.generic):
        return obj.item()
    return obj


def dump_stats_json(sched, path, extra=None):
    """Write request_summary() + raw stats counters (the full serving
    telemetry, overlap metrics included) to ``path``."""
    payload = {"request_summary": _jsonable(sched.request_summary()),
               "stats": _jsonable(sched.stats)}
    if extra:
        payload.update(_jsonable(extra))
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    return payload


def main(argv=None):
    use_compile_cache()
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.list_archs:
        from repro.core.capabilities import render_text
        print(render_text())
        return []

    eng = build_engine(args)
    cfg = eng.cfg
    if args.weight_quant != "none":
        from repro.models import model as M
        wb = M.decode_weight_bytes(eng.ctx)
        bb = M.decode_weight_bytes(M.ModelCtx.make(
            cfg, ParallelConfig(tp=args.tp, dp=args.dp, remat=False)))
        print(f"weight quant {args.weight_quant}"
              f"{f'-g{args.wq_group_size}' if args.weight_quant == 'int4' else ''}: "
              f"{wb['swept']/2**20:.1f} MiB swept/token vs "
              f"{bb['swept']/2**20:.1f} MiB bf16 "
              f"({bb['swept']/max(wb['swept'],1):.2f}x less)")
    sched = make_scheduler(eng, args)
    submit_workload(sched, cfg, args)
    t0 = time.monotonic()
    try:
        sched.run()
    finally:
        # the report (and --stats-json) flushes even when the run raised or
        # was interrupted: sched.done holds everything retired so far, so a
        # crashed chaos run still leaves its counters on disk
        _report(sched, cfg, args, time.monotonic() - t0)
    return sched.done


def _report(sched, cfg, args, dt):
    done = sched.done
    total_tokens = sum(len(r.output) for r in done if r.output is not None)
    print(f"served {len(done)} requests, {total_tokens} tokens "
          f"in {dt:.2f}s -> {1000*dt/max(total_tokens,1):.1f} ms/token "
          f"({args.scheduler}; arch={cfg.name}, tp={args.tp})")
    if args.scheduler in ("continuous", "paged", "disagg"):
        s = sched.stats
        util = s["active_slot_steps"] / max(1, s["slot_steps"])
        print(f"  decode steps {s['decode_steps']}, slot util {util:.0%}, "
              f"admission rounds {s['admission_rounds']} "
              f"({s['in_flight_admissions']} requests admitted in-flight)")
        lat = sched.request_summary()
        if "ttft_s" in lat:
            print(f"  ttft mean {lat['ttft_s']['mean']*1e3:.0f} ms "
                  f"(p50 {lat['ttft_s']['p50']*1e3:.0f}, "
                  f"max {lat['ttft_s']['max']*1e3:.0f}); queue mean "
                  f"{lat['queue_s']['mean']*1e3:.0f} ms")
        if s.get("chunked_admissions"):
            print(f"  chunked prefill: {s['chunked_admissions']} requests in "
                  f"{s['prefill_chunks']} chunks of <= {sched.chunk} tokens")
        if "spec" in lat:
            sp, tps = lat["spec"], lat.get("tokens_per_step", {})
            print(f"  spec decode (k={sched.spec_k}, "
                  f"ngram<={sched.spec_ngram}): acceptance "
                  f"{sp['acceptance_rate']:.0%}, mean accepted "
                  f"{sp['mean_accepted_per_step']:.2f} tokens/step "
                  f"({sp['mean_tokens_per_step']:.2f} emitted; "
                  f"tokens/step p50 {tps.get('p50', 1):.0f} "
                  f"p95 {tps.get('p95', 1):.0f})")
        if "decode_itl_admission_s" in lat:
            adm, itl = lat["decode_itl_admission_s"], lat["decode_itl_s"]
            print(f"  decode inter-token p50/p95 {itl['p50']*1e3:.1f}/"
                  f"{itl['p95']*1e3:.1f} ms (admission windows "
                  f"{adm['p50']*1e3:.1f}/{adm['p95']*1e3:.1f} ms)")
        if "faults" in lat:
            fc = lat["faults"]
            print(f"  faults: {fc['step_faults']} step faults "
                  f"({fc['step_retries']} retried), "
                  f"{fc['quarantined']} quarantined, "
                  f"{fc['timeouts']} timeouts, "
                  f"{fc['migration_faults']} migration faults, "
                  f"{fc['aborts_exhaustion']} exhaustion aborts, "
                  f"{fc['livelock_aborts']} livelock aborts; "
                  f"finish_reasons {lat['finish_reasons']}")
        if "classes" in lat:
            for name, c in lat["classes"].items():
                line = (f"  class {name}: {c.get('served', 0)} served, "
                        f"{c.get('shed', 0)} shed, "
                        f"{c.get('timeout', 0)} timed out")
                if "itl_s" in c:
                    line += (f"; itl p50/p95 {c['itl_s']['p50']*1e3:.1f}/"
                             f"{c['itl_s']['p95']*1e3:.1f} ms")
                if "slo_attainment" in c:
                    line += (f"; SLO {c['slo_attainment']:.0%} "
                             f"@ {c['slo_target_s']*1e3:.0f} ms/token")
                print(line)
        if "overload" in lat:
            ov = lat["overload"]
            print(f"  overload ladder: level {ov['level']} "
                  f"({ov['level_name']}), peak {ov['max_level_name']}, "
                  f"{ov['escalations']} escalations / "
                  f"{ov['restorations']} restorations")
        if lat.get("overlap", {}).get("enabled"):
            ov = lat["overlap"]
            print(f"  overlap: host-overlap {ov['host_overlap_fraction']:.0%} "
                  f"({ov['host_overlap_s']:.2f}s hidden, "
                  f"{ov['host_blocked_s']:.2f}s blocked, "
                  f"{ov['host_blocked_per_step_s']*1e3:.1f} ms/step); "
                  f"dispatch-ahead max {ov['max_dispatch_ahead']}, "
                  f"eos rollbacks {ov['eos_rollbacks']}")
    if args.scheduler in ("paged", "disagg"):
        s = sched.stats
        print(f"  pool {sched.n_blocks} x {sched.bs}-token blocks, "
              f"high-water {s['blocks_hwm']} blocks; prefill tokens "
              f"{s['prefill_tokens']} (+{s['prefill_tokens_saved']} reused), "
              f"preemptions {s['preemptions']}")
    if args.scheduler == "disagg":
        p = sched.request_summary()["pools"]
        print(f"  pools: {p['prefill_shards']} prefill + "
              f"{p['decode_shards']} decode shards; prefill occupancy "
              f"{p['prefill_occupancy']:.0%} over {p['prefill_steps']} "
              f"chunk steps")
        print(f"  migration: {p['migrated_blocks']} blocks copied "
              f"({p['migration_bytes']/2**20:.1f} MiB), "
              f"{p['migration_skipped_blocks']} skipped via decode-side "
              f"prefix hits, {p['handoffs']} handoffs, "
              f"{p['migration_deferrals']} deferrals")
        if "migration_wait_s" in p:
            w = p["migration_wait_s"]
            print(f"  migration wait p50/p95 {w['p50']*1e3:.1f}/"
                  f"{w['p95']*1e3:.1f} ms")
    for r in done[:4]:
        if r.output is None:
            continue
        out = r.output if r.output.ndim == 1 else r.output[..., 0]
        print(f"  req {r.rid}: {len(r.output)} tokens, first 8: {out[:8].tolist()}")
    if args.stats_json:
        if args.scheduler == "wave":
            print("  --stats-json needs a continuous scheduler; skipping")
        else:
            dump_stats_json(sched, args.stats_json,
                            extra={"wall_s": dt, "total_tokens": total_tokens,
                                   "scheduler": args.scheduler,
                                   "arch": cfg.name})
            print(f"  stats -> {args.stats_json}")


if __name__ == "__main__":
    main()
