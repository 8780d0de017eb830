import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any other import (jax locks the device count on first init).

"""Multi-pod dry-run (deliverable (e)).

For every (architecture x input-shape x mesh) combination, lower + compile
the real step function (train_step / prefill_step / decode_step) on the
production mesh — 16x16 = 256 chips single-pod, 2x16x16 = 512 multi-pod —
with ShapeDtypeStruct stand-ins (no allocation), and record:

  * memory_analysis()  — per-device bytes: proves the configuration fits;
  * cost_analysis()    — HLO FLOPs / bytes for the roofline (§g);
  * the collective schedule parsed from the optimized HLO — op counts,
    payload bytes, and estimated per-device wire bytes per collective kind.

Usage:
  python -m repro.launch.dryrun                      # full 10x4x2 sweep
  python -m repro.launch.dryrun --arch yi-9b --shape decode_32k --multi-pod
  python -m repro.launch.dryrun --out experiments/dryrun
"""
import argparse
import json
import re
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.configs import ASSIGNED_ARCHS, get_config, get_shape
from repro.configs.base import INPUT_SHAPES, SamplingConfig
from repro.launch import inputs as I
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.models.common import specs_of
from repro.runtime.engine import make_decode_step, make_prefill_step
from repro.training.train_loop import AdamWConfig, make_train_step
from repro.training.zero import zero_state_defs

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
               "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
               "u64": 8, "c64": 8}

COLL_RE = re.compile(
    r"=\s*(?P<res>\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s*"
    r"(?P<kind>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)
SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
GROUPS_RE = re.compile(r"replica_groups=\{(\{[^}]*\})")
GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _result_bytes(res: str) -> int:
    total = 0
    for dt, dims in SHAPE_RE.findall(res):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = GROUPS_RE.search(line)
    if m:
        return len(m.group(1).strip("{}").split(","))
    return 1


def wire_bytes(kind: str, result_bytes: int, n: int) -> int:
    """Per-device bytes crossing links for ring implementations."""
    if n <= 1:
        return 0
    if kind == "all-gather":
        return result_bytes * (n - 1) // n
    if kind == "all-reduce":
        return 2 * result_bytes * (n - 1) // n
    if kind == "reduce-scatter":
        return result_bytes * (n - 1)          # result is the 1/n shard
    if kind == "all-to-all":
        return result_bytes * (n - 1) // n
    return result_bytes                         # collective-permute


def parse_collectives(hlo: str) -> dict:
    per_kind = {}
    seen_done = set()
    for line in hlo.splitlines():
        m = COLL_RE.search(line)
        if not m:
            continue
        if "-done(" in line:
            continue                            # count start ops only
        kind = m.group("kind")
        rb = _result_bytes(m.group("res"))
        n = _group_size(line)
        d = per_kind.setdefault(kind, {"count": 0, "result_bytes": 0, "wire_bytes": 0})
        d["count"] += 1
        d["result_bytes"] += rb
        d["wire_bytes"] += wire_bytes(kind, rb, n)
    return per_kind


def _opt_input_specs(ctx, mesh):
    defs = zero_state_defs(M.model_defs(ctx), ctx.dist)
    from repro.models.common import is_def

    return (
        jax.tree.map(
            lambda d: jax.ShapeDtypeStruct(
                d.shape, d.dtype, sharding=NamedSharding(mesh, d.spec)
            ),
            defs, is_leaf=is_def,
        ),
        specs_of(defs),
    )


def build_lowered(arch: str, shape_name: str, *, multi_pod: bool,
                  use_pallas: bool = False, overrides=None,
                  n_layers_override: int = 0):
    cfg = get_config(arch)
    if n_layers_override:
        import dataclasses as _dc

        cfg = _dc.replace(cfg, n_layers=n_layers_override, force_unroll=True)
    shape = get_shape(shape_name)
    overrides = dict(overrides or {})
    tp = overrides.pop("tp", 16)
    dp = overrides.pop("dp", 16)
    grad_accum = overrides.pop("grad_accum", 1)
    if (tp, dp) == (16, 16):
        mesh = make_production_mesh(multi_pod=multi_pod)
    else:  # same chip count, different geometry (perf experiments)
        shp = (2, dp, tp) if multi_pod else (dp, tp)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
        mesh = compat.make_mesh(shp, axes)
    pods = 2 if multi_pod else 1
    par = I.parallel_for(cfg, shape, tp=tp, dp=dp, pods=pods, use_pallas=use_pallas)
    if overrides:
        import dataclasses

        par = dataclasses.replace(par, **overrides)
    ctx = M.ModelCtx.make(cfg, par, pod_axis="pod" if multi_pod else None)
    pspecs = M.param_specs(ctx)
    p_in = I.param_input_specs(ctx, mesh)
    sm = partial(compat.shard_map, mesh=mesh, check_vma=False)
    rep_b = I.replicate_batch_for(ctx, shape)
    b_ax = None if rep_b else I.batch_axes(ctx)
    text_len = I.text_len_for(cfg, shape)

    if shape.kind == "train":
        step = make_train_step(ctx, AdamWConfig(), zero1=True,
                               grad_accum=grad_accum)
        opt_in, ospecs = _opt_input_specs(ctx, mesh)
        tok = I.token_specs(ctx, mesh, shape.global_batch, text_len,
                            replicate_batch=rep_b)
        batch_in = {"tokens": tok, "labels": tok}
        bspecs = {"tokens": tok.sharding.spec, "labels": tok.sharding.spec}
        feat = I.feature_specs(ctx, mesh, shape.global_batch, replicate_batch=rep_b)
        if feat is not None:
            batch_in["features"] = feat
            bspecs["features"] = feat.sharding.spec
        fn = sm(step, in_specs=(pspecs, ospecs, bspecs),
                out_specs=(pspecs, ospecs, P()))
        lowered = jax.jit(fn, donate_argnums=(0, 1)).lower(p_in, opt_in, batch_in)

    elif shape.kind == "prefill":
        step = make_prefill_step(ctx, SamplingConfig())
        caches_in, cspecs = I.cache_input_specs(ctx, mesh, shape)
        tok = I.token_specs(ctx, mesh, shape.global_batch, text_len,
                            replicate_batch=rep_b)
        feat = I.feature_specs(ctx, mesh, shape.global_batch, replicate_batch=rep_b)
        tok_out = P(b_ax) if cfg.n_codebooks == 1 else P(b_ax, None)
        if feat is None:
            fn = sm(lambda p, t, c, r: step(p, t, None, c, r),
                    in_specs=(pspecs, tok.sharding.spec, cspecs, P()),
                    out_specs=(tok_out, cspecs))
            lowered = jax.jit(fn, donate_argnums=(2,)).lower(
                p_in, tok, caches_in, I.rng_spec(mesh))
        else:
            fn = sm(step,
                    in_specs=(pspecs, tok.sharding.spec, feat.sharding.spec,
                              cspecs, P()),
                    out_specs=(tok_out, cspecs))
            lowered = jax.jit(fn, donate_argnums=(3,)).lower(
                p_in, tok, feat, caches_in, I.rng_spec(mesh))

    else:  # decode
        step = make_decode_step(ctx, SamplingConfig())
        caches_in, cspecs = I.cache_input_specs(ctx, mesh, shape)
        tok_spec = P(b_ax) if cfg.n_codebooks == 1 else P(b_ax, None)
        tshape = (shape.global_batch,) if cfg.n_codebooks == 1 else (
            shape.global_batch, cfg.n_codebooks)
        tok = jax.ShapeDtypeStruct(tshape, jnp.int32,
                                   sharding=NamedSharding(mesh, tok_spec))
        cur = jax.ShapeDtypeStruct((), jnp.int32,
                                   sharding=NamedSharding(mesh, P()))
        fn = sm(step, in_specs=(pspecs, tok_spec, cspecs, P(), P()),
                out_specs=(tok_spec, cspecs))
        lowered = jax.jit(fn, donate_argnums=(2,)).lower(
            p_in, tok, caches_in, cur, I.rng_spec(mesh))

    return lowered, ctx, mesh, shape


def analyze(lowered, compiled, ctx, shape, *, t_compile: float) -> dict:
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    colls = parse_collectives(hlo)
    from repro.core.zero_copy import count_copies

    cfg = ctx.cfg
    n_chips = ctx.dist.tp * ctx.dist.dp * ctx.dist.pods
    rec = {
        "arch": cfg.name,
        "shape": shape.name,
        "kind": shape.kind,
        "chips": n_chips,
        "compile_s": round(t_compile, 1),
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
        "collectives": colls,
        "copies": count_copies(hlo),
        "model_params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    return rec


def _cost_probe(arch, shape_name, multi_pod, n_layers, overrides):
    """flops / bytes / collectives of a depth-reduced, FULLY-UNROLLED compile
    (inner chunk scans unrolled too — cost_analysis counts loop bodies once)."""
    from repro.models.common import UNROLL_SCANS

    token = UNROLL_SCANS.set(True)
    try:
        lowered, ctx, mesh, shape = build_lowered(
            arch, shape_name, multi_pod=multi_pod, overrides=overrides,
            n_layers_override=n_layers)
        compiled = lowered.compile()
    finally:
        UNROLL_SCANS.reset(token)
    cost = compiled.cost_analysis() or {}
    colls = parse_collectives(compiled.as_text())
    return {
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "collectives": colls,
    }


def _layer_bases(arch: str) -> tuple:
    """(base1, base2, n_full_periods): two shallow depths whose difference is
    exactly one pattern period, plus how many periods the full config has.
    XLA's cost_analysis counts while-loop bodies ONCE, so per-layer costs are
    recovered by the two-point delta and scaled to full depth."""
    cfg = get_config(arch)
    p = len(cfg.layer_pattern)
    extra = len(cfg.dense_ffn_layers)
    n_regular = cfg.n_layers - extra
    n_periods = n_regular // p
    rem = n_regular % p
    base1 = extra + rem + p
    base2 = extra + rem + 2 * p
    return base1, base2, n_periods


def _merge_coll(c1, c2, scale):
    """c1 + scale * (c2 - c1), per collective kind/field."""
    out = {}
    for kind in set(c1) | set(c2):
        a = c1.get(kind, {"count": 0, "result_bytes": 0, "wire_bytes": 0})
        b = c2.get(kind, {"count": 0, "result_bytes": 0, "wire_bytes": 0})
        out[kind] = {
            f: round(a[f] + scale * (b[f] - a[f]))
            for f in ("count", "result_bytes", "wire_bytes")
        }
    return out


def run_one(arch: str, shape_name: str, *, multi_pod: bool, out_dir: str,
            force: bool = False, overrides=None) -> dict:
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_tag}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    t0 = time.time()
    lowered, ctx, mesh, shape = build_lowered(arch, shape_name, multi_pod=multi_pod,
                                              overrides=overrides)
    compiled = lowered.compile()
    t_compile = time.time() - t0
    print(compiled.memory_analysis())
    rec = analyze(lowered, compiled, ctx, shape, t_compile=t_compile)
    # --- loop-aware cost extrapolation (see _layer_bases) -------------------
    base1, base2, n_periods = _layer_bases(arch)
    if n_periods > 1:
        c1 = _cost_probe(arch, shape_name, multi_pod, base1, overrides)
        c2 = _cost_probe(arch, shape_name, multi_pod, base2, overrides)
        scale = n_periods - 1
        rec["flops"] = c1["flops"] + scale * (c2["flops"] - c1["flops"])
        rec["bytes_accessed"] = c1["bytes_accessed"] + scale * (
            c2["bytes_accessed"] - c1["bytes_accessed"])
        rec["collectives"] = _merge_coll(c1["collectives"], c2["collectives"], scale)
        rec["cost_extrapolated"] = {"base1": base1, "base2": base2,
                                    "n_periods": n_periods}
    os.makedirs(out_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ASSIGNED_ARCHS)
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = [args.multi_pod] if (args.arch or args.multi_pod) else [False, True]
    if args.single_pod_only:
        meshes = [False]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
                try:
                    t0 = time.time()
                    rec = run_one(arch, shape, multi_pod=mp, out_dir=args.out,
                                  force=args.force)
                    coll_wire = sum(v["wire_bytes"] for v in rec["collectives"].values())
                    print(f"OK   {tag}: {rec['flops']:.3e} flops, "
                          f"temp {rec['memory']['temp_bytes']/2**30:.2f} GiB/dev, "
                          f"wire {coll_wire/2**20:.1f} MiB/dev "
                          f"({time.time()-t0:.0f}s)", flush=True)
                except Exception as e:  # noqa: BLE001 - report and continue
                    failures.append((tag, repr(e)))
                    print(f"FAIL {tag}: {e}", flush=True)
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print("\nAll dry-run combinations compiled successfully.")


if __name__ == "__main__":
    main()
