#!/usr/bin/env python3
"""Bring-up run of the serving path on a TPU.

    python chip_smoke.py             # one chip: yi-9b, 24 of 48 layers
    python chip_smoke.py --chips 4   # four chips: the tensor-parallel phase

One chip: yi-9b at its published widths (d_model 4096, 32 q / 4 kv heads of
128, d_ff 11008, vocab 64,000), cut in depth from 48 to 24 layers, the
share of one of two 24-layer pipeline stages.  The engine and the paged
scheduler come from ``repro.launch.serve`` (8 slots, 2,048-token stripes,
256-token prefill chunks, prefix cache on, top-k 40), behind the HTTP
frontend's ``EngineService`` + ``HttpFrontend``.  Client threads POST 8
``/v1/completions`` requests of 32 tokens, two of them streamed, with
300-1,000-token prompts drawn from a fixed seed, so chunked prefill and the
mixed prefill/decode step run.

Four chips: yi-9b at full depth (48 layers, more than one chip holds) at
tp=4 through the same path for 4 requests; then the 24-layer cut at tp=1 on
device 0 and at tp=4, one after the other, greedy, compared by their first
decode step's logits and by their token streams.

The run fails (non-zero exit, no ``ok`` line) unless JAX's devices are
TPUs, every kernel runs compiled, the mixed and chunk steps contain a
Pallas kernel, every response is a 200 that ends ``stop`` or ``length`` at
its budget, and the engine thread survives.  The last line is the JSON
``{"ok": true, "device": {...}}`` record.  The weights are random.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import gc
import http.client
import json
import os
import re
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro import compat  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.frontend import EngineService, HttpFrontend  # noqa: E402
from repro.models import model as M  # noqa: E402

ARCH = "yi-9b"
CUT_LAYERS = 24
SLOTS = 8
MAX_LEN = 2048
PROMPT_LENS = (300, 1000)
SEED = 0
# Bound on |logit(tp=1) - logit(tp=4)| at the first decode step (logits of
# std ~1).  The shards sum in another order, so the bf16 residual stream
# rounds differently at every layer.  On 4 virtual CPU devices this phase
# measured a largest gap of 0.030-0.032 at d_model 256-2048 and 2-8 layers,
# barely growing with depth; the bound keeps 4x of headroom.
LOGIT_TOL = 0.125


class SmokeFailure(Exception):
    """A check of the run failed; the message says which."""


def cut(cfg, n_layers):
    """``cfg`` at its published widths with only its depth cut."""
    return dataclasses.replace(cfg, name=f"{cfg.name}-{n_layers}L",
                               n_layers=n_layers)


def _args(tp, top_k, block_steps):
    return serve.build_parser().parse_args([
        "--arch", ARCH, "--scheduler", "paged", "--slots", str(SLOTS),
        "--max-len", str(MAX_LEN), "--tp", str(tp), "--top-k", str(top_k),
        "--block-steps", str(block_steps)])


def build(cfg, *, tp=1, top_k=40, block_steps=8):
    """Engine + paged scheduler through the serve entry points."""
    args = _args(tp, top_k, block_steps)
    eng = serve.build_engine(args, cfg=cfg)
    return eng, serve.make_scheduler(eng, args)


def param_bytes(params):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))


def make_prompts(vocab, n, seed=SEED, lens=PROMPT_LENS):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, int(rng.integers(lens[0], lens[1] + 1)))
            .tolist() for _ in range(n)]


def _post(port, body, timeout):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        c.request("POST", "/v1/completions", json.dumps(body),
                  {"Content-Type": "application/json"})
        r = c.getresponse()
        return r.status, r.read(), time.monotonic()
    finally:
        c.close()


def _parse(body, raw):
    """-> (token ids, finish_reason) of a unary or SSE response."""
    if not body.get("stream"):
        choice = json.loads(raw)["choices"][0]
        return choice["token_ids"], choice["finish_reason"]
    toks, finish = [], None
    for ev in raw.decode().split("\n\n"):
        if not ev.startswith("data: ") or ev == "data: [DONE]":
            continue
        payload = json.loads(ev[len("data: "):])
        if "error" in payload:
            raise SmokeFailure(f"stream failed: {payload['error']}")
        choice = payload["choices"][0]
        toks += choice["token_ids"]
        finish = choice.get("finish_reason", finish)
    return toks, finish


def serve_requests(sched, prompts, *, max_tokens, n_stream, timeout=1500):
    """POST ``prompts`` from client threads to an HTTP frontend over
    ``sched``; the first ``n_stream`` stream over SSE.  Checks every
    response and returns a report with each request's tokens."""
    service = EngineService(sched, max_pending=len(prompts) + 1,
                            idle_wait_s=0.005)
    frontend = HttpFrontend(service, port=0)
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def run_loop():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(frontend.start())
        started.set()
        loop.run_forever()

    thread = threading.Thread(target=run_loop, daemon=True,
                              name="smoke-frontend")
    thread.start()
    if not started.wait(60):
        raise SmokeFailure("frontend did not start")
    bodies = [{"prompt": p, "max_tokens": max_tokens, "stream": i < n_stream}
              for i, p in enumerate(prompts)]
    t0 = time.monotonic()
    try:
        with ThreadPoolExecutor(len(bodies)) as pool:
            results = list(pool.map(
                lambda b: _post(frontend.port, b, timeout), bodies))
    finally:
        # a graceful drain re-raises whatever killed the engine thread
        stopped = asyncio.run_coroutine_threadsafe(frontend.stop(), loop)
        try:
            stopped.result(timeout)
        except Exception as e:
            raise SmokeFailure(f"engine thread died: {e!r}") from e
        finally:
            loop.call_soon_threadsafe(loop.stop)
            thread.join(60)
            loop.close()
    tokens, finishes = [], []
    for body, (status, raw, _) in zip(bodies, results):
        if status != 200:
            raise SmokeFailure(f"response status {status}: {raw[:300]!r}")
        toks, finish = _parse(body, raw)
        if finish not in ("stop", "length"):
            raise SmokeFailure(f"finish_reason {finish!r}")
        if len(toks) != max_tokens and finish != "stop":
            raise SmokeFailure(f"{len(toks)} of {max_tokens} tokens "
                               f"without a stop")
        tokens.append(toks)
        finishes.append(finish)
    return {"tokens": tokens, "finish_reasons": finishes,
            "first_response_s": min(t for _, _, t in results) - t0,
            "wall_s": max(t for _, _, t in results) - t0,
            "tokens_served": sum(map(len, tokens))}


def step_kernel_counts(eng, sched):
    """``tpu_custom_call`` ops in the lowered decode, mixed and chunk
    programs of the paged engine, at the scheduler's live shapes."""
    def sds(x, dtype=None):
        return jax.ShapeDtypeStruct(jnp.shape(x), dtype or jnp.asarray(x).dtype)

    B, C = sched.B, sched.chunk
    slot_i = sds(sched.pos, jnp.int32)
    slot_b = sds(sched.dones, jnp.bool_)
    tok = sds(sched.tok, jnp.int32)
    bt = sds(sched.bt, jnp.int32)
    ctok = jax.ShapeDtypeStruct((B, C), jnp.int32)
    rng = jax.random.key(0)
    cb = eng._cb_paged()
    programs = {
        "decode": (cb["decode"][max(cb["decode"])],
                   (eng.params, tok, sched.caches, slot_i, slot_b, slot_i,
                    slot_i, bt, rng)),
        "mixed": (eng._mixed(True),
                  (eng.params, ctok, sched.caches, slot_b, slot_b, slot_i,
                   slot_i, slot_i, tok, slot_i, slot_b, slot_i, slot_i, bt,
                   bt, rng)),
        "chunk": (eng._chunk_only(True),
                  (eng.params, ctok, sched.caches, slot_b, slot_b, slot_i,
                   slot_i, slot_i, bt, rng)),
    }
    return {name: fn.lower(*a).as_text().count("tpu_custom_call")
            for name, (fn, a) in programs.items()}


def all_reduces_per_layer(eng, sched, n_layers):
    """All-reduce ops in one iteration of the layer loop (the while loop
    whose condition compares against the depth) of the compiled one-token
    paged decode step, and the all-reduce count of every loop body."""
    cb = eng._cb_paged()
    if 1 not in cb["decode"]:
        cb["decode"][1] = cb["build_decode"](1)
    i32 = jax.ShapeDtypeStruct((sched.B,), jnp.int32)
    b = jax.ShapeDtypeStruct((sched.B,), jnp.bool_)
    bt = jax.ShapeDtypeStruct(sched.bt.shape, jnp.int32)
    hlo = cb["decode"][1].lower(eng.params, i32, sched.caches, i32, b, i32,
                                i32, bt, jax.random.key(0)).compile().as_text()
    comps = {m.group(1).lstrip("%"): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?(%?[\w.\-]+) \([^\n]*\{\n(.*?)\n\}", hlo, re.S | re.M)}

    def n_all_reduce(text):
        return sum(1 for line in text.splitlines()
                   if re.search(r"\ball-reduce(?:-start)?\(", line))

    loops, per_layer = {}, None
    for text in comps.values():
        for m in re.finditer(r" while\([^\n]*", text):
            body = re.search(r"body=%?([\w.\-]+)", m.group(0)).group(1)
            cond = re.search(r"condition=%?([\w.\-]+)", m.group(0)).group(1)
            loops[body] = n_all_reduce(comps.get(body, ""))
            if f"constant({n_layers})" in comps.get(cond, ""):
                per_layer = loops[body]
    return per_layer, loops


def last_logits(eng, tokens):
    """Full-vocab fp32 logits at the last position of ``tokens`` (B, L):
    one cache-free forward through the engine's own sharding."""
    ctx = eng.ctx

    def fwd(params, toks):
        logits, _, _ = M.forward(params, toks, ctx, last_only=True)
        return logits[:, -1]

    fn = jax.jit(compat.shard_map(
        fwd, mesh=eng.mesh, in_specs=(M.param_specs(ctx), P()),
        out_specs=P(None, ctx.dist.model_axis), check_vma=False))
    out = np.asarray(fn(eng.params, jnp.asarray(tokens, jnp.int32)))
    return out[:, :eng.cfg.vocab_size]


def live_bytes():
    return sum(x.nbytes for x in jax.live_arrays())


def device_record():
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def require_tpu(count):
    d = jax.devices()
    if d[0].platform != "tpu":
        raise SmokeFailure(f"JAX finds no TPU (platform {d[0].platform!r})")
    if len(d) != count:
        raise SmokeFailure(f"expected {count} TPU chip(s), JAX finds {len(d)}")


def check_kernels(counts, on_tpu):
    if on_tpu and ops.interpret():
        raise SmokeFailure("Pallas kernels would run in interpret mode")
    if on_tpu:
        for step in ("mixed", "chunk"):
            if counts[step] == 0:
                raise SmokeFailure(f"{step} step has no tpu_custom_call")


def one_chip(cfg, *, n_requests=8, n_stream=2, max_tokens=32,
             prompt_lens=PROMPT_LENS, on_tpu=True):
    """The one-chip serving phase; returns its report."""
    eng, sched = build(cfg)
    print(f"parameter bytes {param_bytes(eng.params)} "
          f"({cfg.param_count()} parameters)", flush=True)
    prompts = make_prompts(cfg.vocab_size, n_requests, lens=prompt_lens)
    rep = serve_requests(sched, prompts, max_tokens=max_tokens,
                         n_stream=n_stream)
    rep["kernels"] = step_kernel_counts(eng, sched)
    check_kernels(rep["kernels"], on_tpu)
    print(f"served {len(prompts)} requests ({n_stream} streamed): first "
          f"response {rep['first_response_s']:.3f} s (compiling included), "
          f"wall {rep['wall_s']:.3f} s, {rep['tokens_served']} tokens; "
          f"finish reasons {sorted(set(rep['finish_reasons']))}", flush=True)
    print(f"tpu_custom_call per step: {rep['kernels']}", flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}", flush=True)
    return rep


def four_chip(full_cfg, cut_cfg, *, n_serve=4, n_compare=8, steps=16,
              on_tpu=True, tol=LOGIT_TOL):
    """The four-chip phase: full depth at tp=4, then tp=1 vs tp=4."""
    eng, sched = build(full_cfg, tp=4, block_steps=1)
    print(f"{full_cfg.name} at tp=4: parameter bytes "
          f"{param_bytes(eng.params)}", flush=True)
    rep = serve_requests(sched, make_prompts(full_cfg.vocab_size, n_serve),
                         max_tokens=32, n_stream=1)
    rep["kernels"] = step_kernel_counts(eng, sched)
    check_kernels(rep["kernels"], on_tpu)
    print(f"served {n_serve} requests: first response "
          f"{rep['first_response_s']:.3f} s, wall {rep['wall_s']:.3f} s, "
          f"{rep['tokens_served']} tokens; tpu_custom_call per step "
          f"{rep['kernels']}", flush=True)
    del eng, sched
    gc.collect()
    print(f"freed; live device bytes {live_bytes()}", flush=True)

    prompts = make_prompts(cut_cfg.vocab_size, n_compare, seed=SEED + 1)
    probe = np.asarray([p[:PROMPT_LENS[0]] for p in prompts], np.int32)
    runs = {}
    for tp in (1, 4):
        eng, sched = build(cut_cfg, tp=tp, top_k=1, block_steps=1)
        rep = serve_requests(sched, prompts, max_tokens=steps, n_stream=0)
        if tp == 1:
            # the probe's next token, chosen once, so that both widths
            # score the same context
            nxt = last_logits(eng, probe).argmax(-1).astype(np.int32)
            probe = np.concatenate([probe, nxt[:, None]], 1)
        # the logits the first decode step after the probe samples from
        runs[tp] = {"tokens": rep["tokens"],
                    "logits": last_logits(eng, probe)}
        if tp == 4:
            per_layer, loops = all_reduces_per_layer(eng, sched,
                                                     cut_cfg.n_layers)
            print(f"tp=4 decode step: all-reduces per layer {per_layer} "
                  f"(per loop body {loops})", flush=True)
            last = eng
        del eng, sched
        gc.collect()
        print(f"tp={tp} done; live device bytes {live_bytes()}", flush=True)

    a, b = runs[1], runs[4]
    gap = float(np.abs(a["logits"] - b["logits"]).max())
    print(f"first decode step logits, tp=1 vs tp=4: max |diff| {gap:.6f} "
          f"(tolerance {tol}; logit std {float(a['logits'].std()):.4f})",
          flush=True)
    agree = total = 0
    first_div = None
    for r, (ta, tb) in enumerate(zip(a["tokens"], b["tokens"])):
        for s, (x, y) in enumerate(zip(ta, tb)):
            total += 1
            agree += x == y
            if x != y and first_div is None:
                first_div = (r, s, x, y)
    print(f"greedy token agreement tp=1 vs tp=4: {agree}/{total} over "
          f"{n_compare} requests x {steps} steps", flush=True)
    if first_div is not None:
        r, s, x, y = first_div
        ctx_toks = np.asarray([prompts[r] + a["tokens"][r][:s]], np.int32)
        lg = last_logits(last, ctx_toks)[0]
        print(f"first divergence: request {r} step {s}: tp=1 token {x}, "
              f"tp=4 token {y}; tp=4 logit of {y} minus logit of {x}: "
              f"{float(lg[y] - lg[x]):.6f}", flush=True)
    if not gap <= tol:
        raise SmokeFailure(f"tp=1 and tp=4 logits differ by {gap} > {tol}")
    return {"logit_gap": gap, "agreement": (agree, total)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip tensor-parallel phase")
    args = ap.parse_args(argv)
    try:
        cache_dir = use_compile_cache()
        require_tpu(args.chips)
        dev = device_record()
        print(f"jax {jax.__version__}; device {dev['kind']} x {dev['count']};"
              f" compile cache {cache_dir}", flush=True)
        full = get_config(ARCH)
        cfg = cut(full, CUT_LAYERS)
        print(f"model {full.name} at published widths (d_model "
              f"{cfg.d_model}, {cfg.n_heads} q / {cfg.n_kv_heads} kv heads "
              f"of {cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
              f"{cfg.vocab_size}); depth cut from {full.n_layers} to "
              f"{cfg.n_layers} layers, one of two {cfg.n_layers}-layer "
              f"pipeline stages", flush=True)
        if args.chips == 1:
            one_chip(cfg)
        else:
            four_chip(full, cfg)
    except SmokeFailure as e:
        print(f"FAIL: {e}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device_record()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
