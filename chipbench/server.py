"""The system under test, as a user reaches it: the program's HTTP frontend
(``HttpFrontend`` -> ``EngineService``) over the paged continuous-batching
scheduler that ``repro.launch.serve.make_scheduler`` builds, on an engine
whose weights the benchmark draws from the seed.

Sampling is greedy over the program's top-k path (top_k candidates, the
first taken), so every served token can be checked against the reference.

With ``instrument`` the engine's dispatches and the scheduler's phases are
wrapped at run time in ``jax.profiler.TraceAnnotation`` host spans named
``chipbench.*``, and each dispatch records the work it carries (per step,
the live context length of each decoding slot; the offset and length of
each prefill chunk) for the per-layer metrics."""
from __future__ import annotations

import asyncio
import threading

import jax
import numpy as np

from repro.configs import ModelConfig, ParallelConfig, SamplingConfig
from repro.launch import serve
from repro.launch.frontend import EngineService, HttpFrontend
from repro.launch.mesh import make_local_mesh
from repro.models import model as M
from repro.models.common import is_def
from repro.runtime.engine import Engine

import archs
import weights as W

SCHED_SPANS = ("serve_step", "_retire", "_admit", "_mixed_step",
               "_decode_block", "_materialize")


def program_config(cfg: dict) -> ModelConfig:
    """The program's config object for a configuration file: its
    architecture's (``arch/<model_type>.py``)."""
    return archs.load(cfg).program_config(cfg)


def parallel_config(cfg: dict) -> ParallelConfig:
    """The architecture's ``parallel_config`` where it gives one, else the
    configuration's ``serving`` sizes on one data-parallel replica."""
    arch = archs.load(cfg)
    if hasattr(arch, "parallel_config"):
        return arch.parallel_config(cfg)
    s = cfg["serving"]
    return ParallelConfig(tp=s["tp"], dp=1, remat=False,
                          kv_block_size=s["kv_block_size"],
                          prefill_chunk=s["prefill_chunk"])


def scheduler_args(cfg: dict):
    s = cfg["serving"]
    return serve.build_parser().parse_args([
        "--scheduler", "paged", "--slots", str(s["slots"]),
        "--block-steps", str(s["block_steps"]), "--tp", str(s["tp"]),
        "--max-len", str(s["max_len"]), "--top-k", str(s["top_k"])])


def make_mesh(cfg: dict):
    return make_local_mesh(1, cfg["serving"]["tp"])


def context(cfg: dict):
    return M.ModelCtx.make(program_config(cfg), parallel_config(cfg))


def draw_weights(cfg: dict, seed: int, mesh):
    ctx = context(cfg)
    plan = ctx.plan
    if (plan.n_heads_p != plan.n_heads or plan.vocab_p != cfg["vocab_size"]):
        raise ValueError("padded heads or vocabulary: the reference reads "
                         "no padding")
    return W.draw(seed, M.model_defs(ctx), is_def, mesh,
                  getattr(archs.load(cfg), "STD", {}))


def build_engine(cfg: dict, params, mesh):
    s = cfg["serving"]
    return Engine(cfg=program_config(cfg), parallel=parallel_config(cfg),
                  sampling=SamplingConfig(top_k=s["top_k"], greedy=True),
                  mesh=mesh, max_len=s["max_len"], params=params)


def make_scheduler(eng, cfg: dict):
    return serve.make_scheduler(eng, scheduler_args(cfg))


def warm_up(sched, vocab: int, seed: int = 0):
    """Runs the programs the traffic uses at their served shapes: the mixed
    chunk+decode step and decode blocks of 8, 4, 2 and 1 steps (a budget of
    2 * block_steps walks the block sizes down)."""
    rng = np.random.default_rng(seed)
    steps = 2 * sched.block_steps
    for _ in range(2):
        sched.submit(rng.integers(0, vocab, 16).astype(np.int32), steps)
    sched.run()


class Recorder:
    """Host spans and per-dispatch work records of an instrumented run."""

    def __init__(self):
        # one dict per dispatch (kind, n, decode_ctx, chunks), appended by
        # the engine's one worker thread; the index names its host span
        self.dispatches = []

    def _note(self, kind, work):
        self.dispatches.append({"kind": kind, **work})
        return len(self.dispatches) - 1

    def wrap_engine(self, eng):
        decode, mixed = eng.decode_slots_paged, eng.mixed_step_paged

        def decode_paged(caches, tok, pos, done, remaining, eos, bt, rng,
                         *, n=1):
            pos, done, rem = (np.asarray(x) for x in (pos, done, remaining))
            live = [[int(p) + i + 1 for p, d, r in zip(pos, done, rem)
                     if not d and r > i] for i in range(n)]
            idx = self._note("decode", {"n": n, "decode_ctx": live,
                                        "chunks": []})
            with jax.profiler.TraceAnnotation(f"chipbench.decode#{idx}"):
                return decode(caches, tok, pos, done, remaining, eos, bt,
                              rng, n=n)

        def mixed_paged(caches, ctokens, admit, first, clens, starts, totals,
                        tok, pos, done, remaining, eos, bt_w, bt, rng):
            a, c, st = (np.asarray(x) for x in (admit, clens, starts))
            p, d, r = (np.asarray(x) for x in (pos, done, remaining))
            live = [[int(pi) + 1 for pi, di, ri in zip(p, d, r)
                     if not di and ri > 0]]
            chunks = [(int(s0), int(cl)) for ai, s0, cl in zip(a, st, c)
                      if ai]
            idx = self._note("mixed", {"n": 1, "decode_ctx": live,
                                       "chunks": chunks})
            with jax.profiler.TraceAnnotation(f"chipbench.mixed#{idx}"):
                return mixed(caches, ctokens, admit, first, clens, starts,
                             totals, tok, pos, done, remaining, eos, bt_w,
                             bt, rng)

        eng.decode_slots_paged = decode_paged
        eng.mixed_step_paged = mixed_paged

    @staticmethod
    def wrap_scheduler(sched):
        for name in SCHED_SPANS:
            fn = getattr(sched, name)

            def spanned(*a, _fn=fn, _n=f"chipbench.sched.{name.lstrip('_')}",
                        **k):
                with jax.profiler.TraceAnnotation(_n):
                    return _fn(*a, **k)

            setattr(sched, name, spanned)


class Server:
    """HTTP frontend + engine service on an event-loop thread of their own."""

    def __init__(self, sched):
        self.sched = sched
        self.service = EngineService(sched, max_pending=1 << 20)
        self.frontend = HttpFrontend(self.service, port=0)
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name="chipbench-server")
        self._started = threading.Event()

    def _run(self):
        asyncio.set_event_loop(self.loop)
        self.loop.run_until_complete(self.frontend.start())
        self._started.set()
        self.loop.run_forever()

    def start(self, timeout=60.0):
        self.thread.start()
        if not self._started.wait(timeout):
            raise RuntimeError("frontend did not start")
        return self.frontend.port

    def stop(self, timeout=120.0):
        """Graceful drain; re-raises what killed the engine thread."""
        fut = asyncio.run_coroutine_threadsafe(self.frontend.stop(),
                                               self.loop)
        try:
            fut.result(timeout)
        finally:
            self.loop.call_soon_threadsafe(self.loop.stop)
            self.thread.join(timeout)
            self.loop.close()
