"""The program's own spans and counters (``runtime/tracing.py``): nothing
is evaluated while no profiler records; under a profiler a paged scheduler
run yields its round, dispatch, sync and emit spans with their stats; the
frontend's per-request counters hold on a served run."""
import glob
import os
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import ParallelConfig, SamplingConfig, get_config
from repro.kernels import decode_attention
from repro.launch.frontend import EngineService
from repro.launch.mesh import make_local_mesh
from repro.runtime import tracing
from repro.runtime.engine import Engine
from repro.runtime.scheduler import PagedContinuousScheduler


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("yi-9b").reduced()
    return Engine(cfg=cfg,
                  parallel=ParallelConfig(tp=1, dp=1, remat=False,
                                          prefill_chunk=16),
                  sampling=SamplingConfig(greedy=True, top_k=1),
                  mesh=make_local_mesh(1, 1), max_len=64)


def _submit(sched, vocab, lens=((40, 6), (8, 9), (20, 3))):
    rng = np.random.default_rng(0)
    for plen, max_new in lens:
        sched.submit(rng.integers(0, vocab, plen).astype(np.int32), max_new)


def span_events(path):
    """(name, stats) of every ``repro.*`` event in a trace, by start."""
    evs = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(tracing.PREFIX):
                    evs.append((e.start_ns, e.name, dict(e.stats)))
    return [(n, st) for _, n, st in sorted(evs, key=lambda x: x[0])]


def words(stats, key):
    """A list stat's items: the trace leaves out an empty stat and reads a
    one-number list back as a number."""
    return str(stats.get(key, "")).split()


def test_span_is_shared_noop_without_a_profiler():
    def stat():
        raise AssertionError("evaluated with no profiler recording")

    a = tracing.span("sched.round", step=1, t_mono_ns=stat)
    b = tracing.span("sched.dispatch", ctx=stat)
    assert a is b
    with a as sp:
        sp.set_metadata(built=stat)


@pytest.fixture(scope="module")
def traced(engine, tmp_path_factory):
    """A paged run of three requests under a recording profiler."""
    sched = PagedContinuousScheduler(engine, n_slots=2, block_steps=4,
                                     block_size=8)
    _submit(sched, engine.cfg.vocab_size)
    d = tmp_path_factory.mktemp("trace")
    built = tracing.builds()
    jax.profiler.start_trace(str(d))
    try:
        sched.run()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    return sched, span_events(path), tracing.builds() - built


def test_scheduler_spans_under_a_profiler(traced):
    sched, evs, built = traced
    names = {n for n, _ in evs}
    assert {"repro.sched.round", "repro.sched.retire", "repro.sched.admit",
            "repro.sched.capacity", "repro.sched.dispatch",
            "repro.sched.sync", "repro.sched.emit"} <= names
    rounds = [st for n, st in evs if n == "repro.sched.round"]
    assert [st["step"] for st in rounds] == sorted(st["step"] for st in rounds)
    assert all(st["t_mono_ns"] > 0 for st in rounds)
    disp = [st for n, st in evs if n == "repro.sched.dispatch"]
    assert {st["kind"] for st in disp} == {"mixed", "decode"}
    chunks = [c for st in disp for c in words(st, "chunks")]
    # the 40-token prompt takes three 16-token chunks
    assert chunks == ["0:16", "0:8", "16:16", "32:8", "0:16", "16:4"]
    # every token but the first of each request is decoded by some step
    live = 0
    for st in disp:
        ctx = [int(c) for c in words(st, "ctx")]
        rem = [int(r) for r in words(st, "rem")]
        assert len(ctx) == len(rem)
        assert st["live_tokens"] == sum(
            c + i for c, r in zip(ctx, rem) for i in range(min(r, st["n"])))
        live += sum(min(r, st["n"]) for r in rem)
        assert st["built"] >= 0
    assert live == (6 - 1) + (9 - 1) + (3 - 1)
    assert sum(st["built"] for st in disp) <= built
    # the CPU engine decodes attention without the paged decode kernel
    assert not any("kv_steps" in st or "kv_grid" in st for st in disp)
    admitted = sum(st["admitted"] for n, st in evs
                   if n == "repro.sched.admit")
    assert admitted == 3 == len(sched.done)


def test_request_counters_on_a_served_run(engine):
    sched = PagedContinuousScheduler(engine, n_slots=2, block_steps=4,
                                     block_size=8)
    service = EngineService(sched, idle_wait_s=0.002)

    class Stream:
        def __init__(self):
            self.done = False

        def push(self, tok):
            pass

        def finish(self, req):
            self.done = True

        def fail(self, *a, **k):
            raise AssertionError(a)

    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, engine.cfg.vocab_size, 24).tolist()
               for _ in range(4)]
    streams = [Stream() for _ in prompts]
    service.start()
    with ThreadPoolExecutor(2) as pool:
        assert list(pool.map(lambda ps: service.try_submit(
            ps[0], 5, None, ps[1]), zip(prompts, streams))) == ["ok"] * 4
    deadline = time.monotonic() + 300
    while not all(s.done for s in streams):
        assert time.monotonic() < deadline
        time.sleep(0.01)
    assert service.drain(60)
    assert len(sched.done) == 4
    for r in sched.done:
        st = r.stats
        assert st["inbox_s"] >= 0
        assert 0 <= st["prefill_s"] <= st["ttft_s"] - st["queue_s"] + 1e-6


def test_kv_walk_stats_follow_the_slots_contexts(tmp_path):
    """Where decode attention runs the paged kernel, the decode and mixed
    dispatch spans carry ``kv_steps`` (the kernel's steps per layer that
    copy and compute: each decoding slot's context in steps of P pages)
    and ``kv_grid`` (slots x the steps of a whole view), P from the
    kernel's own helper; they are evaluated only while a profiler
    records."""
    cfg = get_config("yi-9b").reduced()
    engine = Engine(cfg=cfg,
                    parallel=ParallelConfig(tp=1, dp=1, remat=False,
                                            use_pallas=True),
                    sampling=SamplingConfig(greedy=True, top_k=1),
                    mesh=make_local_mesh(1, 1), max_len=2048)
    sched = PagedContinuousScheduler(engine, n_slots=4, block_size=16)
    sched._init_caches()
    pages = decode_attention.pages_per_step(
        2048 // 16, engine.ctx.plan.local_kv, 16, cfg.resolved_head_dim, 2)
    tokens, n_s = pages * 16, -(-(2048 // 16) // pages)
    assert (tokens, n_s) == (512, 4)
    # contexts 1, 512, 513 and 2048 tokens; the third slot is done
    sched.pos = np.array([0, 511, 512, 2047], np.int32)
    sched.dones = np.array([False, False, True, False])
    sched.remaining = np.array([5, 5, 0, 1], np.int32)

    def walk_fails():
        raise AssertionError("evaluated with no profiler recording")

    sched._kv_walk = walk_fails
    sched._dispatch("decode", lambda: None, n=8)
    del sched._kv_walk

    jax.profiler.start_trace(str(tmp_path))
    try:
        for kind in ("decode", "mixed", "spec", "prefill"):
            sched._dispatch(kind, lambda: None, n=8)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[0]
    disp = {st["kind"]: st for n, st in span_events(path)
            if n == "repro.sched.dispatch"}
    assert len(disp) == 4
    for kind in ("decode", "mixed"):
        ctx = [int(c) for c in words(disp[kind], "ctx")]
        assert ctx == [1, 512, 2048]
        assert disp[kind]["kv_steps"] == sum(-(-c // tokens) for c in ctx)
        assert disp[kind]["kv_steps"] == 1 + 1 + 4
        assert disp[kind]["kv_grid"] == 4 * n_s
    for kind in ("spec", "prefill"):
        assert "kv_steps" not in disp[kind] and "kv_grid" not in disp[kind]
