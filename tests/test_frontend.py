"""Async HTTP frontend e2e: the asyncio server from
``repro.launch.frontend`` running in-process over a real (reduced) engine
with the overlapped loop on — OpenAI-compatible /v1/completions in unary
and SSE-streaming form, concurrent clients, bounded-queue overload
shedding (429 + Retry-After), and graceful drain.

Clients are plain ``http.client`` calls from worker threads (the server
runs its own event loop thread), so the test exercises the exact
cross-thread handoff path production traffic takes.
"""
import asyncio
import http.client
import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager

import numpy as np
import pytest

from repro.configs import ParallelConfig, SamplingConfig, get_config
from repro.launch.frontend import EngineService, HttpFrontend
from repro.launch.mesh import make_local_mesh
from repro.runtime.engine import Engine
from repro.runtime.scheduler import ContinuousScheduler


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("yi-9b").reduced()
    return Engine(cfg=cfg,
                  parallel=ParallelConfig(tp=1, dp=1, remat=False,
                                          overlap_decode=True),
                  sampling=SamplingConfig(greedy=True, top_k=1),
                  mesh=make_local_mesh(1, 1), max_len=64)


@contextmanager
def serving(engine, n_slots=2, max_pending=8, sched=None):
    if sched is None:
        sched = ContinuousScheduler(engine, n_slots=n_slots, block_steps=2)
    service = EngineService(sched, max_pending=max_pending,
                            idle_wait_s=0.002)
    frontend = HttpFrontend(service, port=0)
    loop = asyncio.new_event_loop()

    def run():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(frontend.start())
        loop.run_forever()

    thread = threading.Thread(target=run, daemon=True)
    thread.start()
    deadline = time.monotonic() + 30
    while frontend._server is None:
        assert time.monotonic() < deadline, "server failed to start"
        time.sleep(0.01)
    try:
        yield frontend, sched
    finally:
        try:
            asyncio.run_coroutine_threadsafe(frontend.stop(),
                                             loop).result(timeout=120)
        finally:
            loop.call_soon_threadsafe(loop.stop)
            thread.join(timeout=30)
            loop.close()


def post(port, body, timeout=120):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    c.request("POST", "/v1/completions", json.dumps(body),
              {"Content-Type": "application/json"})
    r = c.getresponse()
    return r.status, dict(r.getheaders()), r.read()


def sse_tokens(raw: bytes):
    toks, finish, done = [], None, False
    for ev in raw.decode().split("\n\n"):
        if not ev.startswith("data: "):
            continue
        payload = ev[len("data: "):]
        if payload == "[DONE]":
            done = True
            continue
        choice = json.loads(payload)["choices"][0]
        toks += choice["token_ids"]
        finish = choice.get("finish_reason", finish)
    return toks, finish, done


def prompt_for(cfg, seed, n=8):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n).tolist()


def test_unary_and_stream_identical(engine):
    with serving(engine) as (fe, sched):
        body = {"prompt": prompt_for(engine.cfg, 0), "max_tokens": 6}
        st, _, data = post(fe.port, body)
        assert st == 200
        resp = json.loads(data)
        choice = resp["choices"][0]
        assert len(choice["token_ids"]) == 6
        assert choice["finish_reason"] == "length"
        assert resp["usage"]["completion_tokens"] == 6
        st, _, raw = post(fe.port, dict(body, stream=True))
        assert st == 200
        toks, finish, done = sse_tokens(raw)
        assert toks == choice["token_ids"]
        assert finish == "length" and done
        assert sched.stats["landings"] > 0    # the overlapped loop served it


def test_stop_token_finish_reason(engine):
    with serving(engine) as (fe, _):
        body = {"prompt": prompt_for(engine.cfg, 1), "max_tokens": 12}
        st, _, data = post(fe.port, body)
        toks = json.loads(data)["choices"][0]["token_ids"]
        # re-run with an EOS pinned to a token the stream actually emits
        body["stop_token_id"] = toks[2]
        st, _, data = post(fe.port, body)
        assert st == 200
        choice = json.loads(data)["choices"][0]
        assert choice["finish_reason"] == "stop"
        assert choice["token_ids"] == toks[:choice["token_ids"].__len__()]
        assert choice["token_ids"][-1] == toks[2]


def test_concurrent_streaming_clients(engine):
    with serving(engine, n_slots=2, max_pending=8) as (fe, sched):
        bodies = [{"prompt": prompt_for(engine.cfg, 10 + i),
                   "max_tokens": 5, "stream": True} for i in range(4)]
        with ThreadPoolExecutor(4) as pool:
            results = list(pool.map(lambda b: post(fe.port, b), bodies))
        for st, _, raw in results:
            assert st == 200
            toks, finish, done = sse_tokens(raw)
            assert len(toks) == 5 and finish == "length" and done
        assert len(sched.done) == 4
        # unary replay of each prompt must reproduce the streamed tokens
        for body, (_, _, raw) in zip(bodies, results):
            st, _, data = post(fe.port, {"prompt": body["prompt"],
                                         "max_tokens": 5})
            assert (json.loads(data)["choices"][0]["token_ids"]
                    == sse_tokens(raw)[0])


def test_validation_errors(engine):
    with serving(engine) as (fe, _):
        st, _, data = post(fe.port, {"prompt": [1], "max_tokens": 4})
        assert st == 400
        assert json.loads(data)["error"]["type"] == "invalid_request_error"
        st, _, _ = post(fe.port, {"max_tokens": 4})
        assert st == 400
        st, _, _ = post(fe.port, {"prompt": ["a", "b"], "max_tokens": 4})
        assert st == 400
        c = http.client.HTTPConnection("127.0.0.1", fe.port, timeout=30)
        c.request("GET", "/nope")
        assert c.getresponse().status == 404


def test_health(engine):
    with serving(engine) as (fe, _):
        c = http.client.HTTPConnection("127.0.0.1", fe.port, timeout=30)
        c.request("GET", "/health")
        r = c.getresponse()
        assert r.status == 200
        h = json.loads(r.read())
        assert h["status"] == "ok" and h["shed_requests"] == 0


def test_overload_sheds_with_429(engine):
    with serving(engine, n_slots=2, max_pending=2) as (fe, sched):
        bodies = [{"prompt": prompt_for(engine.cfg, 20 + i),
                   "max_tokens": 8} for i in range(6)]
        with ThreadPoolExecutor(6) as pool:
            results = list(pool.map(lambda b: post(fe.port, b), bodies))
        statuses = sorted(st for st, _, _ in results)
        shed = statuses.count(429)
        assert shed >= 1, "6 concurrent requests vs 2 pending: must shed"
        for st, headers, data in results:
            if st == 429:
                assert headers.get("Retry-After") == "1"
                assert json.loads(data)["error"]["type"] == "overloaded_error"
            else:
                assert st == 200
                # admitted requests are untouched by the shedding: full
                # budget, clean stream
                assert len(json.loads(data)["choices"][0]["token_ids"]) == 8
        assert sched.stats["shed_requests"] == shed
        assert len(sched.done) == 6 - shed


def test_graceful_drain(engine):
    pool = ThreadPoolExecutor(1)
    body = {"prompt": prompt_for(engine.cfg, 30), "max_tokens": 8,
            "stream": True}
    with serving(engine) as (fe, _):
        port = fe.port
        fut = pool.submit(post, port, body)
        time.sleep(0.3)           # request in flight when drain begins
    # exiting the context ran frontend.stop() while the request streamed:
    # graceful drain must have served it to completion first
    st, _, raw = fut.result(timeout=120)
    pool.shutdown()
    assert st == 200
    toks, finish, done = sse_tokens(raw)
    assert len(toks) == 8 and finish == "length" and done
    with pytest.raises(OSError):
        post(port, {"prompt": [1, 2], "max_tokens": 1}, timeout=5)


def test_engine_failure_fails_open_requests(engine):
    """A scheduler round that raises (a step the device compiler refuses,
    say) must not leave a live server over a dead engine: open requests
    get 500, /health reports the error, new work gets 503, and drain()
    re-raises the exception."""
    sched = ContinuousScheduler(engine, n_slots=2, block_steps=2)

    def serve_step():
        # idle until both requests are open, then fail the round
        if len(sched.queue) < 2:
            return False
        raise RuntimeError("step program refused")

    sched.serve_step = serve_step
    bodies = [{"prompt": prompt_for(engine.cfg, 40), "max_tokens": 4},
              {"prompt": prompt_for(engine.cfg, 41), "max_tokens": 4,
               "stream": True}]
    with pytest.raises(RuntimeError, match="step program refused"):
        with serving(engine, sched=sched) as (fe, _):
            with ThreadPoolExecutor(2) as pool:
                results = list(pool.map(lambda b: post(fe.port, b), bodies))
            for st, _, data in results:
                assert st == 500
                err = json.loads(data)["error"]
                assert err["type"] == "engine_error"
                assert "step program refused" in err["message"]
            c = http.client.HTTPConnection("127.0.0.1", fe.port, timeout=30)
            c.request("GET", "/health")
            r = c.getresponse()
            assert r.status == 503
            h = json.loads(r.read())
            assert h["status"] == "failed"
            assert "step program refused" in h["error"]
            st, _, _ = post(fe.port, bodies[0], timeout=30)
            assert st == 503
