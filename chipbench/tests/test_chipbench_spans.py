"""The program's own spans beside the benchmark's run-time wrappers: on a
tiny paged run under the profiler, with ``server.Recorder`` attached, every
record of ``Recorder.dispatches`` is rebuilt from the stats of its
``repro.sched.dispatch`` span, and ``xplane.load`` reads those spans."""
import glob
import json
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import _paths  # noqa: F401

import server as S
import xplane

CFG = json.loads((_paths.BENCH / "tests" / "data" / "tiny.json").read_text())


def words(stats, key):
    """A list stat's items: the trace leaves out an empty stat and reads a
    one-number list back as a number."""
    return str(stats.get(key, "")).split()


def rebuilt(stats):
    """The ``Recorder.dispatches`` record of one dispatch span."""
    n = stats["n"]
    ctx = [int(c) for c in words(stats, "ctx")]
    rem = [int(r) for r in words(stats, "rem")]
    chunks = [tuple(int(x) for x in c.split(":"))
              for c in words(stats, "chunks")]
    return {"kind": stats["kind"], "n": n,
            "decode_ctx": [[c + i for c, r in zip(ctx, rem) if r > i]
                           for i in range(n)],
            "chunks": chunks}


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    mesh = S.make_mesh(CFG)
    eng = S.build_engine(CFG, S.draw_weights(CFG, 2**33 + 7, mesh), mesh)
    sched = S.make_scheduler(eng, CFG)
    rec = S.Recorder()
    rec.wrap_engine(eng)
    S.Recorder.wrap_scheduler(sched)
    rng = np.random.default_rng(3)
    for plen, max_new in ((90, 12), (20, 30), (40, 5), (8, 9), (70, 20)):
        sched.submit(rng.integers(0, CFG["vocab_size"], plen)
                     .astype(np.int32), max_new)
    d = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(d))
    try:
        sched.run()
    finally:
        jax.profiler.stop_trace()
    path = xplane.find(str(d))
    evs = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    evs.append((e.start_ns, e.name, dict(e.stats)))
    evs.sort(key=lambda e: e[0])
    return rec, path, evs


def test_recorder_dispatches_rebuilt_from_spans(run):
    rec, _, evs = run
    spans = [st for _, name, st in evs if name == "repro.sched.dispatch"]
    assert len(rec.dispatches) > 4
    assert {d["kind"] for d in rec.dispatches} == {"mixed", "decode"}
    assert [rebuilt(st) for st in spans] == rec.dispatches


def test_xplane_reads_the_program_spans(run):
    _, path, evs = run
    spans = xplane.load(path, span_prefix="repro.").spans
    assert [s.name for s in spans] == [name for _, name, _ in evs]
    assert [s.start for s in spans] == pytest.approx(
        [t * 1e-9 for t, _, _ in evs], abs=1e-9)
    assert {"repro.sched.round", "repro.sched.dispatch", "repro.sched.sync",
            "repro.sched.emit"} <= {s.name for s in spans}
    # the benchmark's own spans are not among them
    assert not [s for s in spans if s.name.startswith("chipbench.")]
