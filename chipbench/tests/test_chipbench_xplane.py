"""The trace reduction: interval arithmetic by hand, and a small trace
recorded on a TPU v5e (three runs of one jitted program, each under a
``chipbench.decode#i`` host span, inside a ``chipbench.window`` span)."""
import pytest

import _paths  # noqa: F401

import devtrace
import xplane

SMALL = _paths.BENCH / "tests" / "data" / "small.xplane.pb"


def ev(name, s, e):
    return xplane.Event(name, s, e)


def test_union_busy_and_gaps():
    ops = [ev("a", 1.0, 2.0), ev("b", 1.5, 3.0), ev("c", 5.0, 6.0),
           ev("d", 8.0, 12.0)]
    assert xplane.union([(o.start, o.end) for o in ops], 0.0, 10.0) == [
        (1.0, 3.0), (5.0, 6.0), (8.0, 10.0)]
    assert xplane.busy(ops, 0.0, 10.0) == pytest.approx(5.0)
    assert xplane.gaps(ops, 0.0, 10.0) == [(0.0, 1.0), (3.0, 5.0),
                                           (6.0, 8.0)]



def test_leaves_drop_containers():
    loop = [ev("while.3", 0.0, 5.0), ev("fusion.1", 0.5, 1.0),
            ev("kernel.2", 1.0, 4.0), ev("copy.4", 6.0, 7.0)]
    assert [e.name for e in xplane.leaves(loop)] == ["fusion.1", "kernel.2",
                                                     "copy.4"]
    assert xplane.short("%fusion.266 = bf16[16,256]{1,0} fusion(...)") == \
        "fusion.266"


def test_innermost_span_names_a_gap():
    spans = [ev("chipbench.sched.serve_step", 0.0, 10.0),
             ev("chipbench.sched.admit", 2.0, 4.0)]
    assert xplane.innermost(spans, 3.0, "-") == "chipbench.sched.admit"
    assert xplane.innermost(spans, 5.0, "-") == "chipbench.sched.serve_step"
    assert xplane.innermost(spans, 11.0, "-") == "-"


def test_steps_pair_programs_with_dispatches():
    dev = xplane.Device("/device:TPU:0",
                        ops=[ev("k", 1.2, 1.4), ev("k", 3.1, 3.3)],
                        modules=[ev("small", 1.05, 1.1), ev("big", 1.1, 1.9),
                                 ev("big", 3.0, 3.9), ev("late", 9.5, 9.9)])
    spans = [ev("chipbench.decode#0", 1.0, 1.01),
             ev("chipbench.mixed#1", 2.9, 2.95),
             ev("chipbench.decode#2", 9.4, 9.45)]
    ctx = {"trace": xplane.Trace([dev], spans), "trace_window": (0.0, 9.0),
           "recorder": type("R", (), {"dispatches": [
               {"kind": "decode", "n": 2}, {"kind": "mixed", "n": 1},
               {"kind": "decode", "n": 8}]})()}
    steps = devtrace.steps(ctx)
    assert [(s["dispatch"]["kind"], s["program"].name) for s in steps] == [
        ("decode", "big"), ("mixed", "big")]
    assert [len(s["ops"]) for s in steps] == [1, 1]


def test_recorded_trace():
    tr = xplane.load(str(SMALL))
    assert len(tr.devices) == 1
    lo, hi = xplane.window(tr, "chipbench.window")
    dev = tr.devices[0]
    busy = xplane.busy(dev.ops, lo, hi)
    assert 0 < busy < hi - lo
    idle = sum(e - s for s, e in xplane.gaps(dev.ops, lo, hi))
    assert busy + idle == pytest.approx(hi - lo)
    spans = [s for s in tr.spans if s.name.startswith("chipbench.decode#")]
    assert len(spans) == 3
    # each dispatch's program runs on the device after its host span starts
    ctx = {"trace": tr, "trace_window": (lo, hi),
           "recorder": type("R", (), {"dispatches": [
               {"kind": "decode", "n": 1}] * 3})()}
    steps = devtrace.steps(ctx)
    assert len(steps) == 3
    assert all(s["program"].start >= sp.start
               for s, sp in zip(steps, spans))
    leaf = [o for o in xplane.leaves(dev.ops) if lo <= o.start < hi]
    assert sum(o.dur for o in leaf) >= 0.5 * busy
