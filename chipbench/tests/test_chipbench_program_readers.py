"""The readers of the program's request counters (``Request.stats``), by
hand on two requests due in the window, and on a program that keeps no
such counters: those read nothing."""
from types import SimpleNamespace

import numpy as np
import pytest

import _paths  # noqa: F401

import run


@pytest.fixture
def ctx():
    records = [{"due": 10.0, "times": [12.0], "prompt": [1, 2]},
               {"due": 20.0, "times": [27.0], "prompt": [3, 4]}]
    served = {
        (1, 2): SimpleNamespace(submitted_at=10.5, stats={
            "inbox_s": 0.4, "queue_s": 0.1, "prefill_s": 1.2,
            "ttft_s": 1.3}),
        (3, 4): SimpleNamespace(submitted_at=21.0, stats={
            "inbox_s": 0.9, "queue_s": 0.5, "prefill_s": 5.0,
            "ttft_s": 5.5})}
    return {"records": records, "all_records": records, "served": served,
            "t0": 0.0, "t1": 50.0}


def test_program_readers_by_hand(ctx):
    read = {m: run.load_reader(m).read(ctx) for m in (
        "frontend.inbox_p95_ms", "sched.prefill_p90_ms",
        "frontend.admit_p95_ms", "sched.queue_p95_ms")}
    assert read["frontend.inbox_p95_ms"] == pytest.approx(
        1e3 * float(np.percentile([0.4, 0.9], 95)))
    assert read["sched.prefill_p90_ms"] == pytest.approx(
        1e3 * float(np.percentile([1.2, 5.0], 90)))
    assert read["frontend.admit_p95_ms"] == pytest.approx(
        1e3 * float(np.percentile([0.5, 1.0], 95)))
    assert read["sched.queue_p95_ms"] == pytest.approx(
        1e3 * float(np.percentile([0.1, 0.5], 95)))
    # the inbox wait is the program's part of the admission wait
    assert read["frontend.inbox_p95_ms"] <= read["frontend.admit_p95_ms"]


def test_program_readers_without_program_counters(ctx):
    """A program that keeps no such counters reads nothing."""
    for req in ctx["served"].values():
        for key in ("inbox_s", "prefill_s"):
            del req.stats[key]
    for m in ("frontend.inbox_p95_ms", "sched.prefill_p90_ms"):
        assert run.load_reader(m).read(ctx) is None
