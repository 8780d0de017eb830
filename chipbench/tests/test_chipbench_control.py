"""The control at a size a test run can hold: served tokens of a tiny cell
on the CPU pass the configuration's limits, and the reference with fp8
weights (the control), read on the same sample, fails them."""
import json

import pytest

import _paths  # noqa: F401

import check
import run
import server as S
from traffic import generate as G

DATA = _paths.BENCH / "tests" / "data"
CFG = json.loads((DATA / "tiny.json").read_text())
MIX = json.loads((DATA / "tiny-chat.json").read_text())


@pytest.fixture(scope="module")
def served():
    import jax
    seed = 2**32 + 11
    mesh = S.make_mesh(CFG)
    params = S.draw_weights(CFG, seed, mesh)
    sched = S.make_scheduler(S.build_engine(CFG, params, mesh), CFG)
    S.warm_up(sched, CFG["vocab_size"])
    plan = G.generate(MIX, seed, 2.0, CFG["vocab_size"],
                      CFG["serving"]["max_len"], _paths.BENCH / "traffic")
    ctx = run.serve_window(jax, sched, plan, 2.0)
    finished = [r for r in ctx["all_records"] if run.is_ok(r)]
    sample = check.pick(finished, G.rng_for(seed),
                        CFG["correct"]["tokens_compared"])
    return params, sample


def fails(found):
    lim = CFG["correct"]
    return (found["max_logit_gap"] > lim["max_logit_gap"]
            or found["mean_logit_gap"] > lim["mean_logit_gap"])


def test_program_passes(served):
    params, sample = served
    found = check.compare(params, CFG, sample)
    assert found["tokens_compared"] >= CFG["correct"]["tokens_compared"]
    assert not fails(found), found


def test_control_fails(served):
    params, sample = served
    found = check.compare(params, CFG, sample, "fp8")
    assert fails(found), found
