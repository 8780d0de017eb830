"""A whole run of a tiny cell on the CPU (everything but the look for a
chip), sound and with the timed path broken underneath: ``correct`` has to
come out false for each fault a serving cell can have."""
import json
import shutil

import pytest

import _paths  # noqa: F401

import run
from repro.models import attention
from repro.runtime import engine

DATA = _paths.BENCH / "tests" / "data"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    top = tmp_path_factory.mktemp("bench")
    root = top / "chipbench"
    shutil.copytree(_paths.BENCH, root,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(DATA / "tiny-chat.json", root / "traffic" / "tiny-chat.json")
    bench = json.loads((_paths.CHECKOUT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": str(DATA / "tiny.json"), "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": "tiny.chat", "config": "tiny",
                           "traffic": "tiny-chat", "chips": 1,
                           "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (top / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run_tiny(root, seed=7, cell="tiny.chat"):
    return run.run_cell(cell, seed, 2.0, False, root=root,
                        require_chip=False, log=lambda m: None)


def test_sound_run_is_correct(root):
    res = run_tiny(root)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 18 and res["failed"] == 0
    assert {"ttft_p90_ms", "itl_p95_ms", "tpot_ms",
            "setup_s"} <= set(res["metrics"])
    assert list(res)[-1] == "checks"


def _altered(orig):
    def sample(*a, **k):
        return (orig(*a, **k) + 1) % 512
    return sample


def _half_batch(orig):
    """Every even slot gets its odd neighbour's token: half of the batch
    is left out of sampling.  Slot 0, which the scheduler fills first, is
    in the half left out, so the fault shows however few slots are busy."""
    def sample(*a, **k):
        tok = orig(*a, **k)
        return tok.at[0::2].set(tok[1::2])
    return sample


def _no_write(pool, new, bt, pos):
    return pool


@pytest.mark.parametrize("fault", ["token_altered", "half_batch",
                                   "state_unchanged"])
def test_broken_path_is_not_correct(root, monkeypatch, fault):
    if fault == "token_altered":
        monkeypatch.setattr(engine, "sample_tokens",
                            _altered(engine.sample_tokens))
    elif fault == "half_batch":
        monkeypatch.setattr(engine, "sample_tokens",
                            _half_batch(engine.sample_tokens))
    else:
        monkeypatch.setattr(attention, "_paged_write_decode", _no_write)
    res = run_tiny(root)
    assert not res["correct"], (fault, res["checks"])
