"""The traffic generator, and how the harness finds mixes, kinds and
metric readers by name."""
import json
import shutil

import numpy as np
import pytest

import _paths  # noqa: F401

import run
from traffic import generate as G

CHAT = G.load_mix("chat")


def _plan(mix, seed, seconds=20.0):
    return G.generate(mix, seed, seconds, 64000, 4096)


def test_same_seed_same_requests():
    a, b = _plan(CHAT, 2**31 + 7), _plan(CHAT, 2**31 + 7)
    assert [(r.prompt, r.max_tokens, r.due_s) for r in a.requests] == \
           [(r.prompt, r.max_tokens, r.due_s) for r in b.requests]
    c = _plan(CHAT, 2**31 + 8)
    assert [r.prompt for r in a.requests] != [r.prompt for r in c.requests]


@pytest.mark.parametrize("lead", [0.0, 60.0], ids=["no_lead", "lead"])
def test_every_seed_gets_the_same_work(lead):
    mix = dict(CHAT, lead_s=lead)

    def shape(plan):
        return [(len(r.prompt), r.max_tokens, r.due_s) for r in plan.requests]
    a, b = _plan(mix, 1), _plan(mix, 99)
    assert shape(a) == shape(b)
    assert [r.prompt for r in a.requests] != [r.prompt for r in b.requests]


def test_open_loop_due_times():
    seconds = 30.0
    plan = _plan(CHAT, 5, seconds)
    due = np.array([r.due_s for r in plan.requests])
    rate = CHAT["arrivals"]["rate_per_s"]
    lead = CHAT["lead_s"]
    assert plan.lead_s == lead and plan.grace_s == CHAT["grace_s"]
    assert len(due) == round(rate * (lead + seconds))
    assert due[0] == -lead and np.all(np.diff(due) > 0) and due[-1] < seconds
    lens = [len(r.prompt) for r in plan.requests]
    lo, hi = CHAT["prompt_len"]["min"], CHAT["prompt_len"]["max"]
    assert min(lens) >= lo and max(lens) <= hi


def test_lead_in_keeps_the_rate():
    """The lead-in adds arrivals before the window at the mix's rate; the
    window itself holds about rate x seconds of them."""
    mix = dict(CHAT, arrivals=dict(CHAT["arrivals"], rate_per_s=2.0),
               lead_s=100.0)
    plan = _plan(mix, 3, 50.0)
    due = np.array([r.due_s for r in plan.requests])
    assert len(due) == 300
    assert abs(int((due >= 0).sum()) - 100) <= 10
    assert G.generate(dict(mix, lead_s=0.0), 3, 50.0, 64000,
                      4096).requests[0].due_s == 0.0


def test_mix_that_overflows_the_cache_is_refused():
    with pytest.raises(ValueError, match="max_len"):
        G.generate(CHAT, 1, 10.0, 64000, 1024)


def test_added_files_are_found_by_name(tmp_path):
    """A mix, a length kind and a metric reader added as files only."""
    root = tmp_path / "chipbench"
    shutil.copytree(_paths.BENCH, root,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "traffic" / "kinds" / "fixed.py").write_text(
        "import numpy as np\n"
        "def draw(params, n, rng):\n"
        "    return np.full(n, params['value'])\n")
    mix = {"arrivals": {"kind": "poisson", "rate_per_s": 2.0},
           "prompt_len": {"kind": "fixed", "value": 33},
           "output_len": {"kind": "fixed", "value": 7}}
    (root / "traffic" / "dummy.json").write_text(json.dumps(mix))
    (root / "metrics" / "dummy.count.py").write_text(
        "def read(ctx):\n    return len(ctx['records'])\n")
    bench = json.loads((_paths.CHECKOUT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "yi-9b-24L.dummy",
                               "config": "yi-9b-24L", "traffic": "dummy",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "dummy.count", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "x", "moves": "tpot_ms",
                               "workloads": ["yi-9b-24L.dummy"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(_paths.BENCH / "configs", root / "configs",
                    dirs_exist_ok=True)
    cell = run.load_cell("yi-9b-24L.dummy", True, root)
    assert cell.cfg["name"] == "yi-9b-24L"
    plan = G.generate(cell.mix, 4, 5.0, 64000, 4096, root / "traffic")
    assert {(len(r.prompt), r.max_tokens) for r in plan.requests} == {(33, 7)}
    names = [m["name"] for m in cell.metrics]
    assert "dummy.count" in names
    ctx = {"records": [1, 2, 3]}
    assert run.read_metrics(
        run.Cell(cell.name, 1, cell.cfg, cell.mix, root,
                 [m for m in cell.metrics if m["name"] == "dummy.count"]),
        ctx) == {"dummy.count": {"value": 3.0, "unit": "1"}}
