"""The one traffic generator: reads a mix file ``traffic/<name>.json`` and
draws a run's requests.

A mix names an arrival kind and a length kind for prompts and for outputs;
each kind is a module ``traffic/kinds/<kind>.py`` found by its name.  Its
``lead_s`` (0 if absent) is how long arrivals run, untimed, before the
window opens, so that the window finds the server in its steady state; its
``grace_s`` (``GRACE_S`` if absent) is how long past the window's end the
requests still running are served before their deadline retires them.

The shape of the traffic (arrivals, then prompt lengths, then output
lengths) is drawn from ``ORDER_SEED``, not from ``--seed``: every run of a
mix offers the same lengths at the same times, so runs on different seeds
do the same work.  Which request waits behind which moves time to first
token by more than a fifth, so a permutation per seed would make the seed
change the work.  ``--seed`` draws the prompt tokens (and, elsewhere, the
weights)."""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import List

import numpy as np

HERE = Path(__file__).resolve().parent
ORDER_SEED = 0
GRACE_S = 30.0


@dataclass
class Request:
    prompt: List[int]
    max_tokens: int
    due_s: float                       # offset from the window's start


@dataclass
class Plan:
    requests: List[Request]
    lead_s: float = 0.0
    grace_s: float = GRACE_S
    mix: dict = field(default_factory=dict)


def load_kind(name: str, root: Path = HERE):
    path = root / "kinds" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic kind {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"chipbench_kind_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_mix(name: str, root: Path = HERE) -> dict:
    path = root / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


def rng_for(seed: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(int(seed)))


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             max_len: int, root: Path = HERE) -> Plan:
    """Requests due from ``-lead_s`` to ``seconds`` after the window's
    start; the arrival kind spreads them over that whole span."""
    lead = float(mix.get("lead_s", 0.0))
    rng = rng_for(ORDER_SEED)
    due = np.asarray(load_kind(mix["arrivals"]["kind"], root).schedule(
        mix["arrivals"], lead + seconds, rng)) - lead
    n = len(due)
    plens = load_kind(mix["prompt_len"]["kind"], root).draw(
        mix["prompt_len"], n, rng)
    outs = load_kind(mix["output_len"]["kind"], root).draw(
        mix["output_len"], n, rng)
    if int((plens + outs).max()) > max_len:
        raise ValueError(f"mix needs {int((plens + outs).max())} positions "
                         f"> max_len {max_len}")
    rng = rng_for(seed)
    reqs = [Request(rng.integers(0, vocab, int(p)).tolist(), int(o),
                    float(d)) for p, o, d in zip(plens, outs, due)]
    return Plan(reqs, lead, float(mix.get("grace_s", GRACE_S)), mix)
