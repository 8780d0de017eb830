"""Finds a configuration's architecture: the module ``arch/<model_type>.py``,
named by the configuration file's Hugging Face ``model_type``.

An architecture module gives:

- ``program_config(cfg)``: the program's ``ModelConfig``; and optionally
  ``parallel_config(cfg)``, where the default of ``server.py`` does not fit;
- ``logits(weights, cfg, tokens, at, quant=None)``: the plain float32
  reference at ``precision="highest"``, with ``quant`` the control's
  precision (``reference.round_weight``);
- ``decode_attention(cfg, ctx_len)``, ``prefill_attention(cfg, start,
  clen)`` and ``token_flops(cfg, ctx_len)``: the counts ``flops.py``
  describes;
- optionally ``STD``: standard deviations of the weight leaves it adds to
  ``weights.STD``.

The reference and the counts import nothing of the program (``repro``) at
module level; only ``program_config`` may, inside its body.  A module is
loaded by path once and kept."""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

ROOT = Path(__file__).resolve().parent / "arch"
# a configuration built by hand, with no model_type, is read as the one
# architecture configurations had before they named theirs; a configuration
# file must name its own (``run.load_cell``)
UNNAMED = "llama"


def find(model_type) -> Path:
    """The architecture file of ``model_type``; LookupError if there is
    none."""
    if not model_type:
        raise LookupError(f"no model_type, so no architecture file "
                          f"{ROOT / '<model_type>.py'}")
    path = ROOT / f"{model_type}.py"
    if not path.is_file():
        raise LookupError(f"model_type {model_type!r}, but no architecture "
                          f"file {path}")
    return path


def load(cfg: dict):
    """The architecture module of configuration ``cfg``."""
    return _load(find(cfg.get("model_type", UNNAMED)))


@functools.cache
def _load(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_arch_" + path.stem.replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
