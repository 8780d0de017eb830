"""Plain float32 reference of the model a configuration describes: its
architecture's ``logits`` (``arch/<model_type>.py``, found by ``archs``),
which imports nothing of the served program and runs every matrix product
at ``precision="highest"``.

``quant`` gives the control: every projection weight (not the embedding,
norms or biases) rounded by ``round_weight`` before the float32 math, to
int8 or to fp8 (e4m3), with one scale per output channel.  Every
architecture rounds its projections through this one function, so the
control means the same in every cell."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

import archs


def round_weight(w, quant):
    """Projection weight (..., in, out) in the control's precision."""
    w = w.astype(jnp.float32)
    if quant is None:
        return w
    axes = (-3, -2) if w.ndim == 3 else (-2,)
    amax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
    if quant == "int8":
        scale = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    if quant == "fp8":
        scale = jnp.maximum(amax, 1e-30) / 448.0
        return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"unknown control precision {quant!r}")


def logits(weights, cfg: dict, tokens, at, quant=None) -> np.ndarray:
    """float32 logits (len(at), V) of the next token after each position in
    ``at`` of the sequence ``tokens``."""
    return archs.load(cfg).logits(weights, cfg, tokens, at, quant)
