"""Operations and bytes that the served work needs, from shapes alone.

Counts are of the algorithm, not of an implementation: attention reads the
keys and values of live tokens only, whatever block or padding a kernel
moves.  Activations and caches are bf16 (2 bytes); attention partials that a
decode kernel hands back are fp32.  The counts of a configuration are its
architecture's (``arch/<model_type>.py``, found by ``archs``); the chip's
side, ``least_time``, is here."""
from __future__ import annotations

import archs

BF16 = 2
F32 = 4


def decode_attention(cfg: dict, ctx_len: int):
    """One query token against ``ctx_len`` live tokens, one layer, all
    heads: (flops, bytes)."""
    return archs.load(cfg).decode_attention(cfg, ctx_len)


def prefill_attention(cfg: dict, start: int, clen: int):
    """A chunk of ``clen`` queries at positions start..start+clen-1 against
    their causal context, one layer, all heads: (flops, bytes)."""
    return archs.load(cfg).prefill_attention(cfg, start, clen)


def dense_params_per_layer(cfg: dict) -> int:
    """Weights of one layer, where the architecture counts them so."""
    return archs.load(cfg).dense_params_per_layer(cfg)


def token_flops(cfg: dict, ctx_len: int) -> int:
    """Model flops of one token seeing ``ctx_len`` tokens (itself
    included)."""
    return archs.load(cfg).token_flops(cfg, ctx_len)


def least_time(flops: float, nbytes: float, peaks: dict):
    """(seconds, bound) of the roofline: the larger of compute and memory
    time at the chip's peaks, and which of the two it is."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
