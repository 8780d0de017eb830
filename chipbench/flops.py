"""Operations and bytes that the served work needs, from shapes alone.

Counts are of the algorithm, not of an implementation: attention reads the
keys and values of live tokens only, whatever block or padding a kernel
moves.  Activations and caches are bf16 (2 bytes); attention partials that a
decode kernel hands back are fp32."""
from __future__ import annotations

BF16 = 2
F32 = 4


def dims(cfg: dict) -> dict:
    """Shape numbers of a configuration file (Hugging Face key names)."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return {"d": d, "h": h, "kv": kv, "hd": hd,
            "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"]}


def decode_attention(cfg: dict, ctx_len: int):
    """One query token against ``ctx_len`` live tokens, one layer, all
    heads: (flops, bytes).  QK^T and PV are 2 flops per multiply-add each."""
    m = dims(cfg)
    flops = 4 * m["h"] * m["hd"] * ctx_len
    kv_bytes = 2 * m["kv"] * m["hd"] * ctx_len * BF16
    q_o_bytes = m["h"] * m["hd"] * (BF16 + F32)
    return flops, kv_bytes + q_o_bytes


def prefill_attention(cfg: dict, start: int, clen: int):
    """A chunk of ``clen`` queries at positions start..start+clen-1 against
    their causal context, one layer, all heads: (flops, bytes)."""
    m = dims(cfg)
    keys = clen * start + clen * (clen + 1) // 2      # sum over queries
    flops = 4 * m["h"] * m["hd"] * keys
    kv_bytes = 2 * m["kv"] * m["hd"] * (start + clen) * BF16
    q_o_bytes = 2 * m["h"] * m["hd"] * clen * BF16
    return flops, kv_bytes + q_o_bytes


def dense_params_per_layer(cfg: dict) -> int:
    m = dims(cfg)
    attn = m["d"] * m["h"] * m["hd"] * 2 + m["d"] * m["kv"] * m["hd"] * 2
    return attn + 3 * m["d"] * m["f"]


def token_flops(cfg: dict, ctx_len: int) -> int:
    """Model flops of one token seeing ``ctx_len`` tokens (itself included):
    2 per weight of every layer and of the LM head, plus attention."""
    m = dims(cfg)
    weights = m["layers"] * dense_params_per_layer(cfg) + m["d"] * m["v"]
    attn = 4 * m["layers"] * m["h"] * m["hd"] * ctx_len
    return 2 * weights + attn


def least_time(flops: float, nbytes: float, peaks: dict):
    """(seconds, bound) of the roofline: the larger of compute and memory
    time at the chip's peaks, and which of the two it is."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
