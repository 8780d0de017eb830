"""Llama-style dense decoders (``model_type`` "llama", as Yi's; Qwen2's
decoder has the same structure): token embedding, then per layer RMSNorm,
grouped-query attention with rotary positions (half-split rotation, as in
the Llama and Qwen2 code) and optional q/k/v biases, a residual add,
RMSNorm, a SwiGLU MLP and a residual add; then a final RMSNorm and the
untied LM head over the full vocabulary.

The reference runs one sequence at a time, layer by layer, with every
matrix product at ``precision="highest"``.  It reads the weight tree that
``weights.draw`` makes, in the program's layout: ``embed.table`` (1, V, d);
``groups`` of layers, each ``subN`` holding ``norm1``, ``norm2`` (offsets:
the scale is 1 + w), ``mixer.w_q`` (d, H*hd), ``w_k``/``w_v`` (d, KV*hd),
``w_o`` (H, hd, d), optional ``b_q/b_k/b_v``, and ``ffn.w_up``/``w_gate``
(d, f), ``w_down`` (f, d), with a leading layer axis where a group stacks
layers; ``final_norm``; ``lm_head`` (1, d, V).  Query head i reads
key/value head i // (H / KV).  ``quant`` rounds every projection weight
(not the embedding, norms or biases) to the control's precision
(``reference.round_weight``) before the float32 math.

The counts are of the algorithm (``flops.py``).  Nothing of the program is
imported here but the config class, inside ``program_config``."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from flops import BF16, F32
from reference import round_weight

Q_BLOCK = 512
BUCKET = 512


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs import ModelConfig

    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg.get("head_dim", 0),
        qkv_bias=bool(cfg.get("qkv_bias", cfg.get("attention_bias", False))),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]), act="silu", gated_mlp=True)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w.astype(jnp.float32))


def _rope(x, theta):
    """x (T, heads, hd) at positions 0..T-1."""
    t, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs       # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("h", "kv", "eps", "theta", "quant"))
def _layer(x, p, *, h, kv, eps, theta, quant):
    t, d = x.shape
    hd = p["w_q"].shape[-1] // h
    a = _rms(x, p["norm1"], eps)
    q = a @ round_weight(p["w_q"], quant)
    k = a @ round_weight(p["w_k"], quant)
    v = a @ round_weight(p["w_v"], quant)
    if "b_q" in p:
        q = q + p["b_q"].astype(jnp.float32)
        k = k + p["b_k"].astype(jnp.float32)
        v = v + p["b_v"].astype(jnp.float32)
    q = _rope(q.reshape(t, h, hd), theta)
    k = _rope(k.reshape(t, kv, hd), theta)
    v = v.reshape(t, kv, hd)
    g = h // kv
    k = jnp.repeat(k, g, axis=1)                 # head i reads kv head i // g
    v = jnp.repeat(v, g, axis=1)
    outs = []
    for lo in range(0, t, Q_BLOCK):              # query blocks bound memory
        qb = q[lo:lo + Q_BLOCK]
        s = jnp.einsum("qhe,khe->hqk", qb, k) / np.sqrt(hd)
        causal = (jnp.arange(lo, lo + qb.shape[0])[:, None]
                  >= jnp.arange(t)[None, :])
        s = jnp.where(causal[None], s, -jnp.inf)
        outs.append(jnp.einsum("hqk,khe->qhe", jax.nn.softmax(s, -1), v))
    o = jnp.concatenate(outs, 0)
    x = x + jnp.einsum("qhe,hed->qd", o, round_weight(p["w_o"], quant))
    m = _rms(x, p["norm2"], eps)
    up = m @ round_weight(p["w_up"], quant)
    gate = m @ round_weight(p["w_gate"], quant)
    return x + (jax.nn.silu(gate) * up) @ round_weight(p["w_down"], quant)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, norm, head, *, eps, quant):
    return _rms(x, norm, eps) @ round_weight(head, quant)


def _layers(weights):
    """Per-layer parameter dicts, in order, from the (stacked) groups."""
    for group in weights["groups"]:
        for key in sorted(group, key=lambda s: int(s[3:])):
            sub = group[key]
            flat = {"norm1": sub["norm1"], "norm2": sub["norm2"],
                    **sub["mixer"], **sub["ffn"]}
            if flat["norm1"].ndim == 1:
                yield flat
            else:
                for i in range(flat["norm1"].shape[0]):
                    yield {k: v[i] for k, v in flat.items()}


def logits(weights, cfg: dict, tokens, at, quant=None) -> np.ndarray:
    """float32 logits (len(at), V) of the next token after each position in
    ``at`` of the sequence ``tokens``."""
    t = len(tokens)
    tp = -(-t // BUCKET) * BUCKET        # pad at the end: causal, harmless
    ids = np.zeros(tp, np.int32)
    ids[:t] = tokens
    kw = dict(h=cfg["num_attention_heads"], kv=cfg["num_key_value_heads"],
              eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
              quant=quant)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"]["table"][0][jnp.asarray(ids)].astype(jnp.float32)
        for p in _layers(weights):
            x = _layer(x, p, **kw)
        x = x[jnp.asarray(np.asarray(at, np.int32))]
        out = _head(x, weights["final_norm"], weights["lm_head"][0],
                    eps=kw["eps"], quant=quant)
    return np.asarray(out)[:, :cfg["vocab_size"]]


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
