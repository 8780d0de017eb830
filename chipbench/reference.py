"""Plain float32 reference of the dense decoder the configurations describe:
token embedding, then per layer RMSNorm, grouped-query attention with
rotary positions (half-split rotation, as in the Llama and Qwen2 code) and
optional q/k/v biases, a residual add, RMSNorm, a SwiGLU MLP and a residual
add; then a final RMSNorm and the untied LM head over the full vocabulary.

It runs one sequence at a time, layer by layer, with every matrix product
at ``precision="highest"``.  It imports nothing of the served program.  It
reads the weight tree that ``weights.draw`` makes, in the program's layout:
``embed.table`` (1, V, d); ``groups`` of layers, each ``subN`` holding
``norm1``, ``norm2`` (offsets: the scale is 1 + w), ``mixer.w_q`` (d, H*hd),
``w_k``/``w_v`` (d, KV*hd), ``w_o`` (H, hd, d), optional ``b_q/b_k/b_v``,
and ``ffn.w_up``/``w_gate`` (d, f), ``w_down`` (f, d), with a leading
layer axis where a group stacks layers; ``final_norm``; ``lm_head``
(1, d, V).  Query head i reads key/value head i // (H / KV).

``quant`` gives the control: every projection weight (not the embedding,
norms or biases) rounded to int8 with one scale per output channel, or to
fp8 (e4m3) with one scale per output channel, before the float32 math."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
BUCKET = 512


def _round(w, quant):
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
    q = a @ _round(p["w_q"], quant)
    k = a @ _round(p["w_k"], quant)
    v = a @ _round(p["w_v"], quant)
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
    x = x + jnp.einsum("qhe,hed->qd", o, _round(p["w_o"], quant))
    m = _rms(x, p["norm2"], eps)
    up = m @ _round(p["w_up"], quant)
    gate = m @ _round(p["w_gate"], quant)
    return x + (jax.nn.silu(gate) * up) @ _round(p["w_down"], quant)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, norm, head, *, eps, quant):
    return _rms(x, norm, eps) @ _round(head, quant)


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
