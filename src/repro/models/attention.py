"""Attention: GQA/MHA (+QKV bias, RoPE, sliding window) and MLA.

Per-shard code (runs under shard_map).  All outputs of the out-projection are
returned **unreduced** — the block assembly applies the SyncPolicy so the
collective schedule (paper §2.2) is decided in exactly one place.

KV caches carry an explicit per-slot absolute-position array, which uniformly
handles full caches, sliding-window ring buffers, and the sequence-sharded
long-context cache (cache sequence sharded over the ``data`` axis, partial
attention merged with a log-sum-exp psum — the sub-quadratic long_500k path).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import collectives as cc
from repro.core import wquant
from repro.core.sync_policy import SyncPolicy
from repro.core.zero_copy import fused_out_projection
from repro.models.common import Dist, ParamDef, ShardPlan, apply_rope

KV_CHUNK = 1024  # flash-style kv chunk for prefill
VERIFY_WIDTH = 8  # query widths at/below this take the narrow-q (verify)
                  # flash-kernel specialization on the chunk path


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------


def attn_defs(cfg: ModelConfig, plan: ShardPlan, dist: Dist) -> Dict[str, ParamDef]:
    if cfg.mla is not None:
        return _mla_defs(cfg, plan, dist)
    d, hd = cfg.d_model, cfg.resolved_head_dim
    M = dist.model_axis
    kv_sharded = plan.n_kv_p >= plan.tp
    kv_cols = (plan.n_kv_p if kv_sharded else plan.n_kv_heads) * hd
    kv_spec = P(None, M) if kv_sharded else P(None, None)
    defs = {
        "w_q": ParamDef((d, plan.n_heads_p * hd), P(None, M), init="scaled", scale_dim=0),
        "w_k": ParamDef((d, kv_cols), kv_spec, init="scaled", scale_dim=0),
        "w_v": ParamDef((d, kv_cols), kv_spec, init="scaled", scale_dim=0),
        "w_o": ParamDef((plan.n_heads_p, hd, d), P(M, None, None), init="scaled", scale_dim=1),
    }
    if cfg.qkv_bias:
        bias_spec = P(M) if kv_sharded else P(None)
        defs["b_q"] = ParamDef((plan.n_heads_p * hd,), P(M), init="zeros")
        defs["b_k"] = ParamDef((kv_cols,), bias_spec, init="zeros")
        defs["b_v"] = ParamDef((kv_cols,), bias_spec, init="zeros")
    return defs


def _mla_defs(cfg: ModelConfig, plan: ShardPlan, dist: Dist) -> Dict[str, ParamDef]:
    m, d, M = cfg.mla, cfg.d_model, dist.model_axis
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "w_dq": ParamDef((d, m.q_lora_rank), P(None, None), init="scaled", scale_dim=0),
        "q_norm": ParamDef((m.q_lora_rank,), P(None), init="zeros"),
        "w_uq": ParamDef((m.q_lora_rank, plan.n_heads_p * qd), P(None, M), init="scaled", scale_dim=0),
        "w_dkv": ParamDef((d, m.kv_lora_rank + m.qk_rope_head_dim), P(None, None), init="scaled", scale_dim=0),
        "kv_norm": ParamDef((m.kv_lora_rank,), P(None), init="zeros"),
        "w_uk": ParamDef((m.kv_lora_rank, plan.n_heads_p * m.qk_nope_head_dim), P(None, M), init="scaled", scale_dim=0),
        "w_uv": ParamDef((m.kv_lora_rank, plan.n_heads_p * m.v_head_dim), P(None, M), init="scaled", scale_dim=0),
        "w_o": ParamDef((plan.n_heads_p, m.v_head_dim, d), P(M, None, None), init="scaled", scale_dim=1),
    }


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------


def init_cache(
    cfg: ModelConfig,
    plan: ShardPlan,
    dist: Dist,
    batch_local: int,
    cache_len_local: int,
    *,
    kind: str,
    dtype=jnp.bfloat16,
    quant: bool = False,
    batched_pos: bool = False,
    paged: Optional[Tuple[int, int]] = None,
) -> Dict[str, jax.Array]:
    """Per-shard cache buffers for one layer (stacked by the scan outside).

    quant=True stores K/V as int8 with a per-(batch, head, slot) bf16 absmax
    scale — halves cache HBM residency + read traffic (beyond-paper).

    batched_pos=True gives every batch row (slot) its own position array —
    the continuous-batching engine decodes with a per-slot position vector,
    so validity masks must be trackable per row.

    paged=(n_blocks_local, block_size) swaps the dense per-slot K/V stripes
    for a global block pool addressed through per-slot block tables (see
    runtime.kvcache): memory scales with blocks actually allocated, not
    n_slots x max_seq.  Position arrays stay per-slot dense over the padded
    view length, so validity masking is unchanged."""
    if paged is not None:
        n_blocks, bs = paged
        view = -(-cache_len_local // bs) * bs
        pos = jnp.full((batch_local, view), -1, jnp.int32)
        if cfg.mla is not None:
            m = cfg.mla
            return {
                "ckv": jnp.zeros((n_blocks, bs, m.kv_lora_rank), dtype),
                "krope": jnp.zeros((n_blocks, bs, m.qk_rope_head_dim), dtype),
                "pos": pos,
            }
        hd = cfg.resolved_head_dim
        shape = (n_blocks, plan.local_kv, bs, hd)
        if quant:
            return {
                "k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:3], dtype),
                "v_scale": jnp.zeros(shape[:3], dtype),
                "pos": pos,
            }
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
                "pos": pos}
    pos_shape = (batch_local, cache_len_local) if batched_pos else (cache_len_local,)
    pos = jnp.full(pos_shape, -1, jnp.int32)
    if cfg.mla is not None:
        m = cfg.mla   # latent cache is already 10-30x smaller; no quant
        return {
            "ckv": jnp.zeros((batch_local, cache_len_local, m.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch_local, cache_len_local, m.qk_rope_head_dim), dtype),
            "pos": pos,
        }
    hd = cfg.resolved_head_dim
    shape = (batch_local, plan.local_kv, cache_len_local, hd)
    if quant:
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:3], dtype),
            "v_scale": jnp.zeros(shape[:3], dtype),
            "pos": pos,
        }
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "pos": pos,
    }


def _quantize_kv(x: jax.Array):
    """(b,h,s,hd) -> (int8 values, (b,h,s) scales)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def _dequantize_kv(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16):
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]).astype(dtype)


def cache_len_for(cfg: ModelConfig, kind: str, seq_len: int, kv_seq_shard_dp: int,
                  ring_slack: int = 0) -> int:
    """Per-shard cache length: windowed archs cap at window (plus
    ``ring_slack`` spare ring entries so a spec-decode verify writing
    ``spec_k`` draft tokens past the frontier never clobbers an in-window
    entry), seq-sharding divides over the data axis."""
    if cfg.window and kind == "local_attn":
        eff = min(seq_len, cfg.window + ring_slack)
    else:
        eff = seq_len
    if kv_seq_shard_dp > 1 and eff == seq_len:
        eff = -(-seq_len // kv_seq_shard_dp)
    return eff


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------


def _grouped_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q (b,hq,Sq,hd) x k (b,hkv,Sk,hd) -> (b,hq,Sq,Sk) fp32, GQA groups.

    Inputs stay in their storage dtype (bf16) with fp32 ACCUMULATION
    (preferred_element_type) — casting the KV cache to fp32 first would
    materialise a 2x-sized copy of the whole cache per layer (§Perf H1:
    measured 97.5 -> 43.7 GB/device on qwen2.5-32b decode_32k)."""
    b, hq, sq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, sq, hd)
    s = jnp.einsum("bkgqd,bksd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32)
    return s.reshape(b, hq, sq, k.shape[2])


def _grouped_attend(w: jax.Array, v: jax.Array) -> jax.Array:
    b, hq, sq, sk = w.shape
    hkv = v.shape[1]
    g = hq // hkv
    wg = w.reshape(b, hkv, g, sq, sk)
    out = jnp.einsum("bkgqs,bksd->bkgqd", wg.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, sq, v.shape[3])


def chunked_causal_attention(
    q: jax.Array,                 # (b, hq, Sq, hd) — RoPE already applied
    k: jax.Array,                 # (b, hkv, Sk, hd)
    v: jax.Array,
    q_positions: jax.Array,       # (Sq,) absolute positions, or (b, Sq) per-row
    kv_positions: jax.Array,      # (Sk,) absolute positions (-1 = empty slot),
                                  # or (b, Sk) per-row (ring caches: view
                                  # index != position, each row's pos stripe
                                  # names what its ring slots hold)
    window: int,                  # 0 = full causal
    scale: float,
) -> jax.Array:
    """Flash-style streaming softmax over KV chunks (pure jnp oracle path).

    Batched ``q_positions`` (b, Sq) serve the paged cached-prefix prefill:
    each row's suffix queries start at its own absolute offset while
    attending one shared KV view (view index == absolute position).
    Batched ``kv_positions`` (b, Sk) serve layouts where view index !=
    position (the sliding-window ring cache): masking follows the per-row
    position stripe instead of an implied arange."""
    b, hq, sq, hd = q.shape
    sk = k.shape[2]
    chunk = min(KV_CHUNK, sk)
    n_chunks = -(-sk // chunk)
    pad = n_chunks * chunk - sk
    batched_kv = kv_positions.ndim == 2
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kv_positions = jnp.pad(
            kv_positions,
            ((0, 0), (0, pad)) if batched_kv else (0, pad),
            constant_values=-1)
    kc = k.reshape(b, k.shape[1], n_chunks, chunk, k.shape[3]).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, v.shape[1], n_chunks, chunk, v.shape[3]).transpose(2, 0, 1, 3, 4)
    pc = (kv_positions.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
          if batched_kv else kv_positions.reshape(n_chunks, chunk))
    batched_q = q_positions.ndim == 2

    def step(carry, inputs):
        m, l, acc = carry
        k_i, v_i, p_i = inputs
        s = _grouped_scores(q, k_i) * scale                      # (b,hq,Sq,chunk)
        if batched_q or batched_kv:
            qp = (q_positions[:, :, None] if batched_q
                  else q_positions[None, :, None])               # (b|1,Sq,1)
            pkv = p_i[:, None, :] if batched_kv else p_i[None, None, :]
            valid = (pkv >= 0) & (pkv <= qp)
            if window:
                valid &= pkv > qp - window
            s = jnp.where(valid[:, None], s, -jnp.inf)
        else:
            valid = (p_i[None, :] >= 0) & (p_i[None, :] <= q_positions[:, None])
            if window:
                valid &= p_i[None, :] > q_positions[:, None] - window
            s = jnp.where(valid[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard: fully-masked rows keep m = -inf; exp(-inf - -inf) -> use where
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + _grouped_attend(p, v_i)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hq, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    acc0 = jnp.zeros((b, hq, sq, v.shape[3]), jnp.float32)  # v_dim may != hd (MLA)
    from repro.models.common import maybe_scan
    (m, l, acc), _ = maybe_scan(step, (m0, l0, acc0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def mla_latent_attention(
    qa: jax.Array,                # (b, h, Sq, rank) absorbed nope queries, bf16
    qr: jax.Array,                # (b, h, Sq, rope) RoPE'd rope queries, bf16
    kv_src: jax.Array,            # (b, Sk, rank) latent cache / fresh latents
    krope_src: jax.Array,         # (b, Sk, rope)
    q_positions: jax.Array,       # (Sq,) shared or (b, Sq) per-row
    kv_positions: jax.Array,      # (Sk,) shared or (b, Sk) per-row (-1 = empty)
    scale: float,
) -> jax.Array:
    """Streaming two-dot latent attention (MLA prefill/chunk/verify path).

    Per-chunk math mirrors the decode branch exactly — separate nope/rope
    score dots (§Perf H2: no cache-sized concat), one-pass masked softmax,
    fp32 accumulation, fp32 output (no bf16 round-trip).  For caches at or
    below KV_CHUNK entries the stream is a single chunk and the result is
    bit-identical to decode at the same state — the property the
    chunked==whole and spec==plain greedy admission identities rest on."""
    b, h, sq, _ = qa.shape
    sk = kv_src.shape[1]
    chunk = min(KV_CHUNK, sk)
    n_chunks = -(-sk // chunk)
    pad = n_chunks * chunk - sk
    batched_kv = kv_positions.ndim == 2
    if pad:
        kv_src = jnp.pad(kv_src, ((0, 0), (0, pad), (0, 0)))
        krope_src = jnp.pad(krope_src, ((0, 0), (0, pad), (0, 0)))
        kv_positions = jnp.pad(
            kv_positions,
            ((0, 0), (0, pad)) if batched_kv else (0, pad),
            constant_values=-1)
    kc = kv_src.reshape(b, n_chunks, chunk, -1).transpose(1, 0, 2, 3)
    rc = krope_src.reshape(b, n_chunks, chunk, -1).transpose(1, 0, 2, 3)
    pc = (kv_positions.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
          if batched_kv else kv_positions.reshape(n_chunks, chunk))
    qpos = q_positions if q_positions.ndim == 2 else q_positions[None, :]

    def step(carry, inputs):
        m, l, acc = carry
        k_i, r_i, p_i = inputs
        s_nope = jnp.einsum("bhsr,btr->bhst", qa, k_i,
                            preferred_element_type=jnp.float32)
        s_rope = jnp.einsum("bhse,bte->bhst", qr, r_i,
                            preferred_element_type=jnp.float32)
        sc = (s_nope + s_rope) * scale                       # (b,h,Sq,chunk)
        pkv = p_i[:, None, :] if p_i.ndim == 2 else p_i[None, None, :]
        valid = (pkv >= 0) & (pkv <= qpos[:, :, None])       # (b|1,Sq,chunk)
        sc = jnp.where(valid[:, None], sc, -jnp.inf)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(sc - m_safe[..., None])
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhst,btr->bhsr", p.astype(qa.dtype), k_i,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, kv_src.shape[-1]), jnp.float32)
    from repro.models.common import maybe_scan
    (m, l, acc), _ = maybe_scan(step, (m0, l0, acc0), (kc, rc, pc))
    return acc / jnp.maximum(l, 1e-30)[..., None]   # fp32 — decode-congruent


def banded_causal_attention(
    q: jax.Array,                 # (b, hq, S, hd) — RoPE applied
    k: jax.Array,                 # (b, hkv, S, hd)
    v: jax.Array,
    positions: jax.Array,         # (S,) absolute
    window: int,
    scale: float,
    q_chunk: int = 1024,
) -> jax.Array:
    """Sliding-window prefill in O(S·window) instead of O(S^2) (§Perf H6).

    Scans query chunks; each attends only its [pos-window, pos] KV band,
    sliced with a front-padded cache so slice bounds are static."""
    b, hq, S, hd = q.shape
    cq = min(q_chunk, S)
    if S % cq:
        return chunked_causal_attention(q, k, v, positions, positions, window, scale)
    n_q = S // cq
    band = window + cq            # covers every query in the chunk
    pad = band                    # front pad so (start >= 0) always
    kp = jnp.pad(k, ((0, 0), (0, 0), (pad, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (pad, 0), (0, 0)))
    pp = jnp.pad(positions, (pad, 0), constant_values=-1)
    qc = q.reshape(b, hq, n_q, cq, hd).transpose(2, 0, 1, 3, 4)   # (n_q,b,hq,cq,hd)
    pc = positions.reshape(n_q, cq)

    def one(i, q_i, qpos_i):
        start = pad + (i + 1) * cq - band
        k_i = jax.lax.dynamic_slice_in_dim(kp, start, band, axis=2)
        v_i = jax.lax.dynamic_slice_in_dim(vp, start, band, axis=2)
        p_i = jax.lax.dynamic_slice_in_dim(pp, start, band, axis=0)
        s = _grouped_scores(q_i, k_i) * scale                     # (b,hq,cq,band)
        ok = (p_i[None, :] >= 0) & (p_i[None, :] <= qpos_i[:, None])
        ok &= p_i[None, :] > qpos_i[:, None] - window
        s = jnp.where(ok[None, None], s, -jnp.inf)
        m = s.max(axis=-1)
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        l = p.sum(axis=-1)
        o = _grouped_attend(p, v_i) / jnp.maximum(l, 1e-30)[..., None]
        return o.astype(q.dtype)

    def body(_, inp):
        i, q_i, qp_i = inp
        return None, one(i, q_i, qp_i)

    from repro.models.common import maybe_scan
    _, out = maybe_scan(body, None,
                        (jnp.arange(n_q, dtype=jnp.int32), qc, pc))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, hq, S, hd)


def _prefill_attention(q, k, v, positions, window, scale, *,
                       use_flash: bool = False):
    """Dispatch: fused Pallas flash-prefill kernel on the hot path
    (full-causal, fresh K/V: view index == position), banded O(S*window)
    path for long windowed prefill (§Perf H6), pure-JAX chunked flash scan
    as the reference + fallback (windowed prefill, MLA)."""
    S = q.shape[2]
    if use_flash and not window and positions.ndim == 1:
        from repro.kernels import ops as kops

        qpos = jnp.broadcast_to(positions[None, :], (q.shape[0], S))
        return kops.flash_prefill(q, k, v, qpos, scale)
    if window and S >= 4 * window and S % min(1024, S) == 0:
        return banded_causal_attention(q, k, v, positions, window, scale)
    return chunked_causal_attention(q, k, v, positions, positions, window, scale)


def decode_attention_shardable(
    q: jax.Array,                 # (b, hq, 1, hd)
    k: jax.Array,                 # (b, hkv, S_local, hd) cache slice
    v: jax.Array,
    kv_positions: jax.Array,      # (S_local,) shared or (b, S_local) per-slot
    cur_pos: jax.Array,           # int32 query position: scalar or (b,) per-slot
    window: int,
    scale: float,
    dist: Dist,
    *,
    seq_axis: Optional[str] = None,   # data axis name when cache is seq-sharded
    use_pallas: bool = False,
) -> jax.Array:
    """Single-token attention over the (possibly seq-sharded) cache.

    When ``seq_axis`` is given, each shard holds a slice of the cache
    sequence; partials are merged with a log-sum-exp psum of (num, denom) —
    O(b·h·hd) bytes instead of gathering the O(S) cache.

    With per-slot positions (continuous batching) ``cur_pos`` is a (b,)
    vector and ``kv_positions`` is (b, S): every slot masks against its own
    progress, so slots at different depths decode in one program.
    """
    batched = cur_pos.ndim == 1
    if batched:
        kvp = kv_positions if kv_positions.ndim == 2 else kv_positions[None, :]
        valid = (kvp >= 0) & (kvp <= cur_pos[:, None])
        if window:
            valid &= kvp > cur_pos[:, None] - window
        vmask = valid[:, None, None, :]                          # (b,1,1,S)
    else:
        valid = (kv_positions >= 0) & (kv_positions <= cur_pos)
        if window:
            valid &= kv_positions > cur_pos - window
        vmask = valid[None, None, None, :]
    if (use_pallas and not batched and q.shape[-1] % 128 == 0
            and k.shape[2] % 128 == 0):
        from repro.kernels import ops as kops

        m, l, acc = kops.decode_attention_partial(q, k, v, valid, scale)
    else:
        s = _grouped_scores(q, k) * scale                        # (b,hq,1,S)
        s = jnp.where(vmask, s, -jnp.inf)
        m = s.max(axis=-1)                                       # (b,hq,1)
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        l = p.sum(axis=-1)
        acc = _grouped_attend(p, v)                              # (b,hq,1,hd)
    if seq_axis is not None:
        m_g = jax.lax.pmax(m, seq_axis)
        m_gs = jnp.where(jnp.isfinite(m_g), m_g, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_gs), 0.0)
        l, acc = cc.psum(
            (l * corr, acc * corr[..., None]), seq_axis, tag="lse_merge"
        )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Cache update helpers
# ---------------------------------------------------------------------------


def _write_prefill(cache_side: jax.Array, new: jax.Array, positions: jax.Array, S: int,
                   seq_axis: Optional[str] = None):
    """Write (b,h,s,hd) prefill K/V into an (b,h,S,hd) cache; keeps last S.

    With ``seq_axis`` (sequence-sharded cache) each shard takes its own slice
    of the prefill; requires s == S * axis_size."""
    new = new.astype(cache_side.dtype)
    s = new.shape[2]
    if seq_axis is not None:
        ns = jax.lax.axis_size(seq_axis)
        if s > S * ns:
            raise ValueError(f"seq-sharded prefill needs s <= S*shards ({s} > {S}*{ns})")
        if s < S * ns:  # pad; padded slots keep pos = -1 (masked, decode-writable)
            new = jnp.pad(new, ((0, 0), (0, 0), (0, S * ns - s), (0, 0)))
            positions = jnp.pad(positions, (0, S * ns - s), constant_values=-1)
        idx = jax.lax.axis_index(seq_axis)
        new = jax.lax.dynamic_slice_in_dim(new, idx * S, S, axis=2)
        pos = jax.lax.dynamic_slice_in_dim(positions, idx * S, S, axis=0)
        return jax.lax.dynamic_update_slice_in_dim(cache_side, new, 0, axis=2), pos
    if s <= S:
        out = jax.lax.dynamic_update_slice_in_dim(cache_side, new, 0, axis=2)
        pos = positions[:S] if s == S else jnp.concatenate(
            [positions, jnp.full((S - s,), -1, jnp.int32)]
        )
        return out, pos
    # window cache smaller than prefill: keep the last S tokens, ring layout
    tail = new[:, :, -S:, :]
    tail_pos = positions[-S:]
    slots = tail_pos % S
    out = cache_side.at[:, :, slots, :].set(tail)
    pos = jnp.zeros((S,), jnp.int32).at[slots].set(tail_pos)
    return out, pos


def _write_prefill_chunk(cache_side: jax.Array, new: jax.Array,
                         starts: jax.Array) -> jax.Array:
    """Scatter a (b,h,C,hd) prefill CHUNK into the dense (b,h,S,hd) slot
    cache with each row at its own view offset ``starts[b]`` — the resume
    point of chunked admission (chunk k of a prompt lands at
    [k*C, k*C + C)) and of the spec-decode verify step.  Writes past the
    cache end are DROPPED, not clamped: chunk-tail padding (and rejected
    verify drafts) on a row whose frontier reaches the last cache entry
    would otherwise race the real write at S-1 with an undefined
    duplicate-index winner.  In-range tail garbage stays dead because the
    engine's position-row rewrite marks only [0, start + len) valid."""
    b, h, C, hd = new.shape
    vpos = starts[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]   # (b,C)
    return cache_side.at[jnp.arange(b)[:, None], :, vpos, :].set(
        new.transpose(0, 2, 1, 3).astype(cache_side.dtype), mode="drop")


def _write_prefill_chunk_scale(cache_side: jax.Array, new: jax.Array,
                               starts: jax.Array) -> jax.Array:
    """Scale variant: (b,h,C) chunk into the (b,h,S) scale stripe."""
    b, h, C = new.shape
    vpos = starts[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    return cache_side.at[jnp.arange(b)[:, None], :, vpos].set(
        new.transpose(0, 2, 1).astype(cache_side.dtype), mode="drop")


def _write_prefill_chunk_ring(cache_side: jax.Array, new: jax.Array,
                              positions: jax.Array,
                              real: jax.Array) -> jax.Array:
    """Scatter a (b,h,C,hd) chunk into the (b,h,S,hd) RING cache, each token
    at its ring slot ``position % S``.  Chunk-pad columns (``real`` False)
    are dropped: unlike the dense chunk writer — whose in-range tail garbage
    stays dead behind the position row — every in-range ring index is a live
    in-window entry, so pad garbage must never land."""
    b, h, C, hd = new.shape
    S = cache_side.shape[2]
    wslot = jnp.where(real, positions % S, S)                      # S = drop
    return cache_side.at[jnp.arange(b)[:, None], :, wslot, :].set(
        new.transpose(0, 2, 1, 3).astype(cache_side.dtype), mode="drop")


def _write_prefill_chunk_ring_scale(cache_side: jax.Array, new: jax.Array,
                                    positions: jax.Array,
                                    real: jax.Array) -> jax.Array:
    """Scale variant: (b,h,C) chunk into the (b,h,S) ring scale stripe."""
    b, h, C = new.shape
    S = cache_side.shape[2]
    wslot = jnp.where(real, positions % S, S)
    return cache_side.at[jnp.arange(b)[:, None], :, wslot].set(
        new.transpose(0, 2, 1).astype(cache_side.dtype), mode="drop")


def _write_decode(cache_side: jax.Array, new: jax.Array, cur_pos: jax.Array,
                  S: int, ring: bool, seq_shard: Optional[Tuple[str, int]]):
    """Write one token (b,h,1,hd) at its slot; returns updated cache."""
    new = new.astype(cache_side.dtype)
    if ring:
        slot = cur_pos % S
        return jax.lax.dynamic_update_slice_in_dim(cache_side, new, slot, axis=2)
    if seq_shard is None:
        return jax.lax.dynamic_update_slice_in_dim(cache_side, new, cur_pos, axis=2)
    axis, S_local = seq_shard
    owner = cur_pos // S_local
    slot = cur_pos - owner * S_local
    mine = jax.lax.axis_index(axis) == owner
    updated = jax.lax.dynamic_update_slice_in_dim(cache_side, new, slot, axis=2)
    return jnp.where(mine, updated, cache_side)


def _write_pos(pos_arr: jax.Array, cur_pos: jax.Array, S: int, ring: bool,
               seq_shard: Optional[Tuple[str, int]]):
    one = cur_pos[None].astype(jnp.int32)
    if ring:
        return jax.lax.dynamic_update_slice(pos_arr, one, (cur_pos % S,))
    if seq_shard is None:
        return jax.lax.dynamic_update_slice(pos_arr, one, (cur_pos,))
    axis, S_local = seq_shard
    owner = cur_pos // S_local
    slot = cur_pos - owner * S_local
    mine = jax.lax.axis_index(axis) == owner
    updated = jax.lax.dynamic_update_slice(pos_arr, one, (slot,))
    return jnp.where(mine, updated, pos_arr)


def _slot_index(pos: jax.Array, S: int, ring: bool) -> jax.Array:
    """Per-slot write index from a (b,) position vector.

    Empty/overrun slots are clamped in range; their rows are either masked
    (pos entry -1) or already retired, so the clamped write is harmless and
    keeps the gather/scatter free of out-of-bounds semantics."""
    slot = jnp.maximum(pos, 0)
    return slot % S if ring else jnp.minimum(slot, S - 1)


def _write_decode_batched(cache_side: jax.Array, new: jax.Array,
                          pos: jax.Array, S: int, ring: bool):
    """Write one token (b,h,1,hd) with EACH row at its own slot pos[b]."""
    new = new.astype(cache_side.dtype)
    slot = _slot_index(pos, S, ring)
    b = cache_side.shape[0]
    return cache_side.at[jnp.arange(b), :, slot, :].set(new[:, :, 0, :])


def _write_pos_batched(pos_arr: jax.Array, pos: jax.Array, S: int, ring: bool):
    """pos_arr (b,S): record each row's absolute position at its own slot."""
    slot = _slot_index(pos, S, ring)
    b = pos_arr.shape[0]
    return pos_arr.at[jnp.arange(b), slot].set(pos.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Paged addressing (block pool + per-slot block tables)
#
# Pool leaves have a leading block dim instead of a batch dim; a slot's view
# of the cache is the concatenation of its table's blocks, so view index ==
# absolute position.  Out-of-range or unallocated view positions map to the
# reserved null block 0 — a write sink that is never validly read (its view
# entries carry pos = -1).  Gathering the view materialises a dense-shaped
# TRANSIENT per layer (the jnp reference path); persistent storage is the
# pool, and the Pallas decode kernel gathers block-by-block instead.
# ---------------------------------------------------------------------------


def _paged_view(pool: jax.Array, bt: jax.Array) -> jax.Array:
    """K/V pool (nb, h, bs, hd) gathered through bt (b, nbps) -> per-slot
    dense view (b, h, nbps*bs, hd); view index == absolute position."""
    b, nbps = bt.shape
    g = pool[bt].transpose(0, 2, 1, 3, 4)        # (b, h, nbps, bs, hd)
    return g.reshape(b, g.shape[1], nbps * pool.shape[2], pool.shape[3])


def _paged_view_seq(pool: jax.Array, bt: jax.Array) -> jax.Array:
    """Sequence-major pool (nb, bs, r) -> (b, nbps*bs, r) (MLA latents)."""
    b, nbps = bt.shape
    g = pool[bt]                                 # (b, nbps, bs, r)
    return g.reshape(b, nbps * pool.shape[1], pool.shape[2])


def _paged_view_scale(pool: jax.Array, bt: jax.Array) -> jax.Array:
    """Scale pool (nb, h, bs) -> (b, h, nbps*bs)."""
    b, nbps = bt.shape
    g = pool[bt].transpose(0, 2, 1, 3)           # (b, h, nbps, bs)
    return g.reshape(b, g.shape[1], nbps * pool.shape[2])


def _paged_decode_targets(bt: jax.Array, pos: jax.Array, bs: int):
    """(b,) write positions -> (physical block id, in-block offset); rows
    whose position falls outside the table (frozen/overrun slots) redirect
    to the null block."""
    nbps = bt.shape[1]
    p = jnp.maximum(pos, 0)
    vi, off = p // bs, p % bs
    phys = jnp.where(vi < nbps,
                     bt[jnp.arange(bt.shape[0]), jnp.minimum(vi, nbps - 1)], 0)
    return phys, off


def _paged_write_decode(pool: jax.Array, new: jax.Array, bt: jax.Array,
                        pos: jax.Array) -> jax.Array:
    """One token per row at its own position: pool (nb,h,bs,hd), new (b,h,1,hd)."""
    phys, off = _paged_decode_targets(bt, pos, pool.shape[2])
    return pool.at[phys, :, off, :].set(new[:, :, 0, :].astype(pool.dtype))


def _paged_write_decode_seq(pool: jax.Array, new: jax.Array, bt: jax.Array,
                            pos: jax.Array) -> jax.Array:
    """Sequence-major decode write: pool (nb,bs,r), new (b,1,r)."""
    phys, off = _paged_decode_targets(bt, pos, pool.shape[1])
    return pool.at[phys, off, :].set(new[:, 0, :].astype(pool.dtype))


def _paged_write_decode_scale(pool: jax.Array, new: jax.Array, bt: jax.Array,
                              pos: jax.Array) -> jax.Array:
    """Scale decode write: pool (nb,h,bs), new (b,h,1)."""
    phys, off = _paged_decode_targets(bt, pos, pool.shape[2])
    return pool.at[phys, :, off].set(new[:, :, 0].astype(pool.dtype))


def _paged_flat_targets(bt: jax.Array, starts: jax.Array, Lp: int, bs: int):
    """Flattened (b*Lp,) physical block ids + offsets for a prefill whose
    row b covers view positions [starts[b], starts[b]+Lp)."""
    nbps = bt.shape[1]
    vpos = starts[:, None] + jnp.arange(Lp, dtype=jnp.int32)[None, :]  # (b,Lp)
    vi, off = vpos // bs, vpos % bs
    phys = jnp.where(vi < nbps,
                     jnp.take_along_axis(bt, jnp.minimum(vi, nbps - 1), axis=1),
                     0)
    return phys.reshape(-1), off.reshape(-1)


def _paged_write_prefill(pool: jax.Array, new: jax.Array, bt: jax.Array,
                         starts: jax.Array) -> jax.Array:
    """Scatter prefill K/V (b,h,Lp,hd) into the pool at each row's own view
    offsets.  Padding tokens land in the row's private tail block or the
    null block — never in a shared (registered, hence full) prefix block."""
    b, h, Lp, hd = new.shape
    phys, off = _paged_flat_targets(bt, starts, Lp, pool.shape[2])
    flat = new.transpose(0, 2, 1, 3).reshape(b * Lp, h, hd)
    return pool.at[phys, :, off, :].set(flat.astype(pool.dtype))


def _paged_write_prefill_seq(pool: jax.Array, new: jax.Array, bt: jax.Array,
                             starts: jax.Array) -> jax.Array:
    """Sequence-major prefill scatter: pool (nb,bs,r), new (b,Lp,r)."""
    b, Lp, r = new.shape
    phys, off = _paged_flat_targets(bt, starts, Lp, pool.shape[1])
    return pool.at[phys, off, :].set(new.reshape(b * Lp, r).astype(pool.dtype))


def _paged_write_prefill_scale(pool: jax.Array, new: jax.Array, bt: jax.Array,
                               starts: jax.Array) -> jax.Array:
    """Scale prefill scatter: pool (nb,h,bs), new (b,h,Lp)."""
    b, h, Lp = new.shape
    phys, off = _paged_flat_targets(bt, starts, Lp, pool.shape[2])
    return pool.at[phys, :, off].set(
        new.transpose(0, 2, 1).reshape(b * Lp, h).astype(pool.dtype))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _slice_kv_weight(w, plan: ShardPlan, dist: Dist, hd: int):
    """Replicated (d, n_kv*hd) KV weight -> this shard's (d, local_kv*hd).

    Quantized weights slice q AND scale along the output-column dim (both
    carry the replicated spec in this layout, so the slice is local)."""
    if plan.n_kv_p >= plan.tp:
        return w  # already sharded by pjit/shard_map in_specs
    kv_head = dist.model_idx() // plan.kv_rep
    start = kv_head * plan.local_kv * hd
    if isinstance(w, wquant.QuantWeight):
        return wquant.slice_cols(w, start, plan.local_kv * hd)
    return jax.lax.dynamic_slice_in_dim(w, start, plan.local_kv * hd,
                                        axis=w.ndim - 1)


def gqa_forward(
    params: Dict[str, jax.Array],
    x: jax.Array,                 # (b, s, d) replicated over model axis
    positions: jax.Array,         # (s,) absolute
    cfg: ModelConfig,
    plan: ShardPlan,
    dist: Dist,
    *,
    kind: str,                    # "attn" | "local_attn"
    cache: Optional[Dict[str, jax.Array]] = None,
    cur_pos: Optional[jax.Array] = None,    # scalar, decode only
    kv_seq_axis: Optional[str] = None,
    use_pallas: bool = False,
    flash_prefill: bool = False,
    block_tables: Optional[jax.Array] = None,   # (b, nbps) -> paged cache
    length_mask: Optional[jax.Array] = None,    # (b, s) bool: real (non-pad)
                                                # chunk columns (ring writes)
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Returns (partial out (b,s,d) — UNREDUCED over model axis, new_cache)."""
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    window = cfg.window if kind == "local_attn" else 0
    scale = 1.0 / math.sqrt(hd)
    decode = cache is not None and s == 1
    use_flash = use_pallas and flash_prefill

    q = wquant.matmul(x, params["w_q"])
    if "b_q" in params:
        q = q + params["b_q"]
    w_k = _slice_kv_weight(params["w_k"], plan, dist, hd)
    w_v = _slice_kv_weight(params["w_v"], plan, dist, hd)
    k = wquant.matmul(x, w_k)
    v = wquant.matmul(x, w_v)
    if "b_k" in params:
        b_k = _slice_kv_weight(params["b_k"][None], plan, dist, hd)[0]
        b_v = _slice_kv_weight(params["b_v"][None], plan, dist, hd)[0]
        k, v = k + b_k, v + b_v

    q = q.reshape(b, s, plan.local_q, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, plan.local_kv, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, plan.local_kv, hd).transpose(0, 2, 1, 3)
    rope_pos = positions[None, None, :] if positions.ndim == 1 else positions[:, None, :]
    q = apply_rope(q, rope_pos, cfg.rope_theta)
    k = apply_rope(k, rope_pos, cfg.rope_theta)

    new_cache = None
    if cache is not None and block_tables is not None:
        # -- paged: scatter/gather K/V through the per-slot block table ----
        bt = block_tables
        quant = "k_scale" in cache
        if decode:
            if cur_pos.ndim != 1:
                raise ValueError("paged cache serves the slot engine only "
                                 "(per-slot decode positions)")
            S_view = cache["pos"].shape[-1]
            if quant:
                kq, ksc = _quantize_kv(k)
                vq, vsc = _quantize_kv(v)
                ck = _paged_write_decode(cache["k"], kq, bt, cur_pos)
                cv = _paged_write_decode(cache["v"], vq, bt, cur_pos)
                cks = _paged_write_decode_scale(cache["k_scale"], ksc, bt, cur_pos)
                cvs = _paged_write_decode_scale(cache["v_scale"], vsc, bt, cur_pos)
                cpos = _write_pos_batched(cache["pos"], cur_pos, S_view, False)
                new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs,
                             "pos": cpos}
                k_read = _dequantize_kv(_paged_view(ck, bt), _paged_view_scale(cks, bt))
                v_read = _dequantize_kv(_paged_view(cv, bt), _paged_view_scale(cvs, bt))
            else:
                ck = _paged_write_decode(cache["k"], k, bt, cur_pos)
                cv = _paged_write_decode(cache["v"], v, bt, cur_pos)
                cpos = _write_pos_batched(cache["pos"], cur_pos, S_view, False)
                new_cache = {"k": ck, "v": cv, "pos": cpos}
                k_read, v_read = None, None      # Pallas path gathers per block
            if not quant and use_pallas:
                from repro.kernels import ops as kops

                valid = (cpos >= 0) & (cpos <= cur_pos[:, None])
                if window:
                    valid &= cpos > cur_pos[:, None] - window
                m, l, acc = kops.paged_decode_attention(q, ck, cv, bt, valid, scale)
                out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
            else:
                if k_read is None:
                    k_read, v_read = _paged_view(ck, bt), _paged_view(cv, bt)
                out = decode_attention_shardable(
                    q, k_read, v_read, cpos, cur_pos, window, scale, dist,
                    seq_axis=None, use_pallas=False,
                )
        else:
            starts = (positions[:, 0] if positions.ndim == 2
                      else jnp.zeros((b,), jnp.int32))
            if quant:
                kq, ksc = _quantize_kv(k)
                vq, vsc = _quantize_kv(v)
                ck = _paged_write_prefill(cache["k"], kq, bt, starts)
                cv = _paged_write_prefill(cache["v"], vq, bt, starts)
                cks = _paged_write_prefill_scale(cache["k_scale"], ksc, bt, starts)
                cvs = _paged_write_prefill_scale(cache["v_scale"], vsc, bt, starts)
                new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs,
                             "pos": cache["pos"]}
            else:
                ck = _paged_write_prefill(cache["k"], k, bt, starts)
                cv = _paged_write_prefill(cache["v"], v, bt, starts)
                new_cache = {"k": ck, "v": cv, "pos": cache["pos"]}
            # pos rows are rewritten whole by the engine (set_slot_positions)
            if positions.ndim == 2:
                # cached-prefix / chunked admission: suffix or chunk queries
                # attend the slot's full view (resident blocks + just-written
                # tokens); view index == absolute position, so a plain arange
                # is the KV position vector and causality does all the
                # masking.  The Pallas path gathers block-by-block through
                # the table; the jnp path materialises the dense view.
                if not quant and use_flash and not window:
                    from repro.kernels import ops as kops

                    # narrow chunks (spec-decode verify: Sq = spec_k+1) get
                    # their sublane-rounded q tile inside the kernel; KV
                    # blocking is pinned to the pool block size either way
                    out = kops.paged_flash_prefill(q, ck, cv, bt,
                                                   positions, scale)
                else:
                    if quant:
                        k_att = _dequantize_kv(_paged_view(ck, bt), _paged_view_scale(cks, bt))
                        v_att = _dequantize_kv(_paged_view(cv, bt), _paged_view_scale(cvs, bt))
                    else:
                        k_att, v_att = _paged_view(ck, bt), _paged_view(cv, bt)
                    kv_pos = jnp.arange(k_att.shape[2], dtype=jnp.int32)
                    out = chunked_causal_attention(q, k_att, v_att, positions,
                                                   kv_pos, window, scale)
            else:
                # no shared prefix in the batch: math identical to the dense
                # slot engine (attend the fresh K/V only; int8 attends the
                # dequantized values — exactly what decode will read back)
                if quant:
                    k_att, v_att = _dequantize_kv(kq, ksc), _dequantize_kv(vq, vsc)
                else:
                    k_att, v_att = k, v
                out = _prefill_attention(q, k_att, v_att, positions, window,
                                         scale, use_flash=use_flash)
    elif cache is not None:
        S = cache["k"].shape[2]
        ring = bool(window) and kv_seq_axis is None
        quant = "k_scale" in cache
        if decode:
            batched = cur_pos.ndim == 1        # per-slot positions (cont. batching)
            if batched and kv_seq_axis is not None:
                raise ValueError("per-slot decode positions are incompatible "
                                 "with kv_seq_shard (batch=1 long-context path)")
            seq_shard = (kv_seq_axis, S) if kv_seq_axis else None
            if batched:
                wd = lambda side, new: _write_decode_batched(side, new, cur_pos, S, ring)
                wp = lambda pa: _write_pos_batched(pa, cur_pos, S, ring)
            else:
                wd = lambda side, new: _write_decode(side, new, cur_pos, S, ring, seq_shard)
                wp = lambda pa: _write_pos(pa, cur_pos, S, ring, seq_shard)
            if quant:
                kq, ksc = _quantize_kv(k)
                vq, vsc = _quantize_kv(v)
                ck = wd(cache["k"], kq)
                cv = wd(cache["v"], vq)
                cks = wd(cache["k_scale"][..., None], ksc[..., None])[..., 0]
                cvs = wd(cache["v_scale"][..., None], vsc[..., None])[..., 0]
                cpos = wp(cache["pos"])
                new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs,
                             "pos": cpos}
                k_read = _dequantize_kv(ck, cks)
                v_read = _dequantize_kv(cv, cvs)
            else:
                ck = wd(cache["k"], k)
                cv = wd(cache["v"], v)
                cpos = wp(cache["pos"])
                new_cache = {"k": ck, "v": cv, "pos": cpos}
                k_read, v_read = ck, cv
            out = decode_attention_shardable(
                q, k_read, v_read, cpos, cur_pos, window, scale, dist,
                seq_axis=kv_seq_axis, use_pallas=use_pallas,
            )
        elif positions.ndim == 2:
            # -- chunked admission (dense slot cache): scatter this chunk at
            # each row's own resume offset and attend the row's cache stripe
            # [0, start + C) — earlier chunks are read back from the cache,
            # so a chunk resumes exactly where the last one wrote.  Position
            # rows are rewritten whole by the engine (set_slot_positions);
            # causality (view index == absolute position) masks both the
            # not-yet-written tail and chunk-pad garbage.
            if kv_seq_axis is not None:
                raise ValueError("chunked prefill is incompatible with "
                                 "kv_seq_shard (batch=1 long-context path)")
            if window:
                # -- sliding-window RING chunk (view index != position).  A
                # ring has no dead tail: writing position p claims slot
                # p % S, clobbering the entry for p - S that THIS chunk's
                # earlier queries still attend.  So attend the PRE-write
                # cache — its per-row position stripe names what each ring
                # slot holds — concatenated with the fresh chunk K/V, then
                # scatter the chunk afterwards.  Post-chunk, every clobbered
                # position is >= window behind all later queries, so the
                # written ring is consistent for the next step.  The same
                # branch serves the spec-decode verify chunk (all columns
                # real; ring slack from cache_len_for keeps rejected drafts
                # from clobbering in-window entries).
                real = (length_mask.astype(bool) if length_mask is not None
                        else jnp.ones((b, s), bool))
                if quant:
                    kq, ksc = _quantize_kv(k)
                    vq, vsc = _quantize_kv(v)
                    k_old = _dequantize_kv(cache["k"], cache["k_scale"])
                    v_old = _dequantize_kv(cache["v"], cache["v_scale"])
                    k_new = _dequantize_kv(kq, ksc)
                    v_new = _dequantize_kv(vq, vsc)
                    ck = _write_prefill_chunk_ring(cache["k"], kq, positions, real)
                    cv = _write_prefill_chunk_ring(cache["v"], vq, positions, real)
                    cks = _write_prefill_chunk_ring_scale(
                        cache["k_scale"], ksc, positions, real)
                    cvs = _write_prefill_chunk_ring_scale(
                        cache["v_scale"], vsc, positions, real)
                    new_cache = {"k": ck, "v": cv, "k_scale": cks,
                                 "v_scale": cvs, "pos": cache["pos"]}
                else:
                    k_old, v_old = cache["k"], cache["v"]
                    k_new, v_new = k, v
                    ck = _write_prefill_chunk_ring(cache["k"], k, positions, real)
                    cv = _write_prefill_chunk_ring(cache["v"], v, positions, real)
                    new_cache = {"k": ck, "v": cv, "pos": cache["pos"]}
                k_att = jnp.concatenate([k_old, k_new.astype(k_old.dtype)], axis=2)
                v_att = jnp.concatenate([v_old, v_new.astype(v_old.dtype)], axis=2)
                kv_pos = jnp.concatenate(
                    [cache["pos"], jnp.where(real, positions, -1)], axis=1)
                out = chunked_causal_attention(q, k_att, v_att, positions,
                                               kv_pos, window, scale)
                partial = fused_out_projection(out, params["w_o"])
                return partial, new_cache
            starts = positions[:, 0]
            if quant:
                kq, ksc = _quantize_kv(k)
                vq, vsc = _quantize_kv(v)
                ck = _write_prefill_chunk(cache["k"], kq, starts)
                cv = _write_prefill_chunk(cache["v"], vq, starts)
                cks = _write_prefill_chunk_scale(cache["k_scale"], ksc, starts)
                cvs = _write_prefill_chunk_scale(cache["v_scale"], vsc, starts)
                new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs,
                             "pos": cache["pos"]}
                k_att = _dequantize_kv(ck, cks)
                v_att = _dequantize_kv(cv, cvs)
            else:
                ck = _write_prefill_chunk(cache["k"], k, starts)
                cv = _write_prefill_chunk(cache["v"], v, starts)
                new_cache = {"k": ck, "v": cv, "pos": cache["pos"]}
                k_att, v_att = ck, cv
            if use_flash:
                from repro.kernels import ops as kops

                if s <= VERIFY_WIDTH:   # spec-decode verify chunk
                    out = kops.flash_verify(q, k_att, v_att, positions, scale)
                else:
                    out = kops.flash_prefill(q, k_att, v_att, positions, scale)
            else:
                kv_pos = jnp.arange(S, dtype=jnp.int32)
                out = chunked_causal_attention(q, k_att, v_att, positions,
                                               kv_pos, 0, scale)
        else:
            batched_pos_cache = cache["pos"].ndim == 2
            if quant:
                kq, ksc = _quantize_kv(k)
                vq, vsc = _quantize_kv(v)
                ck, cpos = _write_prefill(cache["k"], kq, positions, S, kv_seq_axis)
                cv, _ = _write_prefill(cache["v"], vq, positions, S, kv_seq_axis)
                cks, _ = _write_prefill(cache["k_scale"][..., None],
                                        ksc[..., None], positions, S, kv_seq_axis)
                cvs, _ = _write_prefill(cache["v_scale"][..., None],
                                        vsc[..., None], positions, S, kv_seq_axis)
                if batched_pos_cache:
                    cpos = jnp.broadcast_to(cpos[None], (b, S))
                new_cache = {"k": ck, "v": cv, "k_scale": cks[..., 0],
                             "v_scale": cvs[..., 0], "pos": cpos}
            else:
                ck, cpos = _write_prefill(cache["k"], k, positions, S, kv_seq_axis)
                cv, _ = _write_prefill(cache["v"], v, positions, S, kv_seq_axis)
                if batched_pos_cache:
                    cpos = jnp.broadcast_to(cpos[None], (b, S))
                new_cache = {"k": ck, "v": cv, "pos": cpos}
            if quant:
                # attend the DEQUANTIZED values — exactly what decode reads
                # back — so prefill and decode see one consistent cache (and
                # chunked admission, which must read the cache, is
                # bit-identical to whole-prompt admission under int8)
                k_att, v_att = _dequantize_kv(kq, ksc), _dequantize_kv(vq, vsc)
            else:
                k_att, v_att = k, v
            out = _prefill_attention(q, k_att, v_att, positions, window, scale,
                                     use_flash=use_flash)
    else:
        out = _prefill_attention(q, k, v, positions, window, scale,
                                 use_flash=use_flash)

    partial = fused_out_projection(out, params["w_o"])  # zero-copy epilogue
    return partial, new_cache


def mla_forward(
    params: Dict[str, jax.Array],
    x: jax.Array,
    positions: jax.Array,
    cfg: ModelConfig,
    plan: ShardPlan,
    dist: Dist,
    *,
    cache: Optional[Dict[str, jax.Array]] = None,
    cur_pos: Optional[jax.Array] = None,
    kv_seq_axis: Optional[str] = None,
    use_pallas: bool = False,
    flash_prefill: bool = False,   # accepted for interface parity; MLA
                                   # prefill stays on the pure-JAX scan
    block_tables: Optional[jax.Array] = None,   # (b, nbps) -> paged cache
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Multi-head latent attention (DeepSeek-V2 style, absorbed matmuls).

    Cache holds only (kv_lora_rank + rope_dim) floats/token — MLA's whole
    point; it is replicated over the model axis and optionally seq-sharded
    over the data axis for long_500k.
    """
    from repro.models.common import rms_norm

    m = cfg.mla
    b, s, d = x.shape
    h = plan.local_q
    decode = cache is not None and s == 1
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)

    # --- queries ---------------------------------------------------------
    rope_pos = positions[None, None, :] if positions.ndim == 1 else positions[:, None, :]
    q_lat = rms_norm(x @ params["w_dq"], params["q_norm"], cfg.rms_eps)
    q = (q_lat @ params["w_uq"]).reshape(b, s, h, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope.transpose(0, 2, 1, 3), rope_pos,
                        cfg.rope_theta)                       # (b,h,s,rope)
    # absorb W_uk into q: (b,s,h,nope) @ (rank, h, nope) -> (b,h,s,rank)
    w_uk = params["w_uk"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
    q_abs = jnp.einsum("bshn,rhn->bhsr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))

    # --- latent kv -------------------------------------------------------
    dkv = x @ params["w_dkv"]
    ckv_new, krope_new = jnp.split(dkv, [m.kv_lora_rank], axis=-1)
    ckv_new = rms_norm(ckv_new, params["kv_norm"], cfg.rms_eps)
    krope_new = apply_rope(krope_new[:, None], rope_pos,
                           cfg.rope_theta)[:, 0]              # (b,s,rope)

    if cache is not None and block_tables is not None:
        # -- paged: sequence-major latent pools through the block table ----
        bt = block_tables
        S_view = cache["pos"].shape[-1]
        if decode:
            if cur_pos.ndim != 1:
                raise ValueError("paged cache serves the slot engine only")
            ckv = _paged_write_decode_seq(cache["ckv"], ckv_new, bt, cur_pos)
            krope = _paged_write_decode_seq(cache["krope"], krope_new, bt, cur_pos)
            cpos = _write_pos_batched(cache["pos"], cur_pos, S_view, False)
            new_cache = {"ckv": ckv, "krope": krope, "pos": cpos}
            kv_src = _paged_view_seq(ckv, bt)
            krope_src = _paged_view_seq(krope, bt)
            kv_pos = cpos
        else:
            starts = (positions[:, 0] if positions.ndim == 2
                      else jnp.zeros((b,), jnp.int32))
            ckv = _paged_write_prefill_seq(cache["ckv"], ckv_new, bt, starts)
            krope = _paged_write_prefill_seq(cache["krope"], krope_new, bt, starts)
            # pos rows rewritten whole by the engine (set_slot_positions)
            new_cache = {"ckv": ckv, "krope": krope, "pos": cache["pos"]}
            if positions.ndim == 2:   # cached-prefix admission: use the view
                kv_src = _paged_view_seq(ckv, bt)
                krope_src = _paged_view_seq(krope, bt)
                kv_pos = jnp.arange(S_view, dtype=jnp.int32)
            else:                     # fresh latents only — dense-identical
                kv_src, krope_src, kv_pos = ckv_new, krope_new, positions
    elif cache is not None:
        S = cache["ckv"].shape[1]
        if decode:
            batched = cur_pos.ndim == 1
            if batched and kv_seq_axis is not None:
                raise ValueError("per-slot decode positions are incompatible "
                                 "with kv_seq_shard (batch=1 long-context path)")
            seq_shard = (kv_seq_axis, S) if kv_seq_axis else None
            # reuse the generic writers via a dummy head axis
            if batched:
                ckv = _write_decode_batched(cache["ckv"][:, None],
                                            ckv_new[:, None], cur_pos, S, False)[:, 0]
                krope = _write_decode_batched(cache["krope"][:, None],
                                              krope_new[:, None], cur_pos, S, False)[:, 0]
                cpos = _write_pos_batched(cache["pos"], cur_pos, S, False)
            else:
                ckv = _write_decode(cache["ckv"][:, None], ckv_new[:, None], cur_pos,
                                    S, False, seq_shard)[:, 0]
                krope = _write_decode(cache["krope"][:, None], krope_new[:, None],
                                      cur_pos, S, False, seq_shard)[:, 0]
                cpos = _write_pos(cache["pos"], cur_pos, S, False, seq_shard)
        elif positions.ndim == 2:
            # -- chunked admission (dense latent cache): scatter this chunk
            # of latents at each row's own resume offset (the generic chunk
            # writer via a dummy head axis) and attend the row's cache
            # stripe [0, start + C) as MQA over the latent.  View index ==
            # absolute position in the latent cache, so a plain arange is
            # the KV position vector; causality masks the unwritten tail
            # and position rows are rewritten whole by the engine.  The
            # same branch serves the spec-decode verify chunk.
            if kv_seq_axis is not None:
                raise ValueError("chunked prefill is incompatible with "
                                 "kv_seq_shard (batch=1 long-context path)")
            starts = positions[:, 0]
            ckv = _write_prefill_chunk(cache["ckv"][:, None],
                                       ckv_new[:, None], starts)[:, 0]
            krope = _write_prefill_chunk(cache["krope"][:, None],
                                         krope_new[:, None], starts)[:, 0]
            new_cache = {"ckv": ckv, "krope": krope, "pos": cache["pos"]}
            kv_src, krope_src = ckv, krope
            kv_pos = jnp.arange(S, dtype=jnp.int32)
        else:
            ckv, cpos = _write_prefill(cache["ckv"][:, None], ckv_new[:, None],
                                       positions, S, kv_seq_axis)
            ckv = ckv[:, 0]
            krope, _ = _write_prefill(cache["krope"][:, None], krope_new[:, None],
                                      positions, S, kv_seq_axis)
            krope = krope[:, 0]
            if cache["pos"].ndim == 2:
                cpos = jnp.broadcast_to(cpos[None], (b, S))
        if positions.ndim != 2 or decode:
            new_cache = {"ckv": ckv, "krope": krope, "pos": cpos}
        if decode:
            kv_src, krope_src, kv_pos = ckv, krope, cpos
        elif positions.ndim != 2:
            # whole prefill attends over the full freshly-computed latents
            kv_src, krope_src, kv_pos = ckv_new, krope_new, positions
    else:
        new_cache = None
        kv_src, krope_src = ckv_new, krope_new
        kv_pos = positions

    if decode:
        # §Perf H2: two-dot scores (nope·ckv + rope·krope) instead of
        # concat([ckv, krope]) — the concat materialised a cache-sized copy
        # per layer per decode step. fp32 accumulation, bf16 operands.
        qa = q_abs.astype(x.dtype)                                  # (b,h,1,r)
        qr = q_rope.astype(x.dtype)                                 # (b,h,1,e)
        s_nope = jnp.einsum("bhsr,btr->bhst", qa, kv_src,
                            preferred_element_type=jnp.float32)
        s_rope = jnp.einsum("bhse,bte->bhst", qr, krope_src,
                            preferred_element_type=jnp.float32)
        sc = (s_nope + s_rope) * scale                              # (b,h,1,t)
        if cur_pos.ndim == 1:                  # per-slot positions: (b,S) mask
            kvp = kv_pos if kv_pos.ndim == 2 else kv_pos[None, :]
            valid = (kvp >= 0) & (kvp <= cur_pos[:, None])
            sc = jnp.where(valid[:, None, None, :], sc, -jnp.inf)
        else:
            valid = (kv_pos >= 0) & (kv_pos <= cur_pos)
            sc = jnp.where(valid[None, None, None, :], sc, -jnp.inf)
        mx = sc.max(axis=-1)
        mx_safe = jnp.where(jnp.isfinite(mx), mx, 0.0)
        p = jnp.exp(sc - mx_safe[..., None])
        l = p.sum(axis=-1)
        acc = jnp.einsum("bhst,btr->bhsr", p.astype(x.dtype), kv_src,
                         preferred_element_type=jnp.float32)
        if kv_seq_axis is not None:
            m_g = jax.lax.pmax(mx, kv_seq_axis)
            m_gs = jnp.where(jnp.isfinite(m_g), m_g, 0.0)
            corr = jnp.where(jnp.isfinite(mx), jnp.exp(mx - m_gs), 0.0)
            l, acc = cc.psum((l * corr, acc * corr[..., None]), kv_seq_axis,
                             tag="lse_merge")
        o_lat = acc / jnp.maximum(l, 1e-30)[..., None]
    else:
        # prefill / chunked admission / spec verify: the SAME two-dot latent
        # math as decode, streamed over KV chunks (fp32 o_lat, no bf16
        # round-trip through a concat MQA view).  Congruent numerics across
        # decode/prefill/chunk are what make the chunked==whole and
        # spec==plain greedy identities hold bitwise for MLA.
        o_lat = mla_latent_attention(
            q_abs.astype(x.dtype), q_rope.astype(x.dtype),
            kv_src, krope_src, positions, kv_pos, scale)
    # value up-projection (absorbed): (b,h,s,rank) @ (rank,h,vd) -> (b,h,s,vd)
    w_uv = params["w_uv"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    o = jnp.einsum("bhsr,rhv->bhsv", o_lat, w_uv.astype(jnp.float32)).astype(x.dtype)
    partial = fused_out_projection(o, params["w_o"])
    return partial, new_cache
