"""jit'd public wrappers for the Pallas kernels.

Interpret mode follows the platform: on the CPU backend the kernel body runs
in the Pallas interpreter (the exact TPU program, executed in Python); on
any other backend the kernels lower to Mosaic and run compiled.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _da
from repro.kernels import fused_residual as _fr
from repro.kernels import topk_shard as _tk


def interpret() -> bool:
    """True when the kernels run in the Pallas interpreter (CPU backend)."""
    return jax.default_backend() == "cpu"


def topk(x: jax.Array, k: int):
    """(batch, v_local) -> (vals (batch,k) fp32, idx (batch,k) int32)."""
    return _tk.topk(x, k, interpret=interpret())


def fused_dual_matmul(a, wa, b, wb):
    """(T,Ka)@(Ka,D) + (T,Kb)@(Kb,D) accumulated in one output tile."""
    return _fr.fused_dual_matmul(a, wa, b, wb, interpret=interpret())


def decode_attention_partial(q, k, v, valid, scale):
    """Flash partials (m, l, acc) for one decode token over the cache."""
    return _da.decode_attention_partial(q, k, v, valid, float(scale),
                                        interpret=interpret())


def paged_decode_attention(q, kp, vp, bt, valid, scale):
    """Flash partials for one decode token per slot, K/V gathered block-by-
    block from the paged pool through the slot's block table."""
    return _da.paged_decode_attention_partial(q, kp, vp, bt, valid,
                                              float(scale),
                                              interpret=interpret())


def flash_prefill(q, k, v, q_pos, scale):
    """Fused causal flash-prefill: a (b, hq, Sq, hd) query block attends a
    dense K/V stripe, row i valid against kv view index j iff
    j <= q_pos[b, i].  Normalized output, one fused blockwise pass."""
    from repro.kernels import prefill_attention as _pa

    return _pa.flash_prefill(q, k, v, q_pos, float(scale),
                             interpret=interpret())


def paged_flash_prefill(q, kp, vp, bt, q_pos, scale):
    """Fused causal flash-prefill over the paged pool: K/V blocks are
    dereferenced through the slot's block table (scalar prefetch), so the
    dense per-slot view is never materialised."""
    from repro.kernels import prefill_attention as _pa

    return _pa.paged_flash_prefill(q, kp, vp, bt, q_pos, float(scale),
                                   interpret=interpret())


def flash_verify(q, k, v, q_pos, scale):
    """Narrow-q (speculative-verify / small-chunk) specialization of
    ``flash_prefill``: q tile rounded up to whole sublane groups, wider KV
    slabs — same kernel body, blocking tuned for Sq = spec_k+1.  (The
    paged kernel needs no counterpart: its KV blocking is pinned to the
    pool block size and the q-tile round-up is in the shared clamp.)"""
    from repro.kernels import prefill_attention as _pa

    return _pa.flash_verify(q, k, v, q_pos, float(scale),
                            interpret=interpret())


def lru_scan(a, b, h0):
    """RG-LRU linear-recurrence scan: h_t = a_t h_{t-1} + b_t."""
    from repro.kernels import lru_scan as _ls

    return _ls.lru_scan(a, b, h0, interpret=interpret())


def dequant_matmul(x, q, scale, *, mode, group, out_dtype=None):
    """Fused dequantize-and-matmul over packed weight-only-quantized
    weights: (T, K) @ dequant((K, N)) with the bf16 weight never
    materialised.  GEMV blocking (sublane-rounded T tile, wide N slabs)
    engages automatically for decode-narrow T; prefill/verify widths tile
    at 128."""
    from repro.kernels import wquant_matmul as _wq

    return _wq.dequant_matmul(x, q, scale, mode=mode, group=int(group),
                              out_dtype=out_dtype, interpret=interpret())
