"""Pallas TPU kernel: RG-LRU linear-recurrence scan (Griffin's hot loop).

Computes h_t = a_t * h_{t-1} + b_t over the sequence for a (batch, seq, width)
tile, keeping the running state in VMEM registers — one HBM read of (a, b) and
one write of h, vs the log-depth associative_scan which materialises
O(log S) intermediate (b, s, w) tensors in HBM.  Width is tiled in
lane-aligned (128) blocks; the sequential loop is a kernel-internal
fori_loop (TPU scalar unit), which is exactly how the Griffin paper describes
their Pallas implementation ("linear scan", arXiv:2402.19427 §A).

Target: TPU; validated with interpret=True against ``ref.lru_scan_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat


def _lru_kernel(a_ref, b_ref, h0_ref, out_ref, hT_ref, *, seq: int):
    h = h0_ref[0].astype(jnp.float32)                    # (1, bw)

    def body(t, h):
        a_t = a_ref[0, t, :].astype(jnp.float32)         # (bw,)
        b_t = b_ref[0, t, :].astype(jnp.float32)
        h = a_t[None, :] * h + b_t[None, :]
        out_ref[0, t, :] = h[0].astype(out_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, seq, body, h)
    hT_ref[0] = h.astype(hT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def lru_scan(a: jax.Array, b: jax.Array, h0: jax.Array, *,
             block_w: int = 128, interpret: bool = True):
    """a, b: (batch, seq, width) fp32; h0: (batch, width) fp32
    -> (h (batch, seq, width), h_final (batch, width))."""
    bsz, s, w = a.shape
    bw = min(block_w, w)
    pad_w = (-w) % bw
    if pad_w:
        a = jnp.pad(a, ((0, 0), (0, 0), (0, pad_w)))
        b = jnp.pad(b, ((0, 0), (0, 0), (0, pad_w)))
        h0 = jnp.pad(h0, ((0, 0), (0, pad_w)))
    Wp = w + pad_w
    # the carried state rides as (batch, 1, width): a (1, bw) block of its
    # last two dims meets the TPU tiling rule, a one-row (batch, width)
    # block does not
    h0 = h0[:, None, :]

    hs, hT = pl.pallas_call(
        functools.partial(_lru_kernel, seq=s),
        grid=(bsz, Wp // bw),
        in_specs=[
            pl.BlockSpec((1, s, bw), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, s, bw), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, bw), lambda i, j: (i, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, s, bw), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, bw), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, Wp), jnp.float32),
            jax.ShapeDtypeStruct((bsz, 1, Wp), jnp.float32),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(a, b, h0)
    return hs[..., :w], hT[:, 0, :w]
