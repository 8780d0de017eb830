"""Pallas TPU kernels: fused dequantize-and-matmul over packed weights.

The decode-side weight sweep is the single largest bandwidth consumer once
the KV cache is quantized (arXiv 2407.07304 §weight-only quantization); the
fused kernel reads the PACKED low-precision weight stream straight from HBM
and dequantizes per tile inside VMEM, so the bf16 weight never exists in
memory — the whole point of weight-only quantization on a bandwidth-bound
decode.

Two weight formats share the grid shape (T tiles, N tiles, K steps):

* **int8, per-output-channel scales** — the scale depends only on the
  output column, so it commutes with the K reduction: the kernel
  accumulates ``x @ q`` in fp32 across K steps and applies the (1, bn)
  scale row once at emit — one multiply per output element instead of one
  per weight element.
* **int4, group-wise scales** — two values per byte, one scale per
  ``group``-length K segment.  The K block is pinned to the group length,
  so each grid step unpacks one (group/2, bn) byte slab into a (group, bn)
  fp32 tile, scales it with its own (1, bn) scale row, and accumulates.

GEMV vs GEMM is a blocking choice, not a separate kernel (the same move
``flash_verify`` makes on the attention side): decode calls come in with
T = batch (a handful of rows) — the T tile rounds up to whole sublane
groups (multiples of 8, zero-padded rows) and the N block widens so the
weight streams through fewer, fuller slabs; prefill/verify calls tile T at
128.  ``dequant_matmul`` picks the blocking from T.

Target: TPU; validated with interpret=True against
``ref.dequant_matmul_ref`` (tests/test_wquant.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat
from repro.core.wquant import unpack4


def _dq8_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    kdx = pl.program_id(2)

    @pl.when(kdx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32),
                            q_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(kdx == n_k - 1)
    def _emit():
        s = s_ref[...].astype(jnp.float32)           # (1, bn)
        o_ref[...] = (acc_ref[...] * s).astype(o_ref.dtype)


def _dq4_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    kdx = pl.program_id(2)

    @pl.when(kdx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # one packed (g//2, bn) byte slab -> (g, bn) values; the nibble
    # convention lives in ONE place (wquant.unpack4 — plain jnp ops, so it
    # traces inside the kernel body too)
    w = unpack4(q_ref[...]).astype(jnp.float32)
    w = w * s_ref[...].astype(jnp.float32)           # (g, bn) * (1, bn)
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(kdx == n_k - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "mode", "group", "out_dtype", "block_t", "block_n", "block_k",
    "interpret"))
def dequant_matmul(
    x: jax.Array,        # (T, K) activations (bf16); K is the PER-SHARD
                         # reduction length under shard_map — always derived
                         # from x.shape, never from QuantWeight's global aux
    q: jax.Array,        # int8 (K, N) | uint8 (K//2, N) packed int4
    scale: jax.Array,    # bf16 (N,) int8 | (K//group, N) int4
    *,
    mode: str,
    group: int,
    out_dtype=None,
    block_t: int = 128,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool = True,
) -> jax.Array:
    """-> (T, N) = x @ dequant(q, scale), fp32 accumulation, fused dequant.

    Decode-narrow x (T <= 16) takes the GEMV blocking automatically: the T
    tile rounds up to whole sublane groups and N widens to a single block
    when it fits, so the packed weight streams once through full slabs."""
    import jax.experimental.pallas.tpu as pltpu

    T, K = x.shape
    N = q.shape[-1]
    out_dtype = out_dtype or x.dtype
    gemv = T <= 16
    bt = -(-T // 8) * 8 if gemv else min(block_t, -(-T // 8) * 8)
    bn = min(block_n if not gemv else max(block_n, 512), N)
    if mode == "int4":
        bk = group                                   # one scale row per step
    else:
        bk = min(block_k, K)
    pad_t, pad_n, pad_k = (-T) % bt, (-N) % bn, (-K) % bk
    if pad_t or pad_k:
        x = jnp.pad(x, ((0, pad_t), (0, pad_k)))
    Tp, Np, Kp = T + pad_t, N + pad_n, K + pad_k
    n_k = Kp // bk
    if mode == "int4":
        if pad_k:
            raise ValueError("int4 K must be a multiple of the group")
        qp = jnp.pad(q, ((0, 0), (0, pad_n))) if pad_n else q
        sp = jnp.pad(scale, ((0, 0), (0, pad_n))) if pad_n else scale
        # one (1, bn) scale row per group step: as the last two dims of a
        # (K/g, 1, N) array the block meets the TPU tiling rule, where a
        # one-row block of the (K/g, N) array does not
        sp = sp[:, None, :]
        kernel = functools.partial(_dq4_kernel, n_k=n_k)
        q_spec = pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j))
        s_spec = pl.BlockSpec((None, 1, bn), lambda i, j, kk: (kk, 0, j))
    else:
        qp = jnp.pad(q, ((0, pad_k), (0, pad_n)))
        sp = jnp.pad(scale[None, :], ((0, 0), (0, pad_n)))
        kernel = functools.partial(_dq8_kernel, n_k=n_k)
        q_spec = pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))
        s_spec = pl.BlockSpec((1, bn), lambda i, j, kk: (0, j))

    out = pl.pallas_call(
        kernel,
        grid=(Tp // bt, Np // bn, n_k),
        in_specs=[
            pl.BlockSpec((bt, bk), lambda i, j, kk: (i, kk)),
            q_spec,
            s_spec,
        ],
        out_specs=pl.BlockSpec((bt, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Tp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bt, bn), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, qp, sp)
    return out[:T, :N]
