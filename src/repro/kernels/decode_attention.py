"""Pallas TPU kernel: single-token GQA flash-decode over the KV cache.

The latency-critical op of the whole paper: one query token attends over a
long cache.  Grid is (batch x kv_head, cache_blocks); each step loads one
(block_s, head_dim) K/V slab into VMEM, updates running (m, l, acc) flash
statistics for the g query heads sharing that KV head, and never materialises
the (S,) score row in HBM.  Emits the PARTIAL (m, l, acc) triple rather than
the normalized output so the caller can LSE-merge across a sequence-sharded
cache (the long_500k path) — the kernel composes with the distributed
schedule instead of forcing an all-gather.

HBM traffic: one read of K/V (the roofline floor for decode attention).
Target: TPU; validated with interpret=True against ``ref.decode_attention_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat

NEG = -3.0e38


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, m_ref, l_ref, acc_ref,
                   ms_ref, ls_ref, as_ref, *, scale: float, n_s: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        ms_ref[...] = jnp.full_like(ms_ref, NEG)
        ls_ref[...] = jnp.zeros_like(ls_ref)
        as_ref[...] = jnp.zeros_like(as_ref)

    q = q_ref[...].astype(jnp.float32)                   # (g, hd)
    k = k_ref[...].astype(jnp.float32)                   # (bs, hd)
    v = v_ref[...].astype(jnp.float32)                   # (bs, hd)
    ok = valid_ref[...] != 0                             # (1, bs)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (g, bs)
    s = jnp.where(ok, s, NEG)
    m_prev = ms_ref[...][:, 0]                           # (g,)
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    # explicit zeroing: on a fully-masked block exp(NEG - NEG) would be 1
    p = jnp.where(ok, jnp.exp(s - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = ls_ref[...][:, 0] * corr + p.sum(axis=1)
    acc_new = as_ref[...] * corr[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    ms_ref[...] = m_new[:, None]
    ls_ref[...] = l_new[:, None]
    as_ref[...] = acc_new

    @pl.when(j == n_s - 1)
    def _emit():
        m_ref[...] = ms_ref[...]
        l_ref[...] = ls_ref[...]
        acc_ref[...] = as_ref[...]


@functools.partial(jax.jit, static_argnames=("scale", "block_s", "interpret"))
def decode_attention_partial(
    q: jax.Array,        # (b, hq, 1, hd)
    k: jax.Array,        # (b, hkv, S, hd)
    v: jax.Array,
    valid: jax.Array,    # (S,) bool — position mask (causal/window/emptiness)
    scale: float,
    *,
    block_s: int = 512,
    interpret: bool = True,
):
    """-> flash partials (m (b,hq,1), l (b,hq,1), acc (b,hq,1,hd)) fp32."""
    b, hq, _, hd = q.shape
    hkv, S = k.shape[1], k.shape[2]
    g = hq // hkv
    bs = min(block_s, S)
    pad_s = (-S) % bs
    if pad_s:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        valid = jnp.pad(valid, (0, pad_s))
    Sp = S + pad_s
    n_s = Sp // bs
    qg = q.reshape(b, hkv, g, hd).reshape(b * hkv, g, hd)
    kg = k.reshape(b * hkv, Sp, hd)
    vg = v.reshape(b * hkv, Sp, hd)
    # one (1, bs) row per cache block: the block's last two dims then equal
    # the array's, which the TPU tiling rule accepts at any block size
    vmask = valid.astype(jnp.int32).reshape(n_s, 1, bs)
    import jax.experimental.pallas.tpu as pltpu

    m, l, acc = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, n_s=n_s),
        grid=(b * hkv, n_s),
        in_specs=[
            pl.BlockSpec((None, g, hd), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, bs, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, bs, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, 1, bs), lambda i, j: (j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, g, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, g, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, g, hd), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b * hkv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b * hkv, g, hd), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qg, kg, vg, vmask)
    m = m.reshape(b, hq, 1)
    l = l.reshape(b, hq, 1)
    acc = acc.reshape(b, hq, 1, hd)
    # match the jnp path's -inf convention for fully-masked shards
    m = jnp.where(m <= NEG / 2, -jnp.inf, m)
    return m, l, acc


# ---------------------------------------------------------------------------
# Paged variant: gather K/V block-by-block through the slot's block table
# ---------------------------------------------------------------------------


def _paged_decode_kernel(bt_ref, q_ref, k_ref, v_ref, valid_ref,
                         m_ref, l_ref, acc_ref, ms_ref, ls_ref, as_ref,
                         *, scale: float, n_s: int):
    """Grid (b*hkv, blocks_per_slot).  The block table is a SCALAR-PREFETCH
    operand: the index map of K/V dereferences it to DMA the j-th logical
    block's physical (block_size, hd) slab — the kernel never sees a dense
    per-slot cache, so HBM traffic is one read of the blocks that actually
    hold data.  Validity is per block: slabs whose mask is entirely dead
    (unallocated / beyond the slot's length -> null block) skip the flash
    update altogether, moving position masking to block granularity."""
    del bt_ref  # consumed by the index maps
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        ms_ref[...] = jnp.full_like(ms_ref, NEG)
        ls_ref[...] = jnp.zeros_like(ls_ref)
        as_ref[...] = jnp.zeros_like(as_ref)

    ok = valid_ref[...] != 0                             # (1, bs)

    @pl.when(jnp.any(ok))
    def _update():
        q = q_ref[...].astype(jnp.float32)               # (g, hd)
        k = k_ref[...].astype(jnp.float32)               # (bs, hd)
        v = v_ref[...].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok, s, NEG)
        m_prev = ms_ref[...][:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.where(ok, jnp.exp(s - m_new[:, None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = ls_ref[...][:, 0] * corr + p.sum(axis=1)
        acc_new = as_ref[...] * corr[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        ms_ref[...] = m_new[:, None]
        ls_ref[...] = l_new[:, None]
        as_ref[...] = acc_new

    @pl.when(j == n_s - 1)
    def _emit():
        m_ref[...] = ms_ref[...]
        l_ref[...] = ls_ref[...]
        acc_ref[...] = as_ref[...]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention_partial(
    q: jax.Array,        # (b, hq, 1, hd)
    kp: jax.Array,       # (nb, hkv, block_size, hd) block pool
    vp: jax.Array,
    bt: jax.Array,       # (b, nbps) int32 block table (view index -> block)
    valid: jax.Array,    # (b, nbps*block_size) bool per-slot position mask
    scale: float,
    *,
    interpret: bool = True,
):
    """-> flash partials (m (b,hq,1), l (b,hq,1), acc (b,hq,1,hd)) fp32."""
    import jax.experimental.pallas.tpu as pltpu

    b, hq, _, hd = q.shape
    nb, hkv, bs, _ = kp.shape
    nbps = bt.shape[1]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, hd).reshape(b * hkv, g, hd)
    vmask = valid.reshape(b, nbps, 1, bs).astype(jnp.int32)  # see above

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, nbps),
        in_specs=[
            pl.BlockSpec((None, g, hd), lambda i, j, bt_ref: (i, 0, 0)),
            pl.BlockSpec((None, None, bs, hd),
                         lambda i, j, bt_ref: (bt_ref[i // hkv, j],
                                               i % hkv, 0, 0)),
            pl.BlockSpec((None, None, bs, hd),
                         lambda i, j, bt_ref: (bt_ref[i // hkv, j],
                                               i % hkv, 0, 0)),
            pl.BlockSpec((None, None, 1, bs),
                         lambda i, j, bt_ref: (i // hkv, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, g, 1), lambda i, j, bt_ref: (i, 0, 0)),
            pl.BlockSpec((None, g, 1), lambda i, j, bt_ref: (i, 0, 0)),
            pl.BlockSpec((None, g, hd), lambda i, j, bt_ref: (i, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    m, l, acc = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale, n_s=nbps),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b * hkv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b * hkv, g, hd), jnp.float32),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(bt.astype(jnp.int32), qg, kp, vp, vmask)
    m = m.reshape(b, hq, 1)
    l = l.reshape(b, hq, 1)
    acc = acc.reshape(b, hq, 1, hd)
    m = jnp.where(m <= NEG / 2, -jnp.inf, m)
    return m, l, acc
