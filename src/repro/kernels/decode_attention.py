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
# Paged variant: a slot's live runs of pages, all KV heads at once
# ---------------------------------------------------------------------------

STEP_TOKENS = 512        # tokens one step aims to attend
KV_VMEM_BYTES = 8 << 20  # K and V step buffers, double-buffered, at most


def pages_per_step(nbps: int, hkv: int, bs: int, hd: int,
                   itemsize: int) -> int:
    """Pages one step of the paged kernel copies and attends: about
    ``STEP_TOKENS`` tokens, no more than the slot's ``nbps`` pages, and few
    enough that K and V, double-buffered, fit ``KV_VMEM_BYTES`` (a page is
    all ``hkv`` heads: ``hkv * bs * hd * itemsize`` bytes)."""
    page = hkv * bs * hd * itemsize
    return max(1, min(nbps, STEP_TOKENS // bs, KV_VMEM_BYTES // (4 * page)))


def _live_pages(valid: jax.Array, bs: int) -> jax.Array:
    """(b, S) position mask -> flat int32 (3b,): per slot the live page
    range [lo, hi) (pages holding a valid position; (0, 0) when none) and
    the next slot after it with a live range (b when none)."""
    b = valid.shape[0]
    page = valid.reshape(b, -1, bs).any(axis=-1)           # (b, nbps)
    nbps = page.shape[1]
    has = page.any(axis=1)
    lo = jnp.where(has, jnp.argmax(page, axis=1), 0)
    hi = jnp.where(has, nbps - jnp.argmax(page[:, ::-1], axis=1), 0)
    idx = jnp.where(has, jnp.arange(b), b)
    nxt = jnp.append(jax.lax.cummin(idx, reverse=True)[1:], b)
    return jnp.stack([lo, hi, nxt], axis=1).reshape(-1).astype(jnp.int32)


def _paged_decode_kernel(bt_ref, live_ref, q_ref, valid_ref, kp_ref, vp_ref,
                         m_ref, l_ref, acc_ref, k_buf, v_buf, sems, state,
                         *, scale: float, pages: int, nbps: int):
    """Grid (b,): grid step i loops over slot i's live steps only; step j
    attends pages [j*P, (j+1)*P) of the slot's view, all KV heads at once.
    The pool stays in HBM (``pl.ANY``); the kernel copies a step's live
    pages itself through the block table (scalar prefetch) into one of two
    VMEM buffers, and starts the next live step's copies (the same slot's
    next step, else the next slot's first) before it waits on its own.
    The live steps are those that hold the slot's live page range [lo, hi)
    (``live_ref``); inside them the per-position mask still applies, so
    holes and partial pages stay exact.  ``state`` (SMEM) holds the buffer
    of the current step and whether a copy has been started yet."""
    import jax.experimental.pallas.tpu as pltpu

    i = pl.program_id(0)
    b = pl.num_programs(0)
    hkv = m_ref.shape[0]

    def page_range(slot):
        return live_ref[3 * slot], live_ref[3 * slot + 1]

    def step_range(slot):
        lo, hi = page_range(slot)
        return lo // pages, (hi + pages - 1) // pages

    def copies(slot, step, buf):
        """(live, K copy, V copy) per page of (slot, step) into ``buf``."""
        lo, hi = page_range(slot)
        out = []
        for p in range(pages):
            page = step * pages + p
            blk = bt_ref[slot * nbps + page]
            out.append(((page >= lo) & (page < hi),
                        pltpu.make_async_copy(kp_ref.at[blk], k_buf.at[buf, p],
                                              sems.at[0, buf]),
                        pltpu.make_async_copy(vp_ref.at[blk], v_buf.at[buf, p],
                                              sems.at[1, buf])))
        return out

    def start(slot, step, buf):
        for live, ck, cv in copies(slot, step, buf):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(slot, step, buf):
        for p, (live, ck, cv) in enumerate(copies(slot, step, buf)):
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

            # a page not copied holds stale VMEM; its positions are masked,
            # but p @ v would still carry a NaN there into the sum
            @pl.when(jnp.logical_not(live))
            def _():
                v_buf[buf, p] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)

    @pl.when(i == 0)
    def _first():
        state[1] = 0

    m_ref[...] = jnp.full_like(m_ref, NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    s_lo, s_hi = step_range(i)

    def step(j, carry):
        @pl.when(state[1] == 0)          # the grid's first live step
        def _():
            start(i, j, 0)
            state[0] = 0
            state[1] = 1

        buf = state[0]
        last = j + 1 >= s_hi
        ni = jnp.where(last, live_ref[3 * i + 2], i)
        nj = jnp.where(last, step_range(jnp.minimum(ni, b - 1))[0], j + 1)

        @pl.when(ni < b)
        def _():
            start(ni, nj, 1 - buf)
            state[0] = 1 - buf

        wait(i, j, buf)
        ok = valid_ref[j] != 0                            # (1, P*bs)
        n_tok = ok.shape[1]
        dt = jnp.promote_types(q_ref.dtype, k_buf.dtype)
        for h in range(hkv):
            q = q_ref[h].astype(dt)                       # (g, hd)
            k = k_buf[buf, :, h].astype(dt).reshape(n_tok, -1)
            v = v_buf[buf, :, h].astype(jnp.float32).reshape(n_tok, -1)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (g, P*bs)
            s = jnp.where(ok, s, NEG)
            m_prev = m_ref[h]                             # (g, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            # explicit zeroing: on a fully-masked row exp(NEG - NEG) is 1
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        return carry

    jax.lax.fori_loop(s_lo, s_hi, step, 0)


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
    P = pages_per_step(nbps, hkv, bs, hd, kp.dtype.itemsize)
    n_s = -(-nbps // P)
    pad = n_s * P - nbps                 # dead pages: the null block, masked
    live = _live_pages(valid, bs)
    bt = jnp.pad(bt.astype(jnp.int32), ((0, 0), (0, pad))).reshape(-1)
    vmask = jnp.pad(valid, ((0, 0), (0, pad * bs))).astype(jnp.int32)
    vmask = vmask.reshape(b, n_s, 1, P * bs)   # see the dense kernel
    qg = q.reshape(b, hkv, g, hd)

    per_slot = lambda i, bt_ref, live_ref: (i, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, hkv, g, hd), per_slot),
            pl.BlockSpec((None, n_s, 1, P * bs), per_slot),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((None, hkv, g, 1), per_slot),
            pl.BlockSpec((None, hkv, g, 1), per_slot),
            pl.BlockSpec((None, hkv, g, hd), per_slot),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, P, hkv, bs, hd), kp.dtype),
            pltpu.VMEM((2, P, hkv, bs, hd), vp.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    m, l, acc = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale, pages=P,
                          nbps=n_s * P),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g, hd), jnp.float32),
        ],
        # the copies of one step are started by the step before it, across
        # slots too, so the grid runs in order
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(bt, live, qg, vmask, kp, vp)
    m = m.reshape(b, hq, 1)
    l = l.reshape(b, hq, 1)
    acc = acc.reshape(b, hq, 1, hd)
    m = jnp.where(m <= NEG / 2, -jnp.inf, m)
    return m, l, acc
