"""Pallas TPU kernel: fused GQA flash-prefill over a causal query block.

The prefill counterpart of ``decode_attention``: a block of query tokens
attends causally over K/V with one fused blockwise-softmax pass instead of
the pure-JAX ``chunked_causal_attention`` scan.  arXiv 2407.07304 (the
sibling single-node paper) measures the fused flash prefill as the single
largest prefill win on CPUs; this kernel is the TPU/Pallas expression of the
same fusion, and serves the chunked-prefill serving path where a chunk of C
prompt tokens attends the slot's cache stripe [0, start + C).

Grid is (batch x kv_head, q_blocks, kv_blocks) with the kv dimension
innermost: each step loads one (block_k, head_dim) K/V slab into VMEM and
updates running (m, l, acc) flash statistics for the (block_q, g) query tile
sharing that KV head.  Causality is positional: query row i (absolute
position ``q_pos[i]``) attends kv view index j iff ``j <= q_pos[i]`` — on
the chunked path view index == absolute position, so no separate validity
mask is carried.  KV blocks that start beyond the tile's maximum query
position skip their flash update entirely (dead-by-causality blocks cost no
FLOPs; with chunked prefill that is every block past the chunk's end).

Two variants share the kernel body:

* dense stripe — K/V are (b, hkv, Sk, hd) contiguous stripes (fresh prompt
  K/V, or the slot engine's dense cache);
* paged — K/V live in (n_blocks, hkv, block_size, hd) pools addressed
  through a per-slot block table, dereferenced by scalar-prefetch index
  maps exactly like the paged decode kernel.

Target: TPU; validated with interpret=True against the
``chunked_causal_attention`` oracle (tests/test_chunked_prefill.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat

NEG = -3.0e38


def _flash_update(qp_ref, q_ref, k_ref, v_ref, ms_ref, ls_ref, as_ref,
                  *, scale: float, kv0, bk: int, kv_limit: int):
    """One (block_q, g) x (block_k,) flash step against running stats."""
    qpos = qp_ref[...]                                    # (bq, 1)

    @pl.when(kv0 <= jnp.max(qpos))
    def _update():
        q = q_ref[...].astype(jnp.float32)                # (bq, g, hd)
        k = k_ref[...].astype(jnp.float32)                # (bk, hd)
        v = v_ref[...].astype(jnp.float32)
        bq, g, hd = q.shape
        s = jnp.dot(q.reshape(bq * g, hd), k.T,
                    preferred_element_type=jnp.float32)
        s = (s * scale).reshape(bq, g, bk)
        kvpos = kv0 + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bk), 2)
        ok = (kvpos <= qpos[:, :, None]) & (kvpos < kv_limit)
        s = jnp.where(ok, s, NEG)
        m_prev = ms_ref[...]                              # (bq, g)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        # explicit zeroing: on a fully-masked row exp(NEG - NEG) would be 1
        p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        ls_ref[...] = ls_ref[...] * corr + p.sum(axis=-1)
        pv = jnp.dot(p.reshape(bq * g, bk), v,
                     preferred_element_type=jnp.float32).reshape(bq, g, hd)
        as_ref[...] = as_ref[...] * corr[..., None] + pv
        ms_ref[...] = m_new


def _prefill_kernel(qp_ref, q_ref, k_ref, v_ref, o_ref,
                    ms_ref, ls_ref, as_ref,
                    *, scale: float, bk: int, n_k: int, kv_limit: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        ms_ref[...] = jnp.full_like(ms_ref, NEG)
        ls_ref[...] = jnp.zeros_like(ls_ref)
        as_ref[...] = jnp.zeros_like(as_ref)

    _flash_update(qp_ref, q_ref, k_ref, v_ref, ms_ref, ls_ref, as_ref,
                  scale=scale, kv0=j * bk, bk=bk, kv_limit=kv_limit)

    @pl.when(j == n_k - 1)
    def _emit():
        l = ls_ref[...]
        o_ref[...] = (as_ref[...]
                      / jnp.maximum(l, 1e-30)[..., None]).astype(o_ref.dtype)


def _fold_q(q: jax.Array, hkv: int):
    """(b, hq, Sq, hd) -> (b*hkv, Sq, g, hd) GQA-grouped query layout."""
    b, hq, sq, hd = q.shape
    g = hq // hkv
    return (q.reshape(b, hkv, g, sq, hd)
             .transpose(0, 1, 3, 2, 4)
             .reshape(b * hkv, sq, g, hd))


def _column(q_pos: jax.Array):
    """(b, Sq) -> (b, Sq, 1) int32: a (bq, 1) block per query tile keeps the
    tile's rows on sublanes, which the TPU tiling rule accepts for any
    sublane-aligned bq."""
    return q_pos.astype(jnp.int32)[..., None]


def _unfold_o(o: jax.Array, b: int, hkv: int, sq: int):
    """(b*hkv, Sq_pad, g, hd) -> (b, hq, Sq, hd)."""
    _, sqp, g, hd = o.shape
    return (o.reshape(b, hkv, sqp, g, hd)
             .transpose(0, 1, 3, 2, 4)
             .reshape(b, hkv * g, sqp, hd)[:, :, :sq])


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_q", "block_k", "interpret"))
def flash_prefill(
    q: jax.Array,        # (b, hq, Sq, hd) — RoPE already applied
    k: jax.Array,        # (b, hkv, Sk, hd) stripe (fresh K/V or cache)
    v: jax.Array,
    q_pos: jax.Array,    # (b, Sq) int32 absolute/view position per query
    scale: float,
    *,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = True,
):
    """-> normalized attention out (b, hq, Sq, hd) in q.dtype.

    Causal against the kv VIEW index (row i attends j <= q_pos[b, i]); pad
    query rows carry q_pos = -1 and emit zeros (callers discard them)."""
    import jax.experimental.pallas.tpu as pltpu

    b, hq, sq, hd = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    g = hq // hkv
    # clamp the q tile to Sq rounded UP to a whole sublane group (multiple
    # of 8) rather than raw Sq, so narrow-q callers (flash_verify) get a
    # full-sublane tile with q_pos = -1 pad rows instead of a sliver
    bq = min(block_q, -(-sq // 8) * 8)
    bk = min(block_k, sk)
    pad_q, pad_k = (-sq) % bq, (-sk) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)), constant_values=-1)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    sqp, skp = sq + pad_q, sk + pad_k
    n_q, n_k = sqp // bq, skp // bk
    qg = _fold_q(q, hkv)
    qp = _column(q_pos)
    kg = k.reshape(b * hkv, skp, hd)
    vg = v.reshape(b * hkv, skp, hd)

    o = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, bk=bk, n_k=n_k,
                          kv_limit=sk),
        grid=(b * hkv, n_q, n_k),
        in_specs=[
            pl.BlockSpec((None, bq, 1),
                         lambda i, qi, j, hkv=hkv: (i // hkv, qi, 0)),
            pl.BlockSpec((None, bq, g, hd), lambda i, qi, j: (i, qi, 0, 0)),
            pl.BlockSpec((None, bk, hd), lambda i, qi, j: (i, j, 0)),
            pl.BlockSpec((None, bk, hd), lambda i, qi, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, g, hd),
                               lambda i, qi, j: (i, qi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, sqp, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, g), jnp.float32),
            pltpu.VMEM((bq, g), jnp.float32),
            pltpu.VMEM((bq, g, hd), jnp.float32),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, qg, kg, vg)
    return _unfold_o(o, b, hkv, sq)


@functools.partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def flash_verify(
    q: jax.Array,        # (b, hq, Sq, hd), Sq = spec_k+1 (tiny)
    k: jax.Array,        # (b, hkv, Sk, hd) cache stripe
    v: jax.Array,
    q_pos: jax.Array,    # (b, Sq) int32 view position per query
    scale: float,
    *,
    block_k: int = 512,
    interpret: bool = True,
):
    """Verify-width specialization of :func:`flash_prefill` for the
    speculative-decode verify step (Sq = spec_k+1, typically 2..9).

    Same kernel body, different blocking: the generic path would carve an
    Sq-row q tile (a sliver of a sublane group) and stream 128-wide KV
    blocks past it — one grid step per 128 cache tokens for a near-empty
    MXU tile.  Here the single q tile is rounded UP to whole sublane
    groups (multiples of 8; pad rows ride with q_pos = -1 and emit zeros)
    and the KV block widens to ``block_k``, so the q-block grid dimension
    degenerates to 1 and the whole cache streams through 4x fewer, fuller
    slabs.  Dead-beyond-causality KV blocks still skip their update, so
    blocks past the decode frontier cost no FLOPs."""
    sq = q.shape[2]
    bq = -(-sq // 8) * 8
    return flash_prefill(q, k, v, q_pos, scale, block_q=bq,
                         block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "block_q", "interpret"))
def paged_flash_prefill(
    q: jax.Array,        # (b, hq, Sq, hd)
    kp: jax.Array,       # (nb, hkv, block_size, hd) block pool
    vp: jax.Array,
    bt: jax.Array,       # (b, nbps) int32 block table (view index -> block)
    q_pos: jax.Array,    # (b, Sq) int32 view position per query
    scale: float,
    *,
    block_q: int = 128,
    interpret: bool = True,
):
    """Paged variant: K/V gathered block-by-block through the slot's block
    table via scalar-prefetch index maps (never materialises a dense view).
    -> (b, hq, Sq, hd) in q.dtype."""
    import jax.experimental.pallas.tpu as pltpu

    b, hq, sq, hd = q.shape
    nb, hkv, bs, _ = kp.shape
    nbps = bt.shape[1]
    g = hq // hkv
    bq = min(block_q, -(-sq // 8) * 8)   # same sublane-group round-up as
                                         # the dense kernel
    pad_q = (-sq) % bq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)), constant_values=-1)
    sqp = sq + pad_q
    n_q = sqp // bq
    qg = _fold_q(q, hkv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, n_q, nbps),
        in_specs=[
            pl.BlockSpec((None, bq, 1),
                         lambda i, qi, j, bt_ref: (i // hkv, qi, 0)),
            pl.BlockSpec((None, bq, g, hd),
                         lambda i, qi, j, bt_ref: (i, qi, 0, 0)),
            pl.BlockSpec((None, None, bs, hd),
                         lambda i, qi, j, bt_ref: (bt_ref[i // hkv, j],
                                                   i % hkv, 0, 0)),
            pl.BlockSpec((None, None, bs, hd),
                         lambda i, qi, j, bt_ref: (bt_ref[i // hkv, j],
                                                   i % hkv, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, g, hd),
                               lambda i, qi, j, bt_ref: (i, qi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, g), jnp.float32),
            pltpu.VMEM((bq, g), jnp.float32),
            pltpu.VMEM((bq, g, hd), jnp.float32),
        ],
    )

    def kernel(bt_ref, *args):
        del bt_ref  # consumed by the index maps
        _prefill_kernel(*args, scale=scale, bk=bs, n_k=nbps,
                        kv_limit=nbps * bs)

    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, sqp, g, hd), q.dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(bt.astype(jnp.int32), _column(q_pos), qg, kp, vp)
    return _unfold_o(o, b, hkv, sq)


# NOTE: the paged kernel needs no separate verify entry point — its KV
# blocking is pinned to the pool's block size (table entries are
# non-contiguous, one grid step per block either way) and the sublane
# round-up of narrow q tiles happens in the shared clamp above, so
# spec-decode verify widths already get the right blocking through
# paged_flash_prefill.
