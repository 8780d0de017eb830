"""Pallas TPU kernel: running top-k over a vocab shard (paper §2.1b hot spot).

The kernel streams the local logits row through VMEM in (block_b, block_v)
tiles, maintaining a running top-k candidate set in VMEM scratch.  Per tile it
performs k argmax-extract-mask passes over the concatenated
(running ∪ tile) candidates — k is small (<=64), the tile is MXU/VPU-aligned
(block_v multiple of 128), so the pass is VPU-bound and the HBM traffic is a
single read of the logits: exactly the memory-roofline optimum for top-k.

Target: TPU (VMEM BlockSpecs); validated on CPU via interpret=True against
``ref.topk_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat

NEG = -3.0e38  # sentinel below any real logit (fp32)


def _topk_kernel(x_ref, vals_ref, idx_ref, rv_ref, ri_ref, *, k: int, block_v: int,
                 n_vblocks: int, v_local: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        rv_ref[...] = jnp.full_like(rv_ref, NEG)
        ri_ref[...] = jnp.zeros_like(ri_ref)

    x = x_ref[...].astype(jnp.float32)                   # (bb, block_v)
    bb = x.shape[0]
    col0 = j * block_v
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    # mask out-of-range tail (vocab padded to block multiple)
    x = jnp.where(cols < v_local, x, NEG)

    # The candidates are running (bb,k) followed by tile (bb,block_v); each
    # pass extracts the first maximum of that sequence.  The two parts are
    # kept apart (no lane concatenation) and positions are found by iota
    # compares (no gather), both of which the TPU compiler lowers.
    rv, ri = rv_ref[...], ri_ref[...]
    kcol = jax.lax.broadcasted_iota(jnp.int32, (bb, k), 1)
    vcol = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    new_v = jnp.zeros((bb, k), jnp.float32)
    new_i = jnp.zeros((bb, k), jnp.int32)
    for t in range(k):                                   # unrolled: k small
        mr = jnp.max(rv, axis=1, keepdims=True)          # (bb, 1)
        mx = jnp.max(x, axis=1, keepdims=True)
        from_r = mr >= mx                                # ties: running first
        ar = jnp.min(jnp.where(rv == mr, kcol, k), axis=1, keepdims=True)
        ax = jnp.min(jnp.where(x == mx, vcol, block_v), axis=1, keepdims=True)
        hit_r = kcol == ar
        hit_x = vcol == ax
        idx_r = jnp.sum(jnp.where(hit_r, ri, 0), axis=1, keepdims=True)
        picked_v = jnp.where(from_r, mr, mx)
        picked_i = jnp.where(from_r, idx_r, col0 + ax)
        new_v = jnp.where(kcol == t, picked_v, new_v)
        new_i = jnp.where(kcol == t, picked_i, new_i)
        rv = jnp.where(from_r & hit_r, NEG, rv)
        x = jnp.where(~from_r & hit_x, NEG, x)
    rv_ref[...] = new_v
    ri_ref[...] = new_i

    @pl.when(j == n_vblocks - 1)
    def _emit():
        vals_ref[...] = new_v
        idx_ref[...] = new_i


@functools.partial(jax.jit, static_argnames=("k", "block_b", "block_v", "interpret"))
def topk(x: jax.Array, k: int, *, block_b: int = 8, block_v: int = 512,
         interpret: bool = True):
    """(batch, v_local) -> (vals (batch,k) fp32, idx (batch,k) int32)."""
    b, v = x.shape
    bb = min(block_b, b)
    bv = min(block_v, max(128, v))
    pad_b = (-b) % bb
    pad_v = (-v) % bv
    xp = jnp.pad(x, ((0, pad_b), (0, pad_v)), constant_values=NEG)
    B, V = xp.shape
    n_vblocks = V // bv
    grid = (B // bb, n_vblocks)
    import jax.experimental.pallas.tpu as pltpu

    vals, idx = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, block_v=bv, n_vblocks=n_vblocks,
                          v_local=v),
        grid=grid,
        in_specs=[pl.BlockSpec((bb, bv), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((bb, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, k), jnp.float32),
            jax.ShapeDtypeStruct((B, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bb, k), jnp.float32),
            pltpu.VMEM((bb, k), jnp.int32),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xp)
    return vals[:b], idx[:b]
