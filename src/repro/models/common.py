"""Shared model building blocks: param machinery, norms, RoPE, activations.

Everything in ``repro.models`` is written as *per-shard* code intended to run
inside ``jax.shard_map`` over the mesh axes in :class:`Dist`.  Collectives are
explicit ``jax.lax`` calls (see :mod:`repro.core.collectives`), which is what
makes the paper's communication schedule a countable property of the program.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig

Pytree = Any


# ---------------------------------------------------------------------------
# Distribution context
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Dist:
    """Names + sizes of the mesh axes the per-shard code runs under."""

    model_axis: str = "model"
    data_axis: str = "data"
    pod_axis: Optional[str] = None
    tp: int = 1
    dp: int = 1
    pods: int = 1

    @property
    def data_axes(self) -> Tuple[str, ...]:
        """Axes over which the batch is sharded (pod is outer data parallel)."""
        if self.pod_axis is not None:
            return (self.pod_axis, self.data_axis)
        return (self.data_axis,)

    def model_idx(self):
        return jax.lax.axis_index(self.model_axis)


def pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass(frozen=True)
class ShardPlan:
    """How attention heads / vocab / experts land on the ``model`` axis.

    Q heads are padded to a multiple of tp (zero-initialised padding heads are
    exact no-ops under the row-parallel out-projection + psum).  When
    n_kv < tp each KV head is replicated over ``rep = tp // n_kv`` adjacent
    shards and the per-KV-group Q heads are padded to a multiple of ``rep``.
    """

    tp: int
    n_heads: int            # true head count
    n_kv_heads: int         # true kv head count
    n_heads_p: int          # padded q heads (multiple of tp)
    n_kv_p: int             # padded kv heads
    kv_rep: int             # how many model shards share one kv head
    local_q: int            # q heads per shard
    local_kv: int           # kv heads per shard
    vocab_p: int            # padded vocab (multiple of tp)
    local_vocab: int

    @staticmethod
    def make(cfg: ModelConfig, tp: int) -> "ShardPlan":
        n_q, n_kv = cfg.n_heads, cfg.n_kv_heads
        if n_kv >= tp:
            # shard kv heads directly; pad both q and kv to multiples of tp
            n_kv_p = pad_to(n_kv, tp)
            g = max(1, n_q // n_kv)
            if n_q % n_kv:
                raise ValueError(f"{cfg.name}: n_heads {n_q} not a multiple of n_kv {n_kv}")
            n_q_p = n_kv_p * g
            kv_rep = 1
        else:
            if tp % n_kv:
                raise ValueError(f"{cfg.name}: tp {tp} not a multiple of n_kv {n_kv}")
            kv_rep = tp // n_kv
            g = n_q // n_kv
            if n_q % n_kv:
                raise ValueError(f"{cfg.name}: ragged GQA groups unsupported")
            g_p = pad_to(g, kv_rep)
            n_q_p = n_kv * g_p
            n_kv_p = n_kv
        vocab_p = pad_to(cfg.vocab_size, tp)
        return ShardPlan(
            tp=tp,
            n_heads=n_q,
            n_kv_heads=n_kv,
            n_heads_p=n_q_p,
            n_kv_p=n_kv_p,
            kv_rep=kv_rep,
            local_q=n_q_p // tp,
            local_kv=max(1, n_kv_p // tp),
            vocab_p=vocab_p,
            local_vocab=vocab_p // tp,
        )


# ---------------------------------------------------------------------------
# Parameter definition machinery
# ---------------------------------------------------------------------------


@dataclass
class ParamDef:
    """Declarative parameter: global shape + partition spec + initializer."""

    shape: Tuple[int, ...]
    spec: P
    init: str = "normal"        # normal | zeros | ones | scaled
    scale_dim: int = -1         # fan-in dim index for "scaled"
    dtype: Any = jnp.bfloat16

    def initialize(self, key) -> jax.Array:
        if self.init == "zeros":
            return jnp.zeros(self.shape, self.dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, self.dtype)
        fan_in = self.shape[self.scale_dim] if self.init == "scaled" else None
        std = 0.02 if fan_in is None else 1.0 / math.sqrt(fan_in)
        # reduce_precision to fp32's own width changes no value; it keeps
        # XLA from folding ``std`` into the draw's own sqrt(2) factor inside
        # a fused program, which would move the rounding of the product
        draw = jax.lax.reduce_precision(
            jax.random.normal(key, self.shape, jnp.float32), 8, 23)
        return (draw * std).astype(self.dtype)


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def materialize(defs: Pytree, key, mesh=None) -> Pytree:
    """Draw every leaf of ``defs`` from ``key`` in one jitted program.

    With a ``mesh``, each leaf is constrained to ``NamedSharding(mesh,
    spec)`` inside the program, so every device draws only its own shard (the
    threefry PRNG is partitionable: values do not depend on the sharding)
    and no leaf passes through one device whole.  Inside the program the
    fp32 draw, scale and cast fuse, so no fp32 copy of a leaf is kept."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_def)
    sig = tuple((d.shape, d.init, d.scale_dim, jnp.dtype(d.dtype).name)
                for d in leaves)
    shardings = (None if mesh is None
                 else tuple(NamedSharding(mesh, d.spec) for d in leaves))
    return jax.tree.unflatten(treedef, _draw_leaves(key, sig, shardings))


@partial(jax.jit, static_argnums=(1, 2))
def _draw_leaves(key, sig, shardings):
    keys = jax.random.split(key, len(sig))
    out = [ParamDef(shape, P(), init, scale_dim, dtype).initialize(k)
           for (shape, init, scale_dim, dtype), k in zip(sig, keys)]
    if shardings is not None:
        out = [jax.lax.with_sharding_constraint(x, s)
               for x, s in zip(out, shardings)]
    return out


def specs_of(defs: Pytree) -> Pytree:
    return jax.tree.map(lambda d: d.spec, defs, is_leaf=is_def)


def shapes_of(defs: Pytree) -> Pytree:
    """ShapeDtypeStructs with shardings attached — used by the dry-run."""
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), defs, is_leaf=is_def
    )


def stack_defs(defs: Pytree, n: int) -> Pytree:
    """Stack a layer's defs ``n`` times along a new leading (scan) axis."""

    def s(d: ParamDef) -> ParamDef:
        return ParamDef(
            shape=(n,) + d.shape,
            spec=P(None, *d.spec),
            init=d.init,
            scale_dim=d.scale_dim if d.scale_dim < 0 else d.scale_dim + 1,
            dtype=d.dtype,
        )

    return jax.tree.map(s, defs, is_leaf=is_def)


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + gamma.astype(jnp.float32))).astype(x.dtype)


def activation(name: str) -> Callable[[jax.Array], jax.Array]:
    return {"silu": jax.nn.silu, "gelu": partial(jax.nn.gelu, approximate=True)}[name]


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, head_dim); positions: (..., seq) int32."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)                     # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def conv_tail(ext: jax.Array, W: int, valid_len: Optional[jax.Array],
              tail: jax.Array) -> jax.Array:
    """Carried depthwise-conv history from the extended input ``ext``
    (b, s+W-1, ch) = [old tail ; new inputs].

    ``valid_len`` (b,) ends each row's tail at its own last REAL input
    (right-padded admission prefill); rows where valid_len == s reduce to
    the plain last-(W-1) slice."""
    if W == 1:
        return tail
    if valid_len is not None:
        idx = valid_len[:, None] + jnp.arange(W - 1, dtype=jnp.int32)[None, :]
        return jnp.take_along_axis(ext, idx[..., None], axis=1)
    return ext[:, -(W - 1):]


def causal_mask(q_len: int, kv_len: int, q_offset) -> jax.Array:
    """(q_len, kv_len) bool mask; q position i is at absolute q_offset + i."""
    qi = jnp.arange(q_len)[:, None] + q_offset
    kj = jnp.arange(kv_len)[None, :]
    return kj <= qi


def window_mask(q_len: int, kv_len: int, q_offset, window: int) -> jax.Array:
    qi = jnp.arange(q_len)[:, None] + q_offset
    kj = jnp.arange(kv_len)[None, :]
    return (kj <= qi) & (kj > qi - window)


# ---------------------------------------------------------------------------
# Scan handling for cost probes
# ---------------------------------------------------------------------------

import contextvars

# The dry-run cost probes set this: XLA cost_analysis counts while-loop bodies
# once, so probe traces unroll every inner (chunk) scan into straight-line HLO.
UNROLL_SCANS = contextvars.ContextVar("repro_unroll_scans", default=False)


def maybe_scan(body, init, xs, length=None):
    """jax.lax.scan, or a Python-unrolled equivalent under UNROLL_SCANS."""
    if not UNROLL_SCANS.get():
        return jax.lax.scan(body, init, xs, length=length)
    n = length if xs is None else jax.tree.leaves(xs)[0].shape[0]
    carry, ys = init, []
    for i in range(n):
        x_i = None if xs is None else jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if not ys or all(y is None for y in jax.tree.leaves(ys[0], is_leaf=lambda v: v is None)):
        return carry, None
    stacked = jax.tree.map(lambda *z: jnp.stack(z), *ys)
    return carry, stacked
