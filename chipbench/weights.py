"""Seeded weights in the layout the served program reads, drawn on the
device in one jitted call, in bf16 (the type they are served in).

Leaves are drawn per name; the reference (``reference.py``) reads the same
tree.  Norm weights are stored as offsets (the layer scales by 1 + w), so
they are drawn around 0.  An architecture names the leaves it adds in its
own ``STD`` (``arch/<model_type>.py``)."""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

# standard deviation by leaf name; a projection (2-D or more) not named
# here uses 1/sqrt(fan-in)
STD = {"table": 1.0, "norm1": 0.1, "norm2": 0.1, "final_norm": 0.1,
       "b_q": 0.5, "b_k": 0.5, "b_v": 0.5}


def weight_key(seed: int):
    """A JAX key from any whole-number seed (larger than 32 bits too)."""
    state = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.key(int(state))


def _std(name: str, shape, table) -> float:
    if name in table:
        return table[name]
    if len(shape) < 2:
        raise ValueError(
            f"weight leaf {name!r} of shape {tuple(shape)} has no standard "
            "deviation: name it in STD of chipbench/weights.py or of its "
            "architecture, chipbench/arch/<model_type>.py")
    fan_in = shape[-3] * shape[-2] if name == "w_o" else shape[-2]
    return 1.0 / math.sqrt(fan_in)


def leaf_names(defs, is_leaf):
    paths, _ = jax.tree_util.tree_flatten_with_path(defs, is_leaf=is_leaf)
    return [str(getattr(p[-1], "key", p[-1])) for p, _ in paths]


def draw(seed: int, defs, is_leaf, mesh, arch_std=None):
    """``defs``: a tree whose leaves carry ``shape``, ``spec`` and ``dtype``
    (the program's parameter definitions); ``arch_std``: the architecture's
    ``STD``, which adds to and overrides ``STD``.  Returns the tree of
    arrays, each leaf created sharded as its ``spec`` says."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_leaf)
    names = leaf_names(defs, is_leaf)
    table = {**STD, **(arch_std or {})}
    sig = tuple((tuple(d.shape), jnp.dtype(d.dtype).name,
                 _std(n, d.shape, table)) for n, d in zip(names, leaves))
    out_sh = tuple(NamedSharding(mesh, d.spec) for d in leaves)
    arrays = _draw(weight_key(seed), sig, out_sh)
    return jax.tree.unflatten(treedef, list(arrays))


@partial(jax.jit, static_argnums=(1, 2))
def _draw(key, sig, out_sh):
    keys = jax.random.split(key, len(sig))
    out = []
    for k, (shape, dtype, std), sh in zip(keys, sig, out_sh):
        x = jax.random.normal(k, shape, jnp.bfloat16) * jnp.bfloat16(std)
        out.append(jax.lax.with_sharding_constraint(x.astype(dtype), sh))
    return tuple(out)
