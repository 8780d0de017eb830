"""The few jax entry points every call site shares, as one-liners.

``shard_map`` fixes the keyword set the per-shard code uses, ``make_mesh``
keeps meshes ``Auto`` (jax's default is ``Explicit``, which the shard_map'd
programs here are not written for), and ``tpu_compiler_params`` keeps the
Pallas TPU import out of module import time.
"""
from __future__ import annotations

from typing import Any

import jax

__all__ = ["shard_map", "make_mesh", "tpu_compiler_params"]


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map``; ``check_vma=False`` where per-shard code returns
    unreduced partials on purpose."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(axis_shapes, axis_names, **kwargs: Any):
    """``jax.make_mesh`` with every axis ``Auto``."""
    kwargs.setdefault("axis_types",
                      (jax.sharding.AxisType.Auto,) * len(axis_shapes))
    return jax.make_mesh(axis_shapes, axis_names, **kwargs)


def tpu_compiler_params(**kwargs: Any):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)
