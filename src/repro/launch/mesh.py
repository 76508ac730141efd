"""Mesh construction — every mesh of the repo is built here.

Defined as FUNCTIONS so importing this module never touches jax device
state. The production target is TPU v5e: one pod = 16x16 = 256 chips,
multi-pod = 2 pods = 512 chips with a leading "pod" axis (DCN between pods,
ICI within).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``: the partitioner
    propagates shardings through the fused round (gathers such as
    ``g[membership]`` included). JAX 0.9 defaults to ``Explicit`` axes,
    under which those gathers refuse to trace without an out_sharding."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_fed_mesh(data: int, model: int = 1):
    """Federated-round mesh: the round executor's client axis shards over
    "data" (``data`` slices — cohorts, ShardedClientStore shards) and the
    local solver's parameter dim over "model" (``model``-way, replicated
    when 1). ``data * model`` must equal the visible device count; see
    docs/scaling.md for the placement rules."""
    return make_mesh((data, model), ("data", "model"))
