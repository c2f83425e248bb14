"""Production mesh construction + logical-axis helpers.

Meshes (TPU v5e):
  single-pod : (16, 16)     axes ("data", "model")   = 256 chips
  multi-pod  : (2, 16, 16)  axes ("pod", "data", "model") = 512 chips

The "pod" axis composes with "data" for batch sharding ("dp" logical axis),
so multi-pod scaling is purely more data parallelism with a hierarchical
gradient reduction (intra-pod ICI reduce-scatter, inter-pod DCN all-reduce --
XLA derives the hierarchy from the nested spec).
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.params import resolve_pspec


def make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh() -> Mesh:
    """1x1 mesh over the single CPU device (smoke tests)."""
    return make_mesh((1, 1), ("data", "model"))


def chips(mesh: Mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n


def batch_axes(mesh: Mesh):
    if "pod" in mesh.axis_names:
        return ("pod", "data")
    return "data"


def act_pspec(mesh: Mesh, batch: int, seq_shard: bool = False) -> P:
    """[B, L, d] activation constraint: batch over dp (+ optional SP)."""
    dp = batch_axes(mesh)
    dp_size = (mesh.shape["data"] * mesh.shape.get("pod", 1))
    first = dp if batch % dp_size == 0 else None
    if seq_shard:
        return P(first, "model")
    return P(first)


def input_shardings(mesh: Mesh, batch_sds: dict, axes_tree: dict) -> dict:
    """Resolve configs.input_specs logical axes to NamedShardings."""
    out = {}
    for k, sds in batch_sds.items():
        out[k] = NamedSharding(
            mesh, resolve_pspec(mesh, sds.shape, axes_tree[k]))
    return out
