"""Parameter definition & storage-layout infrastructure.

Models declare their parameters as trees of :class:`ParamDef` — a *logical*
(per-consensus-node) tensor shape plus distribution metadata:

  * ``tp_dim``   — dimension sharded over the tensor-parallel ``model`` axis
                   (None = replicated over model).  Sizes on tp dims must be
                   divisible by ``tp`` (configs pad vocab/experts/heads).
  * ``fsdp_dim`` — dimension along which (a) the per-node replica is sharded
                   over the intra-node FSDP subgroup of the ``data`` axis and
                   (b) the per-node replicas of all consensus nodes are
                   concatenated in the *storage* (global, jit-boundary)
                   layout.  Padded to a multiple of fsdp.

Storage layout of a leaf with logical shape ``(..., F, ...)``:

    global = (..., n_nodes * pad(F, fsdp), ...)  sharded P(..., 'data', ...)

so that data row ``r`` of the mesh holds exactly the ``(r % fsdp)``-th FSDP
shard of consensus node ``r // fsdp``'s replica — the data axis factors into
``consensus_nodes x fsdp`` without leaving the mandated mesh axes.

Inside ``shard_map`` each device sees the local block; ``gather_replica``
all-gathers over the FSDP subgroup (``axis_index_groups``) and slices off the
padding to recover the logical (tp-local) tensor for compute.  Gradient AD
through the (tiled) all_gather transposes to the reduce-scatter, giving
ZeRO-3-style sharded gradients for free.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

__all__ = [
    "ParamDef",
    "logical_shape_local",
    "storage_shape",
    "storage_partition_spec",
    "storage_shape_dtype",
    "materialize_logical",
    "materialize_storage_host",
    "gather_replica",
    "tree_paths",
]


@dataclasses.dataclass(frozen=True)
class ParamDef:
    """Declaration of one parameter tensor (logical, per-node, tp-global)."""

    shape: tuple[int, ...]          # full logical shape (before tp split)
    tp_dim: int | None = None       # dim sharded over 'model'
    fsdp_dim: int = 0               # dim carrying nodes*fsdp in storage
    init: str = "normal"            # normal | zeros | ones | scaled
    scale: float = 1.0              # stddev multiplier for 'normal'
    dtype: Any = jnp.float32

    def __post_init__(self):
        if self.tp_dim is not None and self.tp_dim == self.fsdp_dim:
            raise ValueError(f"tp_dim == fsdp_dim == {self.tp_dim} for shape {self.shape}")


def _pad_to(x: int, m: int) -> int:
    return int(math.ceil(x / m) * m)


def logical_shape_local(d: ParamDef, tp: int) -> tuple[int, ...]:
    """Per-model-rank logical shape (tp dim divided)."""
    s = list(d.shape)
    if d.tp_dim is not None:
        if s[d.tp_dim] % tp != 0:
            raise ValueError(f"tp dim {d.tp_dim} of {d.shape} not divisible by {tp}")
        s[d.tp_dim] //= tp
    return tuple(s)


def storage_shape(d: ParamDef, tp: int, n_nodes: int, fsdp: int) -> tuple[int, ...]:
    """Global (jit-boundary) shape: tp dim full, fsdp dim = nodes*pad(F,fsdp)."""
    del tp  # tp dim stays full in the global array (pjit shards it)
    s = list(d.shape)
    s[d.fsdp_dim] = n_nodes * _pad_to(s[d.fsdp_dim], fsdp)
    return tuple(s)


def local_block_shape(d: ParamDef, tp: int, fsdp: int) -> tuple[int, ...]:
    """Shape each device sees inside shard_map."""
    s = list(d.shape)
    s[d.fsdp_dim] = _pad_to(s[d.fsdp_dim], fsdp) // fsdp
    if d.tp_dim is not None:
        s[d.tp_dim] //= tp
    return tuple(s)


def storage_partition_spec(d: ParamDef, data_axes: tuple[str, ...] = ("data",),
                           tp_axis: str | None = "model") -> P:
    """PartitionSpec for the storage layout on the production mesh.

    ``data_axes`` may be ("data",) or ("pod", "data") — in the multi-pod case
    the consensus node set spans pods, so the fsdp/storage dim is sharded over
    both axes (pod-major).  ``tp_axis=None`` names no model axis.
    """
    ndim = len(d.shape)
    spec: list[Any] = [None] * ndim
    if data_axes:  # () = replicated-over-data layout (weight-stationary serve)
        spec[d.fsdp_dim] = data_axes if len(data_axes) > 1 else data_axes[0]
    if d.tp_dim is not None:
        spec[d.tp_dim] = tp_axis
    return P(*spec)


def storage_shape_dtype(d: ParamDef, tp: int, n_nodes: int, fsdp: int) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(storage_shape(d, tp, n_nodes, fsdp), d.dtype)


# ---------------------------------------------------------------------------
# Materialization
# ---------------------------------------------------------------------------

def _init_array(key: jax.Array, d: ParamDef, shape: tuple[int, ...]) -> jax.Array:
    if d.init == "zeros":
        return jnp.zeros(shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(shape, d.dtype)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = d.scale / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(d.dtype)


def tree_paths(tree: Any) -> list[tuple]:
    """Stable list of key-paths of a pytree of ParamDefs."""
    leaves = jax.tree_util.tree_leaves_with_path(
        tree, is_leaf=lambda x: isinstance(x, ParamDef))
    return [p for p, _ in leaves]


def materialize_logical(defs: Any, key: jax.Array, tp: int = 1) -> Any:
    """Per-node logical params with tp-local shapes (CPU tests, oracles)."""
    leaves, treedef = jax.tree_util.tree_flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(leaves))
    out = [_init_array(k, d, logical_shape_local(d, tp)) for k, d in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def materialize_storage_host(defs: Any, key: jax.Array, tp: int, n_nodes: int,
                             fsdp: int) -> Any:
    """Host-side (np) storage-layout params: identical replicas tiled on the
    fsdp dim.  Only for *small* real runs (examples/tests); big configs are
    dry-run only (ShapeDtypeStruct)."""
    leaves, treedef = jax.tree_util.tree_flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, d in zip(keys, leaves):
        logical = np.asarray(_init_array(k, d, d.shape))
        f = d.fsdp_dim
        padded = _pad_to(d.shape[f], fsdp)
        pad_widths = [(0, 0)] * logical.ndim
        pad_widths[f] = (0, padded - d.shape[f])
        logical = np.pad(logical, pad_widths)
        tiled = np.concatenate([logical] * n_nodes, axis=f)
        out.append(tiled)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Inside-shard_map gather
# ---------------------------------------------------------------------------

def gather_replica(local: jax.Array, d: ParamDef, ctx) -> jax.Array:
    """All-gather this node's FSDP shards and strip padding -> logical tensor
    (tp-local).  ``ctx`` is a ParallelContext (models.sharding)."""
    x = ctx.fsdp_all_gather(local, axis=d.fsdp_dim)
    logical = list(d.shape)
    if d.tp_dim is not None:
        logical[d.tp_dim] //= ctx.tp
    if x.shape[d.fsdp_dim] != logical[d.fsdp_dim]:
        x = jax.lax.slice_in_dim(x, 0, logical[d.fsdp_dim], axis=d.fsdp_dim)
    return x


def gather_tree(local_tree: Any, defs: Any, ctx) -> Any:
    """gather_replica over a whole (sub)tree."""
    return _gather_tree_impl(local_tree, defs, ctx)


def _gather_tree_impl(local_tree, defs, ctx):
    flat_a, treedef = jax.tree_util.tree_flatten(local_tree)
    flat_d = jax.tree_util.tree_flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))[0]
    assert len(flat_a) == len(flat_d), (len(flat_a), len(flat_d))
    return jax.tree_util.tree_unflatten(
        treedef, [gather_replica(a, d, ctx) for a, d in zip(flat_a, flat_d)])
