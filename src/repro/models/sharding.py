"""ParallelContext: explicit-collective helpers used inside shard_map.

All model/runtime code talks to the mesh exclusively through this object, so
the same code runs:
  * on a single CPU device (all sizes 1 -> every collective is a no-op),
  * on the production meshes (16x16) / (2,16,16) under shard_map.

Axis roles:
  tp_axis   ('model')          — tensor parallelism (heads / d_ff / vocab /
                                 experts / ssm heads).
  data_axis ('data')           — factored as consensus_nodes x fsdp:
                                 node(r) = r // fsdp, fsdp_rank(r) = r % fsdp.
  pod_axis  ('pod', optional)  — outer consensus ring across pods (the slow
                                 links the paper targets).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

__all__ = ["ParallelContext", "local_context", "make_context"]


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    tp: int = 1
    data_size: int = 1
    n_nodes: int = 1               # consensus nodes along the data axis
    pods: int = 1                  # consensus ring across pods (multiplied in)
    tp_axis: str = "model"
    data_axis: str = "data"
    pod_axis: str | None = None
    head_sharded: bool = True      # attention TP strategy (see DESIGN.md)
    in_shard_map: bool = False     # True when running under shard_map

    # ------------------------------------------------------------------
    @property
    def fsdp(self) -> int:
        return self.data_size // self.n_nodes

    @property
    def dp(self) -> int:
        """Total data-parallel ways (microbatch shards)."""
        return self.data_size * self.pods

    @property
    def total_consensus_nodes(self) -> int:
        return self.n_nodes * self.pods

    @property
    def fsdp_groups(self) -> tuple[tuple[int, ...], ...] | None:
        if self.fsdp == self.data_size:
            return None  # whole axis, no groups needed
        return tuple(
            tuple(range(n * self.fsdp, (n + 1) * self.fsdp))
            for n in range(self.n_nodes)
        )

    # -- tensor parallel ------------------------------------------------
    def psum_tp(self, x):
        if self.tp == 1:
            return x
        return jax.lax.psum(x, self.tp_axis)

    def pmax_tp(self, x):
        if self.tp == 1:
            return x
        return jax.lax.pmax(x, self.tp_axis)

    def tp_index(self):
        if self.tp == 1:
            return 0
        return jax.lax.axis_index(self.tp_axis)

    def invariant_mean_tp(self, x):
        """Collapse a *replicated-compute* (numerically identical on every
        model rank, but vma-varying) scalar to a single invariant scalar.

        Critical for anything that feeds the differentiated loss: jax.grad
        inside shard_map of a vma-varying scalar computes the gradient of the
        SUM of the per-rank replicas (psum appears at every invariant
        boundary in the transpose), silently scaling all gradients by tp.
        psum/tp keeps both the value and the gradient exact."""
        if self.tp == 1 or not self.in_shard_map:
            return x
        if self.tp_axis in jax.typeof(x).vma:
            return jax.lax.psum(x, self.tp_axis) / self.tp
        return x

    def pvary_tp(self, x):
        """Mark x as vma-varying over the model axis (no-op semantically;
        needed so lax.scan carries type-check under check_vma=True when the
        body contains model-axis all_gathers)."""
        if self.tp == 1 or not self.in_shard_map:
            return x
        return jax.lax.pcast(x, (self.tp_axis,), to="varying")

    def ag_tp(self, x, axis: int, tiled: bool = True):
        """all_gather over the model axis (seq-sharded attention path)."""
        if self.tp == 1:
            return x
        return jax.lax.all_gather(x, self.tp_axis, axis=axis, tiled=tiled)

    def ppermute_tp(self, x, perm):
        if self.tp == 1:
            return x
        return jax.lax.ppermute(x, self.tp_axis, perm)

    # -- FSDP (intra-consensus-node subgroup of the data axis) -----------
    def fsdp_all_gather(self, x, axis: int):
        if self.fsdp == 1:
            return x
        return jax.lax.all_gather(
            x, self.data_axis, axis=axis, tiled=True,
            axis_index_groups=self.fsdp_groups,
        )

    def psum_fsdp(self, x):
        if self.fsdp == 1:
            return x
        return jax.lax.psum(x, self.data_axis, axis_index_groups=self.fsdp_groups)

    # -- data-parallel reductions over the node's microbatches -----------
    def psum_node_batch(self, x):
        """Sum over the microbatch shards *within* one consensus node.

        Gradients must be averaged per node only — each node's f_i stays a
        distinct local objective (paper Problem (1)).
        """
        return self.psum_fsdp(x)

    def psum_all_data(self, x):
        """Sum over every data shard and pod (metrics only)."""
        if self.data_size > 1:
            x = jax.lax.psum(x, self.data_axis)
        if self.pod_axis is not None and self.pods > 1:
            x = jax.lax.psum(x, self.pod_axis)
        return x

    def mean_metric(self, x):
        """Mean of a per-device metric over exactly the mesh axes it varies on.

        VMA-aware: psum only the axes in ``jax.typeof(x).vma`` (psum of an
        *invariant* value multiplies by the axis size, and a size-1 axis can
        still be vma-varying — e.g. a (1, 8) mesh with the batch sharded over
        'data'), then divide by the sizes actually summed.  This keeps
        ``check_vma=True`` out_specs of ``P()`` valid for every mesh shape."""
        if not self.in_shard_map:
            return x
        varying = jax.typeof(x).vma
        denom = 1
        for a in (self.tp_axis, self.data_axis, self.pod_axis):
            if a is not None and a in varying:
                x = jax.lax.psum(x, a)
                denom *= self.axis_size_of(a)
        return x / denom if denom > 1 else x

    # -- consensus rings --------------------------------------------------
    def node_index(self):
        """This device's consensus-node id within the data axis."""
        if self.data_size == 1:
            return 0
        return jax.lax.axis_index(self.data_axis) // self.fsdp

    def ppermute_node_ring(self, x, shift: int):
        """Send to the consensus node ``shift`` steps around the data ring.

        Devices exchange with the peer having the same fsdp rank in the
        neighbor node: data row r -> (r + shift*fsdp) mod data_size.
        """
        if self.n_nodes == 1:
            return x
        n = self.data_size
        perm = [(r, (r + shift * self.fsdp) % n) for r in range(n)]
        return jax.lax.ppermute(x, self.data_axis, perm)

    def ppermute_pod_ring(self, x, shift: int):
        if self.pod_axis is None or self.pods == 1:
            return x
        perm = [(p, (p + shift) % self.pods) for p in range(self.pods)]
        return jax.lax.ppermute(x, self.pod_axis, perm)

    # -- flash-decode combines ---------------------------------------------
    def psum_axes(self, x, axes: tuple[str, ...]):
        for a in axes:
            size = {self.tp_axis: self.tp, self.data_axis: self.data_size,
                    self.pod_axis: self.pods}.get(a, 1)
            if size > 1:
                x = jax.lax.psum(x, a)
        return x

    def pmax_axes(self, x, axes: tuple[str, ...]):
        for a in axes:
            size = {self.tp_axis: self.tp, self.data_axis: self.data_size,
                    self.pod_axis: self.pods}.get(a, 1)
            if size > 1:
                x = jax.lax.pmax(x, a)
        return x

    def axis_index_of(self, axis: str):
        size = {self.tp_axis: self.tp, self.data_axis: self.data_size,
                self.pod_axis: self.pods}.get(axis, 1)
        if size == 1:
            return 0
        return jax.lax.axis_index(axis)

    def axis_size_of(self, axis: str) -> int:
        return {self.tp_axis: self.tp, self.data_axis: self.data_size,
                self.pod_axis: self.pods}.get(axis, 1)


def local_context(head_sharded: bool = True) -> ParallelContext:
    """Single-device context: every collective degenerates to identity."""
    return ParallelContext(tp=1, data_size=1, n_nodes=1, pods=1,
                           pod_axis=None, head_sharded=head_sharded)


def make_context(mesh: jax.sharding.Mesh, consensus_nodes: int,
                 head_sharded: bool = True) -> ParallelContext:
    """Build the context from a production mesh (launch/mesh.py)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = sizes.get("model", 1)
    data = sizes.get("data", 1)
    pods = sizes.get("pod", 1)
    if data % consensus_nodes != 0:
        raise ValueError(f"consensus_nodes={consensus_nodes} must divide data={data}")
    return ParallelContext(
        tp=tp, data_size=data, n_nodes=consensus_nodes, pods=pods,
        pod_axis="pod" if "pod" in sizes else None,
        head_sharded=head_sharded,
        in_shard_map=True,
    )
