"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Shapes:

  single pod : (16, 16)        axes ("data", "model")      = 256 chips
  multi-pod  : (2, 16, 16)     axes ("pod", "data", "model") = 512 chips

The dry-run launcher sets XLA_FLAGS=--xla_force_host_platform_device_count=512
*before any jax import* so these meshes can be built on the CPU container.

The mesh "pod" axis shards *devices*; it is orthogonal to hierarchical
consensus pods (``ConsensusConfig(hierarchy="pods=P")``, DESIGN.md §14),
which partition the consensus *node ring* over the flattened
(pod, data) axes — the two compose: a multi-pod mesh flattens into one
ring, and the HierarchySpec groups consecutive ring nodes into
psum-averaged consensus pods on top of it.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_cpu_mesh"]


def _make_mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_cpu_mesh(data: int = 1, model: int = 1, pod: int | None = None
                  ) -> jax.sharding.Mesh:
    """Small mesh over however many (host) devices exist — tests/examples."""
    if pod is not None:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))
