"""Flat wire packing: one lane-aligned buffer for the whole parameter tree.

The per-leaf consensus exchange pays a per-leaf tax on the hottest path we
have: every parameter leaf costs a blockify reshape, a quantize launch,
four ``ppermute`` collectives (codes/scales x two ring directions) and a
dequant-combine launch — O(leaf count) small collectives per training step.
This module makes the whole tree look like ONE quantization problem:

* :class:`WireLayout` — a **static** map from every fp32-consensus leaf to a
  row range of a single lane-aligned ``(n_rows, BLOCK)`` buffer.  Each leaf
  is padded to whole ``BLOCK`` rows only (row-granular: quantization blocks
  never span leaves, so per-block scales/codes are **identical** to
  quantizing each leaf separately — tests/test_wire.py asserts this); the
  buffer tail is padded to a ``TILE_N``-row multiple once for the Pallas
  grid.  Row granularity keeps padding overhead at < BLOCK elements per
  leaf — per-leaf ``TILE_N`` padding would inflate leaf-rich trees
  (hundreds of per-layer leaves) by 2-3x.
* ``pack`` / ``unpack`` — the only per-leaf work left on the hot path:
  reshape+pad+concat into the packed buffer (fuses into one copy, no
  collectives) and the inverse slice-out for the returned parameter tree.

The consensus shadows ``x_tilde`` / ``m_agg`` live **persistently** in
packed form (``ConsensusRuntime.init_state``), so the per-step
blockify/unblockify reshapes of the shadows disappear from the trace
entirely; the ring then exchanges one byte payload per direction
(``repro.kernels.ops.pack_payload``) regardless of leaf count.

Padding invariant: padding rows quantize to code 0 (stochastic rounding of
an exact 0 differential never rounds away from 0), so the zero padding of
``x_tilde`` / ``m_agg`` is preserved by every exchange step and resync —
no re-zeroing pass is needed (asserted in tests).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops

__all__ = ["LeafSlot", "WireLayout", "ChunkedLayout", "pvary_to",
           "lift_concat"]


def pvary_to(x, axes):
    """Mark ``x`` vma-varying over ``axes`` (no-op semantically; required so
    shard_map(check_vma=True) out_specs naming those axes type-check even
    when no leaf of the packed tree happened to vary on one of them)."""
    have = jax.typeof(x).vma
    missing = tuple(a for a in axes if a is not None and a not in have)
    return jax.lax.pcast(x, missing, to="varying")


def _lift_common_vma(arrays):
    """pcast every array to the union vma of the group before concatenation
    (shard_map check_vma=True requires concat operands uniformly typed; a
    no-op outside shard_map)."""
    union = frozenset().union(*(jax.typeof(a).vma for a in arrays))
    return [jax.lax.pcast(a, tuple(union - jax.typeof(a).vma), to="varying")
            for a in arrays]


def _flatten_with_paths(tree):
    """(leaves, treedef, path strings via ``jax.tree_util.keystr``)."""
    keyed, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return ([leaf for _, leaf in keyed], treedef,
            [jax.tree_util.keystr(kp) for kp, _ in keyed])


def lift_concat(parts, axis: int = 0):
    """vma-lifted concatenation of buffer parts (a single part passes
    through) — THE reassembly idiom of every packed-wire path: per-chunk
    results (ChunkedLayout), per-fragment payloads/results (wireplan,
    distributed)."""
    parts = _lift_common_vma(list(parts))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=axis)


#: consensus-state keys of the async (one-step-stale) exchange's in-flight
#: payload triple: this node's own transmitted payload and the two ring
#: arrivals, carried across the step boundary (core.distributed)
INFLIGHT_KEYS = ("fly_self", "fly_up", "fly_dn")


def inflight_init(payload_bytes: int, trailer=None):
    """Initial in-flight wire payload for the async exchange's double
    buffer: all-zero bytes — every codec decodes an all-zero payload to a
    zero differential (the same contract the link-loss machinery relies
    on), so retiring it at step 1 is an exact no-op gossip — plus an
    optional pre-encoded uint8 trailer (the push-sum weight w_0 = 1, which
    must NOT decode to 0)."""
    buf = jnp.zeros((int(payload_bytes),), jnp.uint8)
    if trailer is not None:
        buf = jnp.concatenate([buf, trailer.astype(jnp.uint8)])
    return buf


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Where one leaf lives inside the packed buffer (all static)."""

    shape: tuple[int, ...]
    dtype: Any                 # original leaf dtype (unpack casts back)
    size: int                  # number of real elements
    row_start: int             # first block row of this leaf
    n_rows: int                # whole BLOCK-rows owned by this leaf (ceil)
    #: leaf path name (jax.tree_util.keystr), e.g. "['layers'][0]['norm1']"
    #: — what WirePlan rules pattern-match against (core.wireplan)
    path: str = ""

    @property
    def row_end(self) -> int:
        return self.row_start + self.n_rows


@dataclasses.dataclass(frozen=True)
class WireLayout:
    """Static packing plan for a parameter tree (hashable; trace-constant).

    Built once from shapes/dtypes (arrays or ShapeDtypeStructs both work);
    ``pack``/``unpack`` are pure jittable functions of the tree/buffer.
    ``n_rows`` (the buffer height) = ``n_data_rows`` (leaf-owned rows)
    rounded up to a ``TILE_N`` multiple; the tail rows belong to no leaf.
    """

    slots: tuple[LeafSlot, ...]
    treedef: Any
    n_rows: int
    n_data_rows: int
    block: int = kops.BLOCK
    #: buffer-order permutation of leaf indices (``()`` = leaf order): slot
    #: ``placement[0]`` owns the first row range, and so on.  ``slots`` stay
    #: in LEAF order (``row_start`` is always absolute), so ``unpack`` /
    #: ``leaf_rows`` are placement-oblivious; only ``pack`` /
    #: ``from_leaf_rows`` iterate buffer order.  WirePlan groups same-codec
    #: leaves with this so mixed plans keep their codec runs few and large
    #: (core.wireplan.grouped_placement).
    placement: tuple[int, ...] = ()

    # -- construction ---------------------------------------------------
    @classmethod
    def for_tree(cls, tree: Any, block: int = kops.BLOCK) -> "WireLayout":
        import math
        leaves, treedef, paths = _flatten_with_paths(tree)
        slots = []
        row = 0
        for leaf, path in zip(leaves, paths):
            shape = tuple(int(s) for s in leaf.shape)
            size = int(np.prod(shape, dtype=np.int64)) if shape else 1
            n_rows = int(math.ceil(max(size, 1) / block))
            slots.append(LeafSlot(shape=shape, dtype=jnp.dtype(leaf.dtype),
                                  size=size, row_start=row, n_rows=n_rows,
                                  path=path))
            row += n_rows
        total = int(math.ceil(max(row, 1) / kops.TILE_N) * kops.TILE_N)
        return cls(slots=tuple(slots), treedef=treedef, n_rows=total,
                   n_data_rows=row, block=block)

    # -- buffer order -----------------------------------------------------
    @property
    def buffer_order(self) -> tuple[int, ...]:
        """Leaf indices in buffer-row order (identity without placement)."""
        return self.placement or tuple(range(len(self.slots)))

    def with_placement(self, placement) -> "WireLayout":
        """The same leaves re-packed in ``placement`` order: every slot's
        ``row_start`` is recomputed to its position in the new buffer order
        (heights, padding and the TILE_N tail are unchanged, so the total
        geometry — ``n_rows`` / ``n_data_rows`` — is invariant)."""
        placement = tuple(int(i) for i in placement)
        if sorted(placement) != list(range(len(self.slots))):
            raise ValueError(f"placement {placement} is not a permutation "
                             f"of {len(self.slots)} leaf indices")
        slots = list(self.slots)
        row = 0
        for i in placement:
            slots[i] = dataclasses.replace(slots[i], row_start=row)
            row += slots[i].n_rows
        assert row == self.n_data_rows, (row, self.n_data_rows)
        identity = placement == tuple(range(len(self.slots)))
        return dataclasses.replace(self, slots=tuple(slots),
                                   placement=() if identity else placement)

    # -- derived sizes ---------------------------------------------------
    @property
    def n_leaves(self) -> int:
        return len(self.slots)

    @property
    def n_elements(self) -> int:
        """Real (un-padded) element count across the tree."""
        return sum(s.size for s in self.slots)

    def buffer_struct(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct((self.n_rows, self.block), jnp.float32)

    def describe(self) -> dict:
        """JSON-able geometry snapshot (telemetry ``wire_plan`` events)."""
        return {"n_leaves": self.n_leaves, "n_elements": self.n_elements,
                "n_rows": self.n_rows, "n_data_rows": self.n_data_rows,
                "block": self.block,
                "reordered": bool(self.placement)}

    # -- pack / unpack ---------------------------------------------------
    def check_tree(self, tree: Any) -> list:
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if treedef != self.treedef or len(leaves) != len(self.slots):
            raise ValueError(
                f"tree structure does not match layout: {treedef} vs "
                f"{self.treedef}")
        for leaf, slot in zip(leaves, self.slots):
            if tuple(leaf.shape) != slot.shape:
                raise ValueError(
                    f"leaf shape {tuple(leaf.shape)} != layout slot "
                    f"{slot.shape}")
        return leaves

    def pack(self, tree: Any) -> jax.Array:
        """Tree -> one (n_rows, block) fp32 buffer, zero padded per leaf to
        whole rows (quantization blocks never span leaves) plus the
        TILE_N-alignment tail."""
        leaves = self.check_tree(tree)
        flats = []
        for i in self.buffer_order:
            leaf, slot = leaves[i], self.slots[i]
            flat = leaf.astype(jnp.float32).reshape(-1)
            pad = slot.n_rows * self.block - slot.size
            flats.append(jnp.pad(flat, (0, pad)))
        tail = (self.n_rows - self.n_data_rows) * self.block
        if tail:
            flats.append(jnp.zeros((tail,), jnp.float32))
        flats = _lift_common_vma(flats)
        out = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
        return out.reshape(self.n_rows, self.block)

    def unpack(self, packed: jax.Array, cast: bool = True) -> Any:
        """Packed buffer -> tree (casting back to each leaf's dtype)."""
        if packed.shape != (self.n_rows, self.block):
            raise ValueError(f"packed shape {packed.shape} != "
                             f"{(self.n_rows, self.block)}")
        flat = packed.reshape(-1)
        leaves = []
        for slot in self.slots:
            start = slot.row_start * self.block
            seg = jax.lax.slice_in_dim(flat, start, start + slot.size)
            seg = seg.reshape(slot.shape)
            leaves.append(seg.astype(slot.dtype) if cast else seg)
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    # -- per-leaf views (reference path / tests) -------------------------
    def leaf_rows(self, packed: jax.Array, i: int) -> jax.Array:
        """The (n_rows_i, block) row range of leaf ``i`` — exactly what the
        per-leaf path would have produced with ``kops.blockify``."""
        slot = self.slots[i]
        return jax.lax.slice_in_dim(packed, slot.row_start, slot.row_end,
                                    axis=0)

    def from_leaf_rows(self, rows: list) -> jax.Array:
        """Reassemble a packed buffer from per-leaf row blocks, given in
        LEAF order (the TILE_N-alignment tail is re-zeroed)."""
        if len(rows) != len(self.slots):
            raise ValueError(f"{len(rows)} row blocks != {len(self.slots)}")
        rows = [rows[i] for i in self.buffer_order]
        tail = self.n_rows - self.n_data_rows
        if tail:
            rows.append(jnp.zeros((tail, self.block), jnp.float32))
        rows = _lift_common_vma(rows)
        out = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)
        assert out.shape == (self.n_rows, self.block), out.shape
        return out


@dataclasses.dataclass(frozen=True)
class ChunkedLayout:
    """Static split of a packed ``(n_rows, BLOCK)`` buffer into pipeline
    chunks (the unit of the double-buffered consensus exchange).

    Chunk boundaries sit on ``TILE_N``-row multiples: rows ARE quantization
    blocks (one per-block scale per row), so any row-aligned split leaves
    codes/scales bit-identical to quantizing the whole buffer at once, and
    tile alignment additionally keeps every chunk a valid standalone Pallas
    grid.  The requested chunk count is clamped to the buffer's tile count;
    when it does not divide evenly the leading chunks carry one extra tile
    (ragged tail allowed — chunk sizes are static, no scan stacking).
    """

    n_rows: int
    block: int
    #: per chunk: (row_start, n_rows) — contiguous, covering [0, n_rows)
    bounds: tuple[tuple[int, int], ...]

    @classmethod
    def split(cls, layout: "WireLayout", pipeline_chunks: int,
              tile: int = kops.TILE_N) -> "ChunkedLayout":
        if pipeline_chunks < 1:
            raise ValueError(f"pipeline_chunks must be >= 1, got "
                             f"{pipeline_chunks}")
        n_tiles = layout.n_rows // tile
        assert n_tiles * tile == layout.n_rows, (layout.n_rows, tile)
        n_chunks = max(1, min(pipeline_chunks, n_tiles))
        base, rem = divmod(n_tiles, n_chunks)
        bounds, row = [], 0
        for c in range(n_chunks):
            rows = (base + (1 if c < rem else 0)) * tile
            bounds.append((row, rows))
            row += rows
        assert row == layout.n_rows, (row, layout.n_rows)
        return cls(n_rows=layout.n_rows, block=layout.block,
                   bounds=tuple(bounds))

    @property
    def n_chunks(self) -> int:
        return len(self.bounds)

    def slice_rows(self, buf: jax.Array, c: int) -> jax.Array:
        """Chunk ``c``'s row range of a full-height packed buffer (static
        slice — fuses into consumers, never a standalone copy)."""
        start, rows = self.bounds[c]
        return jax.lax.slice_in_dim(buf, start, start + rows, axis=0)

    def concat(self, parts: list) -> jax.Array:
        """Reassemble the full-height buffer from per-chunk results."""
        if len(parts) != self.n_chunks:
            raise ValueError(f"{len(parts)} chunk parts != {self.n_chunks}")
        out = lift_concat(parts)
        assert out.shape[0] == self.n_rows, out.shape
        return out
