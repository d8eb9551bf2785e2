"""Pallas TPU kernel: fused stochastic int8 quantization (ADC-DGD wire path).

This is the compute hot-spot the paper's technique inserts on the critical
communication path: every training step, every parameter shard is quantized
before the consensus ``ppermute`` and dequantized after.  Fusing
(max-reduce -> scale -> divide -> stochastic round -> clip -> pack) into one
VMEM-resident kernel avoids 5 HBM round-trips of the fp32 differential.

TPU mapping
-----------
* input y is reshaped by the caller to (n_blocks, BLOCK) with BLOCK a
  multiple of 128 (lane width); rows are the quantization blocks.
* grid tiles TILE_N = 32 rows at a time: fp32 tile (32, 512) = 64 KiB VMEM,
  int8 output tile (32, 512) matches the TPU int8 (32, 128) packing.
* the per-row max reduction runs on the VPU within the tile; the MXU is not
  involved (element-wise kernel).
* stochastic rounding consumes a caller-provided uniform noise tile
  (generated with jax.random outside) — keeps the kernel deterministic and
  oracle-comparable bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["quantize_blocks_pallas", "quantize_payload_pallas", "TILE_N",
           "BLOCK", "SCALE_BYTES", "default_interpret"]

TILE_N = 32     # rows per grid step (int8 sublane tile)
BLOCK = 512     # quantization block = lane-dim multiple of 128
SCALE_BYTES = 4  # one fp32 scale per row, appended to the wire payload
#: whole-array SMEM operand: the kernels' scalar inputs (grid step,
#: combine weights) — read as ``ref[i]``, which Mosaic allows only on
#: SMEM/VMEM refs
SMEM_SCALARS = pl.BlockSpec(memory_space=pltpu.SMEM)


def default_interpret() -> bool:
    """Backend-derived ``interpret`` default for every kernel in this
    package: compiled Pallas on real TPUs, interpret mode everywhere else
    (CPU CI, host-platform meshes) where Mosaic cannot lower."""
    return jax.default_backend() != "tpu"


def _chunk_view(n_full: int, n_rows: int | None, row_offset: int):
    """Resolve a static chunk view over full-height (n_full, ...) operands.

    Returns ``(n, tile_offset)``: the grid covers ``n`` rows starting at
    ``row_offset`` of the full buffer — the kernel reads the chunk directly
    out of the persistent packed array via BlockSpec index offsets, no
    sliced copy is materialized.  Offsets/heights must sit on TILE_N
    boundaries (chunk boundaries are tile-aligned by ChunkedLayout).
    """
    n = n_full if n_rows is None else int(n_rows)
    assert n % TILE_N == 0, f"chunk rows {n} not a multiple of {TILE_N}"
    assert row_offset % TILE_N == 0, f"row_offset {row_offset} unaligned"
    assert row_offset + n <= n_full, (row_offset, n, n_full)
    return n, row_offset // TILE_N


def _row_index_map(arr_rows: int, n: int, tile_off: int):
    """Index map for an operand that is either full-height (read at the
    chunk offset, in-kernel view) or already chunk-height (offset 0)."""
    if arr_rows == n:
        return lambda i: (i, 0)
    return lambda i: (i + tile_off, 0)


def _vma_of(x) -> frozenset:
    """The mesh axes ``x`` is typed as varying over (empty outside
    ``shard_map(check_vma=True)``)."""
    return jax.typeof(x).vma


def _stochastic_round_clip(s, noise):
    lo = jnp.floor(s)
    frac = s - lo
    q = lo + (noise < frac).astype(jnp.float32)
    return jnp.clip(q, -127.0, 127.0)


def _adaptive_kernel(y_ref, noise_ref, codes_ref, scales_ref):
    y = y_ref[...].astype(jnp.float32)                     # (TILE_N, BLOCK)
    noise = noise_ref[...]
    absmax = jnp.max(jnp.abs(y), axis=-1, keepdims=True)   # (TILE_N, 1)
    scale = jnp.maximum(absmax, 1e-30) * jnp.float32(1.0 / 127.0)
    s = y / scale
    codes_ref[...] = _stochastic_round_clip(s, noise).astype(jnp.int8)
    scales_ref[...] = scale


def _fixed_kernel(y_ref, noise_ref, step_ref, codes_ref, scales_ref):
    y = y_ref[...].astype(jnp.float32)
    noise = noise_ref[...]
    scale = jnp.broadcast_to(step_ref[0], (y.shape[0], 1))   # SMEM scalar
    s = y / scale
    codes_ref[...] = _stochastic_round_clip(s, noise).astype(jnp.int8)
    scales_ref[...] = scale


def _scale_to_bytes(scale_col):
    """(T, 1) f32 -> (T, SCALE_BYTES) uint8, least-significant byte first.

    Same-width bitcast + signed-int byte extraction only (shape-changing
    bitcasts are not portable inside kernels, and Mosaic has no unsigned
    reductions or float->uint casts); matches XLA's f32->uint8 bitcast
    order used by ``ops.pack_payload`` (pinned by
    ``test_payload_byte_order``)."""
    u = jax.lax.bitcast_convert_type(scale_col, jnp.int32)         # (T, 1)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, SCALE_BYTES), 1) * 8
    return ((u >> shifts) & 0xFF).astype(jnp.uint8)                # (T, 4)


def _payload_adaptive_kernel(y_ref, noise_ref, payload_ref):
    y = y_ref[...].astype(jnp.float32)                     # (TILE_N, BLOCK)
    noise = noise_ref[...]
    absmax = jnp.max(jnp.abs(y), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-30) * jnp.float32(1.0 / 127.0)
    q = _stochastic_round_clip(y / scale, noise)
    payload_ref[:, : y.shape[1]] = jax.lax.bitcast_convert_type(
        q.astype(jnp.int8), jnp.uint8)
    payload_ref[:, y.shape[1]:] = _scale_to_bytes(scale)


def _payload_fixed_kernel(y_ref, noise_ref, step_ref, payload_ref):
    y = y_ref[...].astype(jnp.float32)
    noise = noise_ref[...]
    scale = jnp.broadcast_to(step_ref[0], (y.shape[0], 1))   # SMEM scalar
    q = _stochastic_round_clip(y / scale, noise)
    payload_ref[:, : y.shape[1]] = jax.lax.bitcast_convert_type(
        q.astype(jnp.int8), jnp.uint8)
    payload_ref[:, y.shape[1]:] = _scale_to_bytes(scale)


def _out_vma(*args):
    """vma kwarg for pallas out ShapeDtypeStructs: union of the input vmas
    (required under shard_map check_vma=True; empty dict elsewhere)."""
    vma: frozenset = frozenset()
    for a in args:
        vma |= _vma_of(a)
    return {"vma": vma} if vma else {}


def _align_vma(*args):
    """pcast every array to the union vma of the group (no-op outside
    shard_map) so the pallas kernel sees uniformly-typed inputs."""
    union: frozenset = frozenset()
    for a in args:
        union |= _vma_of(a)
    if not union:
        return args
    return tuple(jax.lax.pcast(a, tuple(union - _vma_of(a)), to="varying")
                 for a in args)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_blocks_pallas(y: jax.Array, noise: jax.Array,
                           fixed_step: jax.Array | None = None,
                           interpret: bool | None = None):
    """y, noise: (n_blocks, BLOCK) f32.  Returns (codes int8, scales f32)."""
    if interpret is None:
        interpret = default_interpret()
    n, b = y.shape
    assert b % 128 == 0, f"block {b} must be lane-aligned (x128)"
    assert n % TILE_N == 0, f"n_blocks {n} must be a multiple of {TILE_N}"
    grid = (n // TILE_N,)
    row_spec = pl.BlockSpec((TILE_N, b), lambda i: (i, 0))
    scale_spec = pl.BlockSpec((TILE_N, 1), lambda i: (i, 0))
    if fixed_step is None:
        y, noise = _align_vma(y, noise)
        vma_kw = _out_vma(y, noise)
        out_shape = (
            jax.ShapeDtypeStruct((n, b), jnp.int8, **vma_kw),
            jax.ShapeDtypeStruct((n, 1), jnp.float32, **vma_kw),
        )
        return pl.pallas_call(
            _adaptive_kernel,
            grid=grid,
            in_specs=[row_spec, row_spec],
            out_specs=(row_spec, scale_spec),
            out_shape=out_shape,
            interpret=interpret,
            name="int8_quantize_blocks_adaptive",
        )(y, noise)
    step_arr = jnp.reshape(jnp.asarray(fixed_step, jnp.float32), (1,))
    y, noise, step_arr = _align_vma(y, noise, step_arr)
    vma_kw = _out_vma(y, noise, step_arr)
    out_shape = (
        jax.ShapeDtypeStruct((n, b), jnp.int8, **vma_kw),
        jax.ShapeDtypeStruct((n, 1), jnp.float32, **vma_kw),
    )
    return pl.pallas_call(
        _fixed_kernel,
        grid=grid,
        in_specs=[row_spec, row_spec,
                  SMEM_SCALARS],
        out_specs=(row_spec, scale_spec),
        out_shape=out_shape,
        interpret=interpret,
        name="int8_quantize_blocks",
    )(y, noise, step_arr)


@functools.partial(jax.jit, static_argnames=("interpret", "row_offset",
                                             "n_rows"))
def quantize_payload_pallas(y: jax.Array, noise: jax.Array,
                            fixed_step: jax.Array | None = None,
                            interpret: bool | None = None,
                            row_offset: int = 0,
                            n_rows: int | None = None):
    """Fused quantize-to-wire: (n_blocks, BLOCK) f32 -> (n_blocks,
    BLOCK + SCALE_BYTES) uint8 payload (int8 codes || fp32 scale bytes).

    One launch emits the exact byte buffer the ring ``ppermute`` moves —
    no separate codes/scales materialization or concat pass.  Bit-identical
    to ``pack_payload(*quantize_blocks_ref(y, noise, fixed_step))``.

    Chunk view (the pipelined exchange): static ``row_offset``/``n_rows``
    restrict the launch to one tile-aligned row range of full-height
    operands — the grid's BlockSpec index maps read the chunk straight out
    of the persistent packed buffers (no sliced copy), emitting only that
    chunk's ``(n_rows, BLOCK+4)`` payload.  Rows are whole quantization
    blocks, so the chunk payload is bit-identical to the same rows of the
    whole-buffer launch.
    """
    if interpret is None:
        interpret = default_interpret()
    n_full, b = y.shape
    assert b % 128 == 0, f"block {b} must be lane-aligned (x128)"
    n, tile_off = _chunk_view(n_full, n_rows, row_offset)
    grid = (n // TILE_N,)
    y_spec = pl.BlockSpec((TILE_N, b), _row_index_map(y.shape[0], n, tile_off))
    noise_spec = pl.BlockSpec((TILE_N, b),
                              _row_index_map(noise.shape[0], n, tile_off))
    payload_spec = pl.BlockSpec((TILE_N, b + SCALE_BYTES), lambda i: (i, 0))
    if fixed_step is None:
        y, noise = _align_vma(y, noise)
        vma_kw = _out_vma(y, noise)
        return pl.pallas_call(
            _payload_adaptive_kernel,
            grid=grid,
            in_specs=[y_spec, noise_spec],
            out_specs=payload_spec,
            out_shape=jax.ShapeDtypeStruct((n, b + SCALE_BYTES), jnp.uint8,
                                           **vma_kw),
            interpret=interpret,
            name="int8_encode_adaptive",
        )(y, noise)
    step_arr = jnp.reshape(jnp.asarray(fixed_step, jnp.float32), (1,))
    y, noise, step_arr = _align_vma(y, noise, step_arr)
    vma_kw = _out_vma(y, noise, step_arr)
    return pl.pallas_call(
        _payload_fixed_kernel,
        grid=grid,
        in_specs=[y_spec, noise_spec, SMEM_SCALARS],
        out_specs=payload_spec,
        out_shape=jax.ShapeDtypeStruct((n, b + SCALE_BYTES), jnp.uint8,
                                       **vma_kw),
        interpret=interpret,
        name="int8_encode",
    )(y, noise, step_arr)
