"""Pallas TPU kernel: fused dequantize + x_tilde integrate + ring combine.

The receive side of the ADC-DGD exchange.  Per parameter-shard block row:

    x_tilde' = x_tilde + deamp * codes_self * scale_self
    m_agg'   = m_agg  + w_side * deamp * (dec(left) + dec(right))
    combined = w_self * x_tilde' + m_agg'

Unfused, this is 3 int8 dequant reads + 2 fp32 state updates + 1 weighted
combine = 8 HBM round trips over the full parameter shard; fused it is one
pass (3 int8 + 2 fp32 reads, 3 fp32 writes) — the memory-roofline win is
~2.2x on the consensus step (see EXPERIMENTS.md §Perf).

TPU mapping: pure VPU elementwise tile (TILE_N, BLOCK) fp32 = 64 KiB in
VMEM x 5 operands + 3 results; int8 tiles in (32, 128) packing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .quantize import (BLOCK, SCALE_BYTES, SMEM_SCALARS, TILE_N, _align_vma,
                       _chunk_view, _out_vma, _row_index_map,
                       default_interpret)

__all__ = ["dequant_combine_pallas", "dequant_combine_payload_pallas"]


def _kernel(w_ref, cs_ref, ss_ref, cl_ref, sl_ref, cr_ref, sr_ref,
            xt_ref, m_ref, xt_out_ref, m_out_ref, comb_ref):
    w_self = w_ref[0]
    w_side = w_ref[1]
    deamp = w_ref[2]
    d_self = cs_ref[...].astype(jnp.float32) * ss_ref[...]
    d_l = cl_ref[...].astype(jnp.float32) * sl_ref[...]
    d_r = cr_ref[...].astype(jnp.float32) * sr_ref[...]
    x_t = xt_ref[...] + deamp * d_self
    m = m_ref[...] + w_side * deamp * (d_l + d_r)
    xt_out_ref[...] = x_t
    m_out_ref[...] = m
    comb_ref[...] = w_self * x_t + m


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequant_combine_pallas(codes_self, scale_self, codes_left, scale_left,
                           codes_right, scale_right, x_tilde, m_agg,
                           w_self, w_side, deamp,
                           interpret: bool | None = None):
    """All array args (n_blocks, BLOCK) / scales (n_blocks, 1).

    Returns (x_tilde', m_agg', combined).
    """
    if interpret is None:
        interpret = default_interpret()
    n, b = x_tilde.shape
    assert n % TILE_N == 0 and b % 128 == 0, (n, b)
    grid = (n // TILE_N,)
    row = pl.BlockSpec((TILE_N, b), lambda i: (i, 0))
    scal = pl.BlockSpec((TILE_N, 1), lambda i: (i, 0))
    w = jnp.stack([jnp.asarray(w_self, jnp.float32),
                   jnp.asarray(w_side, jnp.float32),
                   jnp.asarray(deamp, jnp.float32)])
    (w, codes_self, scale_self, codes_left, scale_left, codes_right,
     scale_right, x_tilde, m_agg) = _align_vma(
        w, codes_self, scale_self, codes_left, scale_left, codes_right,
        scale_right, x_tilde, m_agg)
    vma_kw = _out_vma(w, codes_self, x_tilde)
    out_shape = tuple(jax.ShapeDtypeStruct((n, b), jnp.float32, **vma_kw)
                      for _ in range(3))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[SMEM_SCALARS,
                  row, scal, row, scal, row, scal, row, row],
        out_specs=(row, row, row),
        out_shape=out_shape,
        interpret=interpret,
        name="int8_dequant_combine_blocks",
    )(w, codes_self, scale_self, codes_left, scale_left, codes_right,
      scale_right, x_tilde, m_agg)


def _payload_scales(payload, block):
    """(rows, block+4) uint8 wire rows -> their fp32 scales (rows, 1).

    Decoded in XLA, outside the kernel: a kernel that reads the four
    trailer bytes of a (TILE_N, block+4) uint8 tile gets bytes of other
    rows on a TPU v5e, and the scales are only 4 of each row's block+4
    bytes."""
    rows = payload.shape[0]
    return jax.lax.bitcast_convert_type(
        payload[:, block:].reshape(rows, 1, SCALE_BYTES), jnp.float32)


def _payload_kernel(w_ref, ps_ref, ss_ref, pl_ref, sl_ref, pr_ref, sr_ref,
                    xt_ref, m_ref, xt_out_ref, m_out_ref, comb_ref):
    block = xt_ref.shape[1]

    def decode(p_ref, s_ref):
        # codes are a same-width bitcast view of the payload's first bytes
        codes = jax.lax.bitcast_convert_type(p_ref[...][:, :block], jnp.int8)
        return codes.astype(jnp.float32) * s_ref[...]

    w_self = w_ref[0]
    w_side = w_ref[1]
    deamp = w_ref[2]
    x_t = xt_ref[...] + deamp * decode(ps_ref, ss_ref)
    m = m_ref[...] + w_side * deamp * (decode(pl_ref, sl_ref)
                                       + decode(pr_ref, sr_ref))
    xt_out_ref[...] = x_t
    m_out_ref[...] = m
    comb_ref[...] = w_self * x_t + m


@functools.partial(jax.jit, static_argnames=("interpret", "row_offset",
                                             "n_rows"))
def dequant_combine_payload_pallas(payload_self, payload_left, payload_right,
                                   x_tilde, m_agg, w_self, w_side, deamp,
                                   interpret: bool | None = None,
                                   row_offset: int = 0,
                                   n_rows: int | None = None):
    """Payload-view receive side: three (n_blocks, BLOCK+4) uint8 wire
    buffers (self / left / right), packed shadows (n_blocks, BLOCK) f32.

    One fused launch decodes all three payloads (their fp32 scales read
    out in XLA first, :func:`_payload_scales`) and applies the shadow
    update + ring combine for the whole parameter tree.  Returns
    (x_tilde', m_agg', combined).

    Chunk view (the pipelined exchange): static ``row_offset``/``n_rows``
    restrict the launch to one tile-aligned row range.  Operands that are
    already chunk-height (the in-flight payloads off the wire, or a
    resync-rebuilt ``m_agg`` slice) are read from row 0; full-height
    operands (the persistent packed shadows) are read at the chunk offset
    in-kernel via BlockSpec index maps — no sliced shadow copy is ever
    materialized.  Outputs are chunk-height.
    """
    if interpret is None:
        interpret = default_interpret()
    b = x_tilde.shape[1]
    assert b % 128 == 0, b
    n, tile_off = _chunk_view(x_tilde.shape[0], n_rows, row_offset)
    for p in (payload_self, payload_left, payload_right):
        assert p.shape[1] == b + SCALE_BYTES, p.shape
        assert p.shape[0] in (n, x_tilde.shape[0]), (p.shape, n)
    grid = (n // TILE_N,)

    def row(arr):
        return pl.BlockSpec((TILE_N, b),
                            _row_index_map(arr.shape[0], n, tile_off))

    def pay(arr):
        return pl.BlockSpec((TILE_N, b + SCALE_BYTES),
                            _row_index_map(arr.shape[0], n, tile_off))

    def scal(arr):
        return pl.BlockSpec((TILE_N, 1),
                            _row_index_map(arr.shape[0], n, tile_off))

    out_row = pl.BlockSpec((TILE_N, b), lambda i: (i, 0))
    w = jnp.stack([jnp.asarray(w_self, jnp.float32),
                   jnp.asarray(w_side, jnp.float32),
                   jnp.asarray(deamp, jnp.float32)])
    operands = [w]
    in_specs = [SMEM_SCALARS]
    for p in (payload_self, payload_left, payload_right):
        operands += [p, _payload_scales(p, b)]
        in_specs += [pay(p), scal(p)]
    operands += [x_tilde, m_agg]
    in_specs += [row(x_tilde), row(m_agg)]
    operands = _align_vma(*operands)
    vma_kw = _out_vma(*operands)
    out_shape = tuple(jax.ShapeDtypeStruct((n, b), jnp.float32, **vma_kw)
                      for _ in range(3))
    return pl.pallas_call(
        _payload_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=(out_row, out_row, out_row),
        out_shape=out_shape,
        interpret=interpret,
        name="int8_combine",
    )(*operands)
