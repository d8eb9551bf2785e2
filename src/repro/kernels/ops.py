"""Wrappers that launch the Pallas kernels (``use_pallas``) or compute the
jnp oracles.

The public API works on arbitrary 1-D (already flattened + padded) parameter
shards; padding/blocking is handled here so callers (core.distributed) stay
shape-agnostic.  On a TPU a ``use_pallas`` call always runs its compiled
kernel (see :func:`_use_kernel`).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from . import bitpack, ref
from .dequant_combine import (dequant_combine_pallas,
                              dequant_combine_payload_pallas)
from .gqa_decode import TILE_S, gqa_decode_pallas
from .quantize import (BLOCK, SCALE_BYTES, TILE_N, _vma_of, default_interpret,
                       quantize_blocks_pallas, quantize_payload_pallas)

__all__ = ["blockify", "unblockify", "quantize_blocks", "dequant_combine",
           "gqa_decode", "BLOCK", "SCALE_BYTES", "padded_block_rows",
           "payload_width", "pack_payload", "unpack_payload",
           "quantize_payload", "dequant_combine_payload",
           "subbyte_encode_payload", "subbyte_decode_payload",
           "subbyte_decode_combine", "topk_encode_payload",
           "topk_decode_payload", "topk_decode_combine"]


def padded_block_rows(n_elements: int, block: int = BLOCK,
                      tile_n: int = TILE_N) -> int:
    rows = math.ceil(max(n_elements, 1) / block)
    return int(math.ceil(rows / tile_n) * tile_n)


def blockify(flat: jax.Array, block: int = BLOCK) -> jax.Array:
    """1-D -> (n_rows, block) zero-padded, rows padded to TILE_N."""
    n = flat.shape[0]
    rows = padded_block_rows(n, block)
    pad = rows * block - n
    return jnp.pad(flat, (0, pad)).reshape(rows, block)


def unblockify(blocks: jax.Array, n: int) -> jax.Array:
    return blocks.reshape(-1)[:n]


def _use_kernel(use_pallas: bool, *arrays) -> bool:
    """Whether a wrapper launches its Pallas kernel.

    Compiled (on a TPU) the kernel always runs when ``use_pallas`` asks for
    it.  Off the TPU the kernels run in interpret mode, whose executor
    cannot replay a kernel jaxpr on vma-typed values (inside
    ``shard_map(check_vma=True)``); there, and only there, the wrapper
    computes the bit-identical jnp reference instead."""
    if not use_pallas:
        return False
    return not default_interpret() or not any(_vma_of(a) for a in arrays)


def quantize_blocks(y_blocks: jax.Array, noise: jax.Array,
                    fixed_step=None, use_pallas: bool = False):
    """(rows, BLOCK) f32 -> (codes int8, scales f32 (rows,1))."""
    if _use_kernel(use_pallas, y_blocks, noise):
        return quantize_blocks_pallas(y_blocks, noise, fixed_step=fixed_step,
                                      interpret=default_interpret())
    return ref.quantize_blocks_ref(y_blocks, noise, fixed_step=fixed_step)


# ---------------------------------------------------------------------------
# Flat wire payload (codes + scales in ONE byte buffer per ring direction)
# ---------------------------------------------------------------------------

def payload_width(block: int = BLOCK) -> int:
    """Bytes per payload row: BLOCK int8 codes + one fp32 scale."""
    return block + SCALE_BYTES


def pack_payload(codes: jax.Array, scales: jax.Array) -> jax.Array:
    """(rows, B) int8 codes + (rows, 1) f32 scales -> (rows, B+4) uint8.

    The single wire buffer the ring exchanges: one ``ppermute`` per ring
    direction moves the codes AND the scales for the whole parameter tree.
    Scale bytes are the host-endian fp32 image (least-significant byte
    first under XLA's bitcast; the Pallas kernels decode with the same
    order — pinned by ``test_payload_byte_order``).
    """
    rows = codes.shape[0]
    cu = jax.lax.bitcast_convert_type(codes, jnp.uint8)
    su = jax.lax.bitcast_convert_type(scales, jnp.uint8)
    return jnp.concatenate([cu, su.reshape(rows, SCALE_BYTES)], axis=1)


def unpack_payload(payload: jax.Array, block: int = BLOCK):
    """(rows, B+4) uint8 -> (codes int8 (rows, B), scales f32 (rows, 1))."""
    rows = payload.shape[0]
    assert payload.shape[1] == payload_width(block), payload.shape
    codes = jax.lax.bitcast_convert_type(payload[:, :block], jnp.int8)
    scales = jax.lax.bitcast_convert_type(
        payload[:, block:].reshape(rows, 1, SCALE_BYTES), jnp.float32)
    return codes, scales


def _chunk_rows(a: jax.Array, row_offset: int, n_rows: int | None):
    """Static chunk slice of a full-height operand (ref-path counterpart of
    the kernels' BlockSpec chunk view); chunk-height operands pass through."""
    if n_rows is None or a.shape[0] == n_rows:
        return a
    return jax.lax.slice_in_dim(a, row_offset, row_offset + n_rows, axis=0)


def _tile_view(row_offset: int, n_rows: int | None, *arrays):
    """Kernel operands for a chunk view: ``(arrays, kwargs, n)``.

    A TILE_N-aligned view passes through with its static ``row_offset`` /
    ``n_rows`` (the kernels read it in place via BlockSpec index maps).  An
    unaligned one — mixed WirePlans cut codec runs at leaf boundaries — is
    sliced out and zero-padded to whole tiles; rows are independent, so the
    caller keeps the first ``n`` result rows and they are bit-identical to
    an in-place launch."""
    n = arrays[0].shape[0] if n_rows is None else n_rows
    if row_offset % TILE_N == 0 and n % TILE_N == 0 and n > 0:
        return arrays, dict(row_offset=row_offset, n_rows=n_rows), n
    pad = -n % TILE_N
    arrays = tuple(jnp.pad(_chunk_rows(a, row_offset, n), ((0, pad), (0, 0)))
                   for a in arrays)
    return arrays, {}, n


def _head(out, n: int):
    """The first ``n`` rows of a kernel result (or of each of a tuple)."""
    if isinstance(out, tuple):
        return tuple(_head(o, n) for o in out)
    return out if out.shape[0] == n else out[:n]


def _noise_lead(noise: jax.Array, cols: int) -> jax.Array:
    """The leading noise columns a codec consumes: mixed WirePlans share
    one noise buffer sized for the plan's widest codec; the jnp refs need
    the exact column count (the Pallas launches read the leading columns
    in place via their BlockSpecs)."""
    if noise.shape[1] == cols:
        return noise
    return jax.lax.slice_in_dim(noise, 0, cols, axis=1)


def quantize_payload(y_blocks: jax.Array, noise: jax.Array,
                     fixed_step=None, use_pallas: bool = False,
                     row_offset: int = 0,
                     n_rows: int | None = None) -> jax.Array:
    """One quantize launch for the whole packed shard, emitting the wire
    payload directly: (rows, BLOCK) f32 -> (rows, BLOCK+4) uint8.

    Static ``row_offset``/``n_rows`` select one tile-aligned chunk of the
    full-height operands (the pipelined exchange unit): the Pallas path
    reads the chunk in-kernel via BlockSpec index offsets, the jnp path
    takes a static slice; both emit only the chunk's payload rows."""
    if _use_kernel(use_pallas, y_blocks, noise):
        (y_blocks, noise), view, n = _tile_view(row_offset, n_rows,
                                                y_blocks, noise)
        return _head(quantize_payload_pallas(
            y_blocks, noise, fixed_step=fixed_step,
            interpret=default_interpret(), **view), n)
    codes, scales = ref.quantize_blocks_ref(
        _chunk_rows(y_blocks, row_offset, n_rows),
        _chunk_rows(_noise_lead(noise, y_blocks.shape[1]), row_offset,
                    n_rows), fixed_step=fixed_step)
    return pack_payload(codes, scales)


# ---------------------------------------------------------------------------
# Sub-byte / top-k wire codecs (kernels/bitpack.py; DESIGN.md §Wire codecs)
# ---------------------------------------------------------------------------

def subbyte_encode_payload(y_blocks: jax.Array, noise: jax.Array,
                           code_bits: int, fixed_step=None,
                           use_pallas: bool = False, row_offset: int = 0,
                           n_rows: int | None = None) -> jax.Array:
    """Bit-packed sub-byte quantize-to-wire: (rows, BLOCK) f32 ->
    (rows, BLOCK // (8 // code_bits) + 2) uint8 (packed codes || bf16
    scale).  Same chunk-view contract as :func:`quantize_payload`."""
    if _use_kernel(use_pallas, y_blocks, noise):
        (y_blocks, noise), view, n = _tile_view(row_offset, n_rows,
                                                y_blocks, noise)
        return _head(bitpack.subbyte_encode_pallas(
            y_blocks, noise, code_bits, fixed_step=fixed_step,
            interpret=default_interpret(), **view), n)
    return bitpack.subbyte_encode_ref(
        _chunk_rows(y_blocks, row_offset, n_rows),
        _chunk_rows(_noise_lead(noise, y_blocks.shape[1]), row_offset,
                    n_rows), code_bits, fixed_step=fixed_step)


def subbyte_decode_payload(payload: jax.Array, code_bits: int,
                           block: int = BLOCK) -> jax.Array:
    """Payload rows -> dequantized (rows, BLOCK) f32 (jnp path; tests,
    overflow accounting and offline tools — the hot path decodes in-kernel
    via :func:`subbyte_decode_combine`)."""
    return bitpack.subbyte_decode_ref(payload, block, code_bits)


def _decode_combine_ref(decode, payloads, x_tilde, m_agg, w_self, w_side,
                        deamp, row_offset, n_rows):
    """Shared jnp fallback for the codec receive sides: decode the three
    (chunk views of the) wire buffers and run the fused combine core."""
    block = x_tilde.shape[1]
    d_s, d_l, d_r = (decode(_chunk_rows(p, row_offset, n_rows), block)
                     for p in payloads)
    return bitpack.combine_core(
        d_s, d_l, d_r, _chunk_rows(x_tilde, row_offset, n_rows),
        _chunk_rows(m_agg, row_offset, n_rows),
        jnp.asarray(w_self, jnp.float32), jnp.asarray(w_side, jnp.float32),
        jnp.asarray(deamp, jnp.float32))


def subbyte_decode_combine(payload_self, payload_left, payload_right,
                           x_tilde, m_agg, w_self, w_side, deamp,
                           code_bits: int, use_pallas: bool = False,
                           row_offset: int = 0, n_rows: int | None = None):
    """Sub-byte receive side (unpack + shadow update + combine fused);
    same chunk-view contract as :func:`dequant_combine_payload`."""
    if _use_kernel(use_pallas, payload_self, x_tilde, m_agg):
        ops, view, n = _tile_view(row_offset, n_rows, x_tilde, m_agg,
                                  payload_self, payload_left, payload_right)
        return _head(bitpack.subbyte_combine_pallas(
            *ops[2:], *ops[:2], w_self, w_side, deamp, code_bits,
            interpret=default_interpret(), **view), n)
    return _decode_combine_ref(
        lambda p, b: bitpack.subbyte_decode_ref(p, b, code_bits),
        (payload_self, payload_left, payload_right), x_tilde, m_agg,
        w_self, w_side, deamp, row_offset, n_rows)


def topk_encode_payload(y_blocks: jax.Array, noise: jax.Array, k: int,
                        fixed_step=None, use_pallas: bool = False,
                        row_offset: int = 0,
                        n_rows: int | None = None) -> jax.Array:
    """Top-k sparse quantize-to-wire: (rows, BLOCK) f32 + (rows, 2*BLOCK)
    noise -> (rows, BLOCK // 8 + k + 2) uint8 (bitmap || int8 values ||
    bf16 scale).  Noise columns [0, BLOCK) drive the magnitude-proportional
    selection, [BLOCK, BLOCK + k) the value rounding."""
    if _use_kernel(use_pallas, y_blocks, noise):
        (y_blocks, noise), view, n = _tile_view(row_offset, n_rows,
                                                y_blocks, noise)
        return _head(bitpack.topk_encode_pallas(
            y_blocks, noise, k, fixed_step=fixed_step,
            interpret=default_interpret(), **view), n)
    return bitpack.topk_encode_ref(
        _chunk_rows(y_blocks, row_offset, n_rows),
        _chunk_rows(_noise_lead(noise, 2 * y_blocks.shape[1]), row_offset,
                    n_rows), k, fixed_step=fixed_step)


def topk_decode_payload(payload: jax.Array, k: int,
                        block: int = BLOCK) -> jax.Array:
    """Sparse payload rows -> dense (rows, BLOCK) f32 (jnp path)."""
    return bitpack.topk_decode_ref(payload, block, k)


def topk_decode_combine(payload_self, payload_left, payload_right,
                        x_tilde, m_agg, w_self, w_side, deamp, k: int,
                        use_pallas: bool = False, row_offset: int = 0,
                        n_rows: int | None = None):
    """Top-k receive side (bitmap scatter + shadow update + combine fused);
    same chunk-view contract as :func:`dequant_combine_payload`."""
    if _use_kernel(use_pallas, payload_self, x_tilde, m_agg):
        ops, view, n = _tile_view(row_offset, n_rows, x_tilde, m_agg,
                                  payload_self, payload_left, payload_right)
        return _head(bitpack.topk_combine_pallas(
            *ops[2:], *ops[:2], w_self, w_side, deamp, k,
            interpret=default_interpret(), **view), n)
    return _decode_combine_ref(
        lambda p, b: bitpack.topk_decode_ref(p, b, k),
        (payload_self, payload_left, payload_right), x_tilde, m_agg,
        w_self, w_side, deamp, row_offset, n_rows)


def gqa_decode(q, k, v, valid, softcap=None, use_pallas: bool = False):
    """Flash-decode partials (m, l, acc) over a KV-cache shard.

    q: (b, kvh, g, hd); k/v: (b, S, kvh, hd); valid: (S,).  The Pallas path
    pads S to a TILE_S multiple with invalid positions, which add exactly
    nothing to the partials."""
    if _use_kernel(use_pallas, q, k, v):
        pad = -k.shape[1] % TILE_S
        if pad:
            k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    for a in (k, v))
            valid = jnp.pad(valid, (0, pad))
        return gqa_decode_pallas(q, k, v, valid, softcap=softcap,
                                 interpret=default_interpret())
    return ref.gqa_decode_ref(q, k, v, valid, softcap=softcap)


def dequant_combine(codes_self, scale_self, codes_left, scale_left,
                    codes_right, scale_right, x_tilde, m_agg,
                    w_self, w_side, deamp, use_pallas: bool = False):
    if _use_kernel(use_pallas, codes_self, x_tilde, m_agg):
        return dequant_combine_pallas(
            codes_self, scale_self, codes_left, scale_left, codes_right,
            scale_right, x_tilde, m_agg, w_self, w_side, deamp,
            interpret=default_interpret())
    return ref.dequant_combine_ref(
        codes_self, scale_self, codes_left, scale_left, codes_right,
        scale_right, x_tilde, m_agg, w_self, w_side, deamp)


def dequant_combine_payload(payload_self, payload_left, payload_right,
                            x_tilde, m_agg, w_self, w_side, deamp,
                            use_pallas: bool = False,
                            row_offset: int = 0, n_rows: int | None = None):
    """Payload-view dequant+combine: the three (rows, BLOCK+4) uint8 wire
    buffers are decoded (scales region decoded in-kernel on the Pallas
    path) and fused with the packed shadow update — ONE launch for the
    whole parameter tree.  Returns (x_tilde', m_agg', combined).

    Static ``row_offset``/``n_rows`` select one tile-aligned chunk (the
    pipelined exchange unit): chunk-height operands (in-flight payloads, a
    resync-rebuilt m_agg slice) are used as-is, full-height persistent
    shadows are viewed at the chunk offset; all three results come back
    chunk-height."""
    if _use_kernel(use_pallas, payload_self, x_tilde, m_agg):
        ops, view, n = _tile_view(row_offset, n_rows, x_tilde, m_agg,
                                  payload_self, payload_left, payload_right)
        return _head(dequant_combine_payload_pallas(
            *ops[2:], *ops[:2], w_self, w_side, deamp,
            interpret=default_interpret(), **view), n)
    block = x_tilde.shape[1]
    cs, ss = unpack_payload(_chunk_rows(payload_self, row_offset, n_rows),
                            block)
    cl, sl = unpack_payload(_chunk_rows(payload_left, row_offset, n_rows),
                            block)
    cr, sr = unpack_payload(_chunk_rows(payload_right, row_offset, n_rows),
                            block)
    return ref.dequant_combine_ref(
        cs, ss, cl, sl, cr, sr, _chunk_rows(x_tilde, row_offset, n_rows),
        _chunk_rows(m_agg, row_offset, n_rows), w_self, w_side, deamp)
