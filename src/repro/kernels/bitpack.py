"""Pallas TPU kernels: sub-byte bit-packed and top-k sparse wire codecs.

The int8 payload path (quantize.py / dequant_combine.py) ships 8 bits per
element + 4 scale bytes per block row.  This module implements the payload
families below it on the bandwidth ladder (DESIGN.md §Wire codecs):

* **sub-byte dense** (``int4`` / ``int2``): stochastic rounding to a
  ``2^bits``-level grid, codes bit-packed ``8 // bits`` per byte inside the
  kernel, unpacked in-kernel on the receive side.  Per payload row:
  ``BLOCK // pack`` code bytes + 2 scale bytes.
* **top-k sparse** (``topk``): per block row, BLOCK elements are split into
  ``k`` strata of ``BLOCK // k``; each stratum transmits exactly ONE element,
  chosen magnitude-proportionally (exponential-race / Gumbel trick on the
  caller-provided uniform noise) and scaled by its inverse selection
  probability — an unbiased sparsifier (paper Definition 1) with a *static*
  payload: a BLOCK-bit selection bitmap + k int8 values + 2 scale bytes.

Scales for both families are quantized to **bf16 BEFORE stochastic
rounding**, so the grid the receiver reconstructs from the 2 scale bytes is
bit-exactly the grid the sender rounded on — unbiasedness survives the
lossy scale (E[code] * decoded_scale == y).  fp32 scales would put int4 at
only 1.98x under int8; bf16 makes the dense ladder exactly {1x, 2x, 3.97x}.

Every transformation is per block row, so any TILE_N-aligned row split is
bit-identical to the whole-buffer launch — the same chunk-view discipline
(static ``row_offset``/``n_rows`` BlockSpec views over full-height packed
operands) as the int8 kernels, reused verbatim.

The jnp reference path and the Pallas kernels share the *same* core
functions (`_subbyte_encode_core` etc.), so ref == interpret == compiled is
structural, not a re-derivation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quantize import (BLOCK, SMEM_SCALARS, TILE_N, _align_vma, _chunk_view,
                       _out_vma, _row_index_map, default_interpret)

__all__ = [
    "SUB_SCALE_BYTES", "subbyte_code_max", "subbyte_pack",
    "subbyte_payload_width", "topk_payload_width",
    "subbyte_encode_ref", "subbyte_decode_ref",
    "topk_encode_ref", "topk_decode_ref",
    "combine_core", "subbyte_encode_pallas", "subbyte_combine_pallas",
    "topk_encode_pallas", "topk_combine_pallas",
]

SUB_SCALE_BYTES = 2   # bf16 scale image appended to each payload row


# ---------------------------------------------------------------------------
# static payload geometry
# ---------------------------------------------------------------------------

def subbyte_code_max(code_bits: int) -> int:
    """Symmetric code range for a b-bit field: +-(2^(b-1) - 1)."""
    return (1 << (code_bits - 1)) - 1


def subbyte_pack(code_bits: int) -> int:
    """Codes per payload byte."""
    assert 8 % code_bits == 0, code_bits
    return 8 // code_bits


def subbyte_payload_width(block: int, code_bits: int) -> int:
    """Bytes per payload row: packed codes + bf16 scale."""
    return block // subbyte_pack(code_bits) + SUB_SCALE_BYTES


def topk_payload_width(block: int, k: int) -> int:
    """Bytes per payload row: selection bitmap + k int8 values + bf16 scale."""
    return block // 8 + k + SUB_SCALE_BYTES


# ---------------------------------------------------------------------------
# shared math (used by BOTH the jnp refs and the Pallas kernels)
# ---------------------------------------------------------------------------

def _bf16_round(scale):
    """Round the per-row scale to bf16 precision (the wire precision) BEFORE
    it is used for rounding — encode and decode then share one exact grid.

    Round-to-nearest-even on the f32 bit pattern, in int32: XLA on a TPU
    may drop an f32 -> bf16 -> f32 convert pair inside a fusion (excess
    precision), which left the reference's scales unrounded while the
    kernels' were rounded.  Integer ops are computed as written everywhere.
    Finite inputs."""
    u = jax.lax.bitcast_convert_type(scale, jnp.int32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & -0x10000
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _sr_clip(s, noise, code_max):
    """Stochastic round + clip to the symmetric code range."""
    lo = jnp.floor(s)
    frac = s - lo
    q = lo + (noise < frac).astype(jnp.float32)
    return jnp.clip(q, -float(code_max), float(code_max))


def _row_scale(y, step, code_max):
    """Per-row grid step: adaptive absmax/code_max when ``step`` is None,
    else the broadcast fixed step; bf16-rounded either way.

    Adaptive scales are rounded UP to bf16: round-to-nearest can land below
    ``absmax / code_max``, which would deterministically clip each row's
    max element — a bias the adaptive grid promises not to have (the int8
    path's never-clips invariant).  Rows whose nearest bf16 fell short are
    bumped one bf16 ulp (``* (1 + 2^-7)`` moves any bf16 strictly to the
    next representable).  Fixed-mode clipping stays the monitored,
    paper-faithful behavior (§IV-D), exactly like the int8 kernels.
    """
    if step is None:
        absmax = jnp.max(jnp.abs(y), axis=-1, keepdims=True)
        scale = jnp.maximum(absmax, 1e-30) * jnp.float32(1.0 / code_max)
        s_near = _bf16_round(scale)
        s_up = _bf16_round(s_near * jnp.float32(1.0 + 2.0 ** -7))
        return jnp.where(s_near < scale, s_up, s_near)
    return _bf16_round(jnp.broadcast_to(step, (y.shape[0], 1)))


# Mosaic lowers no float->uint cast, no reduction over unsigned integers
# and no reshape that splits the lane dimension.  Byte fields are therefore
# formed in f32/int32 (every value fits in the low byte, so the payload
# bytes are the same), lane groups are reduced by an XOR butterfly of lane
# rotations, and groups of lanes are compacted by one bf16 MXU dot inside a
# kernel (``kernel=True``) or by a reshape-sum in XLA.

def _bytes_to_i32(code_bytes):
    """uint8 -> int32 in [0, 255] (same-width bitcast, sign-extend, mask)."""
    return jax.lax.bitcast_convert_type(
        code_bytes, jnp.int8).astype(jnp.int32) & 0xFF


def _expand_groups(x, g):
    """(R, n) -> (R, n * g): each lane repeated over a group of g lanes."""
    r, n = x.shape
    return jnp.broadcast_to(x[:, :, None], (r, n, g)).reshape(r, n * g)


def _lane_roll(x, shift, kernel):
    """``jnp.roll(x, shift, axis=-1)``; a native lane rotation in a kernel."""
    if kernel:
        return pltpu.roll(x, shift % x.shape[-1], x.ndim - 1)
    return jnp.roll(x, shift, axis=-1)


def _group_reduce(x, g, op, kernel):
    """``op``-reduce over aligned groups of ``g`` (a power of two)
    contiguous lanes, the result broadcast to every lane of its group.

    XOR butterfly: at distance d each lane combines with lane ``l ^ d``.
    Both partners compute ``op(a, b)`` / ``op(b, a)``, so for a commutative
    ``op`` every lane of a group ends with the same, deterministic value."""
    assert g & (g - 1) == 0, f"group {g} is not a power of two"
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    d = 1
    while d < g:
        partner = jnp.where((lane & d) == 0, _lane_roll(x, -d, kernel),
                            _lane_roll(x, d, kernel))
        x = op(x, partner)
        d *= 2
    return x


def _compact_groups(x, g, shift_bits, kernel):
    """(R, B) integer-valued f32 -> (R, B // g) f32: each aligned group of
    ``g`` lanes summed into one, lane j of a group weighted by
    ``2 ** (shift_bits * j)``.  Exact for the byte-sized sums the codecs
    form (bf16 holds every operand, f32 every partial sum)."""
    r, b = x.shape
    if kernel:
        row = jax.lax.broadcasted_iota(jnp.int32, (b, b // g), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (b, b // g), 1)
        weight = (1 << (shift_bits * (row % g))).astype(jnp.float32)
        sel = jnp.where(row // g == col, weight, 0.0)
        return jnp.dot(x.astype(jnp.bfloat16), sel.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    weight = jnp.asarray([2.0 ** (shift_bits * j) for j in range(g)],
                         jnp.float32)
    return jnp.sum(x.reshape(r, b // g, g) * weight, axis=-1)


def _to_u8(byte_values):
    """Exact byte values in [0, 255] (f32) -> uint8."""
    return byte_values.astype(jnp.int32).astype(jnp.uint8)


def _pack_fields(q, code_max, pack, kernel):
    """(R, B) float codes in [-code_max, code_max] -> (R, B // pack) uint8.

    Codes are biased to the unsigned field ``code + code_max + 1`` (always
    >= 1, so a zero byte never aliases a valid all-zero-code group only when
    codes are 0 -> field mid-range; the bias is purely a fixed offset) and
    ``pack`` consecutive fields are shifted into one byte, low code first.
    """
    return _to_u8(_compact_groups(q + float(code_max + 1), pack, 8 // pack,
                                  kernel))


def _unpack_fields(code_bytes, code_max, pack):
    """(R, B // pack) uint8 -> (R, B) f32 codes (inverse of _pack_fields)."""
    width = 8 // pack
    lane = jax.lax.broadcasted_iota(
        jnp.int32, (1, code_bytes.shape[1] * pack), 1)
    fields = ((_expand_groups(_bytes_to_i32(code_bytes), pack)
               >> (width * (lane % pack))) & ((1 << width) - 1))
    return fields.astype(jnp.float32) - float(code_max + 1)


def _scale_to_bf16_bytes(scale_col):
    """(R, 1) f32 (bf16-exact) -> (R, 2) uint8, least-significant byte first
    (same byte order discipline as the int8 path's fp32 scale image).  A
    bf16-exact f32 carries the bf16 image in its high 16 bits."""
    u = jax.lax.bitcast_convert_type(scale_col, jnp.int32)
    shifts = 16 + jax.lax.broadcasted_iota(
        jnp.int32, (1, SUB_SCALE_BYTES), 1) * 8
    return ((u >> shifts) & 0xFF).astype(jnp.uint8)


def _bf16_bytes_to_scale(scale_bytes):
    """(R, 2) uint8 -> (R, 1) f32 (inverse of _scale_to_bf16_bytes)."""
    b = _bytes_to_i32(scale_bytes)
    u = (b[:, 0:1] << 16) | (b[:, 1:2] << 24)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _pack_bits(bits, kernel):
    """(R, B) {0,1} -> (R, B // 8) uint8, bit j of byte i = element 8i+j."""
    return _to_u8(_compact_groups(bits.astype(jnp.float32), 8, 1, kernel))


def _unpack_bits(bitmap_bytes):
    """(R, B // 8) uint8 -> (R, B) f32 {0, 1}."""
    lane = jax.lax.broadcasted_iota(
        jnp.int32, (1, bitmap_bytes.shape[1] * 8), 1)
    bits = (_expand_groups(_bytes_to_i32(bitmap_bytes), 8) >> (lane % 8)) & 1
    return bits.astype(jnp.float32)


# -- encode / decode cores (one code path for ref AND kernels) --------------

def _subbyte_encode_core(y, noise, step, code_bits, kernel=False):
    """(R, B) f32 + (R, B) uniform noise -> (R, B//pack + 2) uint8 rows."""
    cm = subbyte_code_max(code_bits)
    pack = subbyte_pack(code_bits)
    y = y.astype(jnp.float32)
    scale = _row_scale(y, step, cm)
    q = _sr_clip(y / scale, noise, cm)
    return jnp.concatenate(
        [_pack_fields(q, cm, pack, kernel), _scale_to_bf16_bytes(scale)],
        axis=1)


def _subbyte_decode_core(payload, block, code_bits):
    """(R, W+2) uint8 payload rows -> (R, B) f32 dequantized values."""
    cm = subbyte_code_max(code_bits)
    pack = subbyte_pack(code_bits)
    w = block // pack
    codes = _unpack_fields(payload[:, :w], cm, pack)
    scale = _bf16_bytes_to_scale(payload[:, w:])
    return codes * scale


def _topk_encode_core(y, noise, step, k, kernel=False):
    """(R, B) f32 + (R, 2B) noise (cols [0,B) selection, [B, B+k) rounding)
    -> (R, B//8 + k + 2) uint8 rows: bitmap || int8 values || bf16 scale.

    Magnitude-proportional one-per-stratum selection: each row splits into
    k strata of g = B // k contiguous elements, and each stratum transmits
    exactly ONE element, picked by the exponential race
    ``argmin_i  -log(u_i) / w_i`` with weights ``w_i = |y_i| + eps`` —
    P(pick i) = w_i / sum_stratum(w) exactly, so the transmitted value
    ``y_i / p_i = y_i * sum(w) / w_i`` is an unbiased estimate of the
    stratum (inverse-probability scaling).  Ties in the race keys (only
    possible through float collisions) break to the lowest index.  Every
    stratum reduction runs over lane groups in place (``_group_reduce``):
    the selected value sits at its lane, zeros elsewhere, until the codes
    are compacted to k bytes.
    """
    r, b = y.shape
    g = b // k
    y = y.astype(jnp.float32)
    w = jnp.abs(y) + jnp.float32(1e-30)
    keys = -jnp.log(jnp.maximum(noise[:, :b], jnp.float32(1e-37))) / w
    kmin = _group_reduce(keys, g, jnp.minimum, kernel)
    idx = jax.lax.broadcasted_iota(jnp.int32, (r, b), 1) % g
    sel = _group_reduce(jnp.where(keys <= kmin, idx, g), g, jnp.minimum,
                        kernel)
    onehot = idx == sel
    wsum = _group_reduce(w, g, jnp.add, kernel)
    v = jnp.where(onehot, y * (wsum / w), 0.0)
    scale = _row_scale(v, step, 127)
    q = _sr_clip(v / scale, _expand_groups(noise[:, b:b + k], g), 127)
    vals = _compact_groups(q, g, 0, kernel).astype(jnp.int8)
    return jnp.concatenate(
        [_pack_bits(onehot, kernel),
         jax.lax.bitcast_convert_type(vals, jnp.uint8),
         _scale_to_bf16_bytes(scale)], axis=1)


def _topk_decode_core(payload, block, k):
    """(R, B//8 + k + 2) uint8 payload rows -> (R, B) f32 (dense, zeros at
    unselected positions)."""
    wb = block // 8
    bits = _unpack_bits(payload[:, :wb])
    codes = jax.lax.bitcast_convert_type(
        payload[:, wb:wb + k], jnp.int8).astype(jnp.float32)
    scale = _bf16_bytes_to_scale(payload[:, wb + k:])
    return bits * _expand_groups(codes * scale, block // k)


def combine_core(d_self, d_l, d_r, xt, m, w_self, w_side, deamp):
    """The fused receive-side update shared with the int8 path:
    x_tilde' = x_tilde + deamp * d_self;  m' = m + w_side*deamp*(d_l + d_r);
    combined = w_self * x_tilde' + m'."""
    x_t = xt + deamp * d_self
    m2 = m + w_side * deamp * (d_l + d_r)
    return x_t, m2, w_self * x_t + m2


# ---------------------------------------------------------------------------
# jnp reference path (production fallback off-TPU; the oracle for tests)
# ---------------------------------------------------------------------------

def _as_step(fixed_step):
    if fixed_step is None:
        return None
    return jnp.asarray(fixed_step, jnp.float32)


def subbyte_encode_ref(y, noise, code_bits, fixed_step=None):
    return _subbyte_encode_core(y, noise, _as_step(fixed_step), code_bits)


def subbyte_decode_ref(payload, block, code_bits):
    return _subbyte_decode_core(payload, block, code_bits)


def topk_encode_ref(y, noise, k, fixed_step=None):
    return _topk_encode_core(y, noise, _as_step(fixed_step), k)


def topk_decode_ref(payload, block, k):
    return _topk_decode_core(payload, block, k)


# ---------------------------------------------------------------------------
# Pallas kernels (same cores, tiled TILE_N rows per grid step)
# ---------------------------------------------------------------------------

def _encode_pallas(name, core, width, noise_cols, y, noise, fixed_step,
                   interpret, row_offset, n_rows):
    """Shared encode launch: grid over TILE_N-row tiles of a (chunk view
    of a) full-height (n, B) operand pair, emitting (n, width) uint8;
    ``name`` is the kernel's name in the compiled program and the trace."""
    if interpret is None:
        interpret = default_interpret()
    n_full, b = y.shape
    assert b % 128 == 0, f"block {b} must be lane-aligned (x128)"
    # >= not ==: mixed WirePlans share ONE noise buffer sized for the
    # widest codec in the plan (core.wireplan.noise_cols); the BlockSpec
    # below reads this codec's leading noise_cols columns in place
    assert noise.shape[1] >= noise_cols, (noise.shape, noise_cols)
    n, tile_off = _chunk_view(n_full, n_rows, row_offset)
    grid = (n // TILE_N,)
    y_spec = pl.BlockSpec((TILE_N, b), _row_index_map(y.shape[0], n, tile_off))
    noise_spec = pl.BlockSpec((TILE_N, noise_cols),
                              _row_index_map(noise.shape[0], n, tile_off))
    out_spec = pl.BlockSpec((TILE_N, width), lambda i: (i, 0))
    if fixed_step is None:
        def kernel(y_ref, noise_ref, payload_ref):
            payload_ref[...] = core(y_ref[...], noise_ref[...], None)

        y, noise = _align_vma(y, noise)
        vma_kw = _out_vma(y, noise)
        return pl.pallas_call(
            kernel, grid=grid, in_specs=[y_spec, noise_spec],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((n, width), jnp.uint8, **vma_kw),
            interpret=interpret, name=f"{name}_adaptive",
        )(y, noise)

    def kernel(y_ref, noise_ref, step_ref, payload_ref):
        y_t = y_ref[...].astype(jnp.float32)
        payload_ref[...] = core(y_t, noise_ref[...], step_ref[0])

    step_arr = jnp.reshape(jnp.asarray(fixed_step, jnp.float32), (1,))
    y, noise, step_arr = _align_vma(y, noise, step_arr)
    vma_kw = _out_vma(y, noise, step_arr)
    return pl.pallas_call(
        kernel, grid=grid,
        in_specs=[y_spec, noise_spec, SMEM_SCALARS],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((n, width), jnp.uint8, **vma_kw),
        interpret=interpret, name=name,
    )(y, noise, step_arr)


def _combine_pallas(name, decode, width, payload_self, payload_left,
                    payload_right, x_tilde, m_agg, w_self, w_side, deamp,
                    interpret, row_offset, n_rows):
    """Shared fused decode + shadow-update + combine launch; mirrors the
    int8 ``dequant_combine_payload_pallas`` chunk-view discipline exactly
    (chunk-height in-flight payloads read at row 0, full-height persistent
    shadows viewed at the chunk offset in-kernel)."""
    if interpret is None:
        interpret = default_interpret()
    b = x_tilde.shape[1]
    assert b % 128 == 0, b
    n, tile_off = _chunk_view(x_tilde.shape[0], n_rows, row_offset)
    for p in (payload_self, payload_left, payload_right):
        assert p.shape[1] == width, (p.shape, width)
        assert p.shape[0] in (n, x_tilde.shape[0]), (p.shape, n)
    grid = (n // TILE_N,)

    def row(arr):
        return pl.BlockSpec((TILE_N, b),
                            _row_index_map(arr.shape[0], n, tile_off))

    def pay(arr):
        return pl.BlockSpec((TILE_N, width),
                            _row_index_map(arr.shape[0], n, tile_off))

    out_row = pl.BlockSpec((TILE_N, b), lambda i: (i, 0))

    def kernel(w_ref, ps_ref, pl_ref, pr_ref, xt_ref, m_ref,
               xt_out_ref, m_out_ref, comb_ref):
        d_s = decode(ps_ref[...], b)
        d_l = decode(pl_ref[...], b)
        d_r = decode(pr_ref[...], b)
        x_t, m2, comb = combine_core(d_s, d_l, d_r, xt_ref[...], m_ref[...],
                                      w_ref[0], w_ref[1], w_ref[2])
        xt_out_ref[...] = x_t
        m_out_ref[...] = m2
        comb_ref[...] = comb

    w = jnp.stack([jnp.asarray(w_self, jnp.float32),
                   jnp.asarray(w_side, jnp.float32),
                   jnp.asarray(deamp, jnp.float32)])
    in_specs = [SMEM_SCALARS, pay(payload_self),
                pay(payload_left), pay(payload_right), row(x_tilde),
                row(m_agg)]
    (w, payload_self, payload_left, payload_right, x_tilde, m_agg) = \
        _align_vma(w, payload_self, payload_left, payload_right, x_tilde,
                   m_agg)
    vma_kw = _out_vma(w, payload_self, x_tilde)
    out_shape = tuple(jax.ShapeDtypeStruct((n, b), jnp.float32, **vma_kw)
                      for _ in range(3))
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs,
        out_specs=(out_row, out_row, out_row), out_shape=out_shape,
        interpret=interpret, name=name,
    )(w, payload_self, payload_left, payload_right, x_tilde, m_agg)


@functools.partial(jax.jit, static_argnames=("code_bits", "interpret",
                                             "row_offset", "n_rows"))
def subbyte_encode_pallas(y, noise, code_bits, fixed_step=None,
                          interpret=None, row_offset=0, n_rows=None):
    """(n, B) f32 -> (n, B // pack + 2) uint8 bit-packed payload."""
    return _encode_pallas(
        f"int{code_bits}_encode",
        lambda yt, nt, st: _subbyte_encode_core(yt, nt, st, code_bits,
                                                kernel=True),
        subbyte_payload_width(y.shape[1], code_bits), y.shape[1],
        y, noise, fixed_step, interpret, row_offset, n_rows)


@functools.partial(jax.jit, static_argnames=("code_bits", "interpret",
                                             "row_offset", "n_rows"))
def subbyte_combine_pallas(payload_self, payload_left, payload_right,
                           x_tilde, m_agg, w_self, w_side, deamp, code_bits,
                           interpret=None, row_offset=0, n_rows=None):
    """Sub-byte receive side: unpack codes + bf16 scale in-kernel, fused
    with the shadow update + ring combine.  Returns (x_tilde', m', comb)."""
    return _combine_pallas(
        f"int{code_bits}_combine",
        lambda p, b: _subbyte_decode_core(p, b, code_bits),
        subbyte_payload_width(x_tilde.shape[1], code_bits),
        payload_self, payload_left, payload_right, x_tilde, m_agg,
        w_self, w_side, deamp, interpret, row_offset, n_rows)


@functools.partial(jax.jit, static_argnames=("k", "interpret", "row_offset",
                                             "n_rows"))
def topk_encode_pallas(y, noise, k, fixed_step=None, interpret=None,
                       row_offset=0, n_rows=None):
    """(n, B) f32 + (n, 2B) noise -> (n, B//8 + k + 2) uint8 sparse payload
    (selection bitmap || int8 values || bf16 scale)."""
    return _encode_pallas(
        "topk_encode",
        lambda yt, nt, st: _topk_encode_core(yt, nt, st, k, kernel=True),
        topk_payload_width(y.shape[1], k), 2 * y.shape[1],
        y, noise, fixed_step, interpret, row_offset, n_rows)


@functools.partial(jax.jit, static_argnames=("k", "interpret", "row_offset",
                                             "n_rows"))
def topk_combine_pallas(payload_self, payload_left, payload_right,
                        x_tilde, m_agg, w_self, w_side, deamp, k,
                        interpret=None, row_offset=0, n_rows=None):
    """Top-k receive side: scatter the k values through the bitmap
    in-kernel, fused with the shadow update + ring combine."""
    return _combine_pallas(
        "topk_combine",
        lambda p, b: _topk_decode_core(p, b, k),
        topk_payload_width(x_tilde.shape[1], k),
        payload_self, payload_left, payload_right, x_tilde, m_agg,
        w_self, w_side, deamp, interpret, row_offset, n_rows)
