"""Pallas TPU kernels: causal flash attention for the training step.

``models.layers.chunked_attention`` is the jnp form of the same online
softmax: XLA writes each of its f32 score and probability tiles to HBM,
stacks them over the kv scan and copies them again in the recompute and
the backward.  These kernels keep every tile in VMEM (Dao et al. 2022):
a forward kernel that also returns each row's log-sum-exp, and dq and dkv
backward kernels that recompute the probabilities from it, behind one
``jax.custom_vjp``.

TPU mapping
-----------
* layout: heads major, (b, h, s, hd) for q / do / dq and (b, kvh, s, hd)
  for k / v; query head ``kv * g + j`` belongs to kv head ``kv`` (the
  order of ``_project_qkv``'s reshape).  A grid step holds the g query
  heads of one kv head, so K/V are read once per group and never repeated
  g times in HBM (grouped-query attention without the repeat).
* forward and dq: grid (b, kvh, s / blk).  K and V of the kv head stay
  resident in VMEM across its q blocks (their block index does not move,
  so they are fetched once); the kernel walks the kv blocks left of the
  causal diagonal unmasked, then the diagonal block masked, by row groups
  that each stop at their own last row, and never touches the blocks
  right of it: no DMA, no compute.
* dkv: grid (b, kvh, s / blk), the transpose: q, do and the rows'
  statistics of the group stay resident; the kernel walks the q blocks at
  and below the diagonal, with the scores transposed (keys, queries) so
  that the row statistics broadcast along sublanes.
* numerics as ``chunked_attention`` on the TPU: bf16 operands (f32 inputs
  rounded to bf16, as the default matmul precision does) with f32
  accumulation, the 1/sqrt(hd) scale applied to the f32 scores, softmax
  statistics in f32, masked scores -1e30, P cast to bf16 before P @ V.
  The backward scales dS in f32 before its bf16 cast, as autodiff of the
  scaled scores does.
* block sizes come from the sequence length (``block_sizes``), multiples
  of the 128 lanes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quantize import _align_vma, _out_vma, default_interpret

__all__ = ["flash_attention", "block_sizes", "supports", "HEAD_DIMS"]

HEAD_DIMS = (64, 128, 256)
LANES = 128
NEG = -1e30                       # a masked score, as in chunked_attention
OPERAND = jnp.bfloat16            # the MXU operand dtype
#: VMEM the resident operands of a grid step may take, double-buffered
RESIDENT_BYTES = 48 * 2**20
#: scoped VMEM a kernel may use: the resident operands, its tiles and the
#: f32 score temporaries (a v5e core has 128 MiB)
VMEM_LIMIT = 96 * 2**20
NT = (((1,), (1,)), ((), ()))     # a @ b.T


def block_sizes(s: int) -> tuple[int, int]:
    """(blk, sub): the square block of queries by keys a step or an inner
    iteration covers, and the row group the diagonal block is cut into.
    Row group r of a diagonal block attends only up to its own last row,
    so the diagonal costs (1 + blk / sub) / 2 of a whole block, not all of
    it; blocks left of the diagonal run unmasked.  blk is the largest
    multiple of 128 up to 1024 that divides s, sub 256 where it divides
    blk: fastest of the sizes tried at both cells' shapes on a TPU v5e
    (PERF.md Findings)."""
    blk = 1024
    while s % blk:
        blk -= LANES
    return blk, 256 if blk % 256 == 0 else blk


def _resident_bytes(s: int, g: int, hd: int) -> int:
    lanes = max(hd, LANES)                     # VMEM pads hd 64 to 128
    kv = 2 * s * lanes * 2                     # K, V (forward, dq)
    group = 2 * g * s * lanes * 2 + 2 * g * 8 * s * 4   # q, do; lse, di (dkv)
    return 2 * max(kv, group)


def supports(s: int, g: int, hd: int) -> bool:
    """Shapes the kernels take: head_dim 64, 128 or 256, a sequence of
    whole 128-lane blocks, and a group whose resident operands fit VMEM."""
    return (hd in HEAD_DIMS and s % LANES == 0
            and _resident_bytes(s, g, hd) <= RESIDENT_BYTES)


def _lanes(x, n: int):
    """(rows, 128) lane-broadcast statistics -> (rows, n)."""
    if n <= LANES:
        return x[:, :n]
    return jnp.tile(x, (1, n // LANES))


def _causal(s, q0, k0, transposed: bool = False):
    """Mask the scores of queries from q0 against keys from k0; rows are
    queries, or keys when ``transposed``."""
    r = lax.broadcasted_iota(jnp.int32, s.shape, 0)
    c = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    visible = (c + q0 >= r + k0) if transposed else (r + q0 >= c + k0)
    return jnp.where(visible, s, NEG)


def _walk(i, blk: int, sub: int, attend):
    """Query block i: the key blocks left of the diagonal whole and
    unmasked, then the diagonal block by row groups, each masked and only
    as wide as its last row reaches.  ``attend(start, n, r0, r1, masked)``
    takes rows [r0, r1) of the block against keys [start, start + n)."""
    def step(j, carry):
        attend(pl.multiple_of(j * blk, blk), blk, 0, blk, False)
        return carry

    lax.fori_loop(0, i, step, 0)
    for r0 in range(0, blk, sub):
        attend(i * blk, r0 + sub, r0, r0 + sub, True)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale: float, blk: int, sub: int):
    i = pl.program_id(2)
    hd = q_ref.shape[-1]
    for h in range(q_ref.shape[0]):
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

        def attend(start, n, r0, r1, masked, h=h):
            rows = slice(r0, r1)
            s = lax.dot_general(q_ref[h, rows], k_ref[pl.ds(start, n), :], NT,
                                preferred_element_type=jnp.float32) * scale
            if masked:
                s = _causal(s, i * blk + r0, start)
            m_prev = m_scr[rows]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - _lanes(m_next, n))
            alpha = jnp.exp(m_prev - m_next)
            l_scr[rows] = l_scr[rows] * alpha + jnp.sum(p, axis=-1,
                                                        keepdims=True)
            m_scr[rows] = m_next
            pv = jnp.dot(p.astype(OPERAND), v_ref[pl.ds(start, n), :],
                         preferred_element_type=jnp.float32)
            acc_scr[rows] = acc_scr[rows] * _lanes(alpha, hd) + pv

        _walk(i, blk, sub, attend)
        l = l_scr[...]
        o_ref[h] = (acc_scr[...] / _lanes(jnp.maximum(l, 1e-30), hd)
                    ).astype(o_ref.dtype)
        # every lane of a row holds its statistic: the transpose's first
        # row is the (1, blk) row the backward reads
        lse_ref[h] = (m_scr[...] + jnp.log(l)).T[:1]


def _dq_kernel(q_ref, k_ref, v_ref, lse_ref, di_ref, do_ref, dq_ref, acc_scr,
               *, scale: float, blk: int, sub: int):
    i = pl.program_id(2)
    for h in range(q_ref.shape[0]):
        lse = lse_ref[h, 0][:, None]                           # (blk, 1)
        di = di_ref[h, 0][:, None]
        acc_scr[...] = jnp.zeros_like(acc_scr)

        def attend(start, n, r0, r1, masked, h=h, lse=lse, di=di):
            rows = slice(r0, r1)
            k = k_ref[pl.ds(start, n), :]
            s = lax.dot_general(q_ref[h, rows], k, NT,
                                preferred_element_type=jnp.float32) * scale
            if masked:
                s = _causal(s, i * blk + r0, start)
            p = jnp.exp(s - lse[rows])
            dp = lax.dot_general(do_ref[h, rows], v_ref[pl.ds(start, n), :],
                                 NT, preferred_element_type=jnp.float32)
            ds = p * (dp - di[rows]) * scale
            acc_scr[rows] += jnp.dot(ds.astype(OPERAND), k,
                                     preferred_element_type=jnp.float32)

        _walk(i, blk, sub, attend)
        dq_ref[h] = acc_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, lse_ref, di_ref, do_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, scale: float, blk: int, sub: int):
    """Key block j against the query blocks at and below the diagonal; the
    diagonal block by key row groups, each from its own first key on."""
    j = pl.program_id(2)
    dk_scr[...] = jnp.zeros_like(dk_scr)
    dv_scr[...] = jnp.zeros_like(dv_scr)
    for h in range(q_ref.shape[0]):
        def attend(start, n, r0, r1, masked, h=h):
            rows, cols = slice(r0, r1), pl.ds(start, n)        # keys, queries
            q, do = q_ref[h, cols, :], do_ref[h, cols, :]
            st = lax.dot_general(k_ref[rows], q, NT,
                                 preferred_element_type=jnp.float32) * scale
            if masked:
                st = _causal(st, start, j * blk + r0, transposed=True)
            pt = jnp.exp(st - lse_ref[h, :, cols])               # (keys, n)
            dv_scr[rows] += jnp.dot(pt.astype(OPERAND), do,
                                    preferred_element_type=jnp.float32)
            dpt = lax.dot_general(v_ref[rows], do, NT,
                                  preferred_element_type=jnp.float32)
            dst = pt * (dpt - di_ref[h, :, cols]) * scale
            dk_scr[rows] += jnp.dot(dst.astype(OPERAND), q,
                                    preferred_element_type=jnp.float32)

        for r0 in range(0, blk, sub):
            attend(pl.multiple_of(j * blk + r0, sub), blk - r0, r0, r0 + sub,
                   True)

        def step(i, carry, attend=attend):
            attend(pl.multiple_of(i * blk, blk), blk, 0, blk, False)
            return carry

        lax.fori_loop(j + 1, q_ref.shape[1] // blk, step, 0)
    dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
    dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _call(kernel, name: str, grid, in_specs, out_specs, out_shape, scratch,
          interpret: bool, *args):
    args = _align_vma(*args)
    vma = _out_vma(*args)
    out_shape = tuple(jax.ShapeDtypeStruct(s, d, **vma) for s, d in out_shape)
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=name)(*args)


def _specs(g: int, s: int, hd: int, blk: int):
    """BlockSpecs over grid (b, kvh, block): the group's q-shaped rows of
    the step's block, the kv head's keys of it, and the whole sequence of
    either."""
    return dict(
        q_block=pl.BlockSpec((None, g, blk, hd), lambda b, h, i: (b, h, i, 0)),
        row_block=pl.BlockSpec((None, g, 1, blk), lambda b, h, i: (b, h, 0, i)),
        kv_block=pl.BlockSpec((None, None, blk, hd), lambda b, h, j: (b, h, j, 0)),
        q_all=pl.BlockSpec((None, g, s, hd), lambda b, h, j: (b, h, 0, 0)),
        row_all=pl.BlockSpec((None, g, 1, s), lambda b, h, j: (b, h, 0, 0)),
        kv_all=pl.BlockSpec((None, None, s, hd), lambda b, h, i: (b, h, 0, 0)),
    )


def _heads(x):
    """(b, s, kvh, g, hd) -> (b, kvh * g, s, hd) operands."""
    b, s, kvh, g, hd = x.shape
    return x.transpose(0, 2, 3, 1, 4).reshape(b, kvh * g, s, hd).astype(OPERAND)


def _from_heads(x, kvh: int):
    b, h, s, hd = x.shape
    return x.reshape(b, kvh, h // kvh, s, hd).transpose(0, 3, 1, 2, 4)


def _forward(q, k, v, interpret: bool):
    b, s, kvh, g, hd = q.shape
    blk, sub = block_sizes(s)
    sp = _specs(g, s, hd, blk)
    qt, kt, vt = _heads(q), _heads(k[:, :, :, None]), _heads(v[:, :, :, None])
    ot, lse = _call(
        functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(hd), blk=blk,
                          sub=sub),
        "flash_attn_fwd", (b, kvh, s // blk),
        [sp["q_block"], sp["kv_all"], sp["kv_all"]],
        (sp["q_block"], sp["row_block"]),
        (((b, kvh * g, s, hd), q.dtype), ((b, kvh * g, 1, s), jnp.float32)),
        [pltpu.VMEM((blk, LANES), jnp.float32),
         pltpu.VMEM((blk, LANES), jnp.float32),
         pltpu.VMEM((blk, hd), jnp.float32)],
        interpret, qt, kt, vt)
    o = _from_heads(ot, kvh)
    return o, (qt, kt, vt, o, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, interpret):
    return _forward(q, k, v, interpret)[0]


def _flash_bwd(interpret, res, do):
    qt, kt, vt, o, lse = res
    b, s, kvh, g, hd = o.shape
    blk, sub = block_sizes(s)
    sp = _specs(g, s, hd, blk)
    scale = 1.0 / math.sqrt(hd)
    # D_i = rowsum(dO * O) in f32: the softmax backward's per-row term
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    di = di.transpose(0, 2, 3, 1).reshape(b, kvh * g, 1, s)
    dot = _heads(do)
    (dqt,) = _call(
        functools.partial(_dq_kernel, scale=scale, blk=blk, sub=sub),
        "flash_attn_dq", (b, kvh, s // blk),
        [sp["q_block"], sp["kv_all"], sp["kv_all"], sp["row_block"],
         sp["row_block"], sp["q_block"]],
        (sp["q_block"],),
        (((b, kvh * g, s, hd), o.dtype),),
        [pltpu.VMEM((blk, hd), jnp.float32)],
        interpret, qt, kt, vt, lse, di, dot)
    dkt, dvt = _call(
        functools.partial(_dkv_kernel, scale=scale, blk=blk, sub=sub),
        "flash_attn_dkv", (b, kvh, s // blk),
        [sp["q_all"], sp["kv_block"], sp["kv_block"], sp["row_all"],
         sp["row_all"], sp["q_all"]],
        (sp["kv_block"], sp["kv_block"]),
        (((b, kvh, s, hd), o.dtype), ((b, kvh, s, hd), o.dtype)),
        [pltpu.VMEM((blk, hd), jnp.float32), pltpu.VMEM((blk, hd), jnp.float32)],
        interpret, qt, kt, vt, lse, di, dot)
    return (_from_heads(dqt, kvh),
            dkt.transpose(0, 2, 1, 3), dvt.transpose(0, 2, 1, 3))


_flash.defvjp(_forward, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    interpret: bool | None = None) -> jax.Array:
    """Causal self-attention, q: (b, s, kvh, g, hd); k, v: (b, s, kvh, hd).

    Returns (b, s, kvh, g, hd) in q's dtype, differentiable in q, k and v.
    The shapes must pass ``supports`` (``models.layers.attention_path``
    checks them).  Matches ``models.layers.chunked_attention`` with
    ``causal=True`` and no offsets, window or soft-cap.
    """
    if interpret is None:
        interpret = default_interpret()
    b, s, kvh, g, hd = q.shape
    assert k.shape == v.shape == (b, s, kvh, hd), (q.shape, k.shape, v.shape)
    assert supports(s, g, hd), (s, g, hd)
    return _flash(q, k.astype(q.dtype), v.astype(q.dtype), interpret)
