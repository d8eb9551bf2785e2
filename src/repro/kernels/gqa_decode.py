"""Pallas TPU kernel: single-token GQA flash-decode over a KV-cache shard.

The decode-shape hot spot (decode_32k / long_500k): one query token attends
over a (possibly sequence-sharded) cache of up to 512k positions.  The
kernel streams the cache through VMEM in (TILE_S, hd) tiles with an online
max/sum accumulation, producing the per-shard partials (m, l, acc) that
`models.layers.combine_decode_partials` merges across mesh axes with the
log-sum-exp trick — so the kernel composes with sequence sharding for free.

TPU mapping
-----------
* grid = (b * kvh, S / TILE_S): the second (minor) grid dim is sequential on
  TPU, so the kernel accumulates into its output refs across S tiles
  (initialize at j == 0, combine otherwise) — the standard accumulation
  pattern; no HBM round-trips for the running (m, l, acc).
* q tile (g_pad, hd) lives in VMEM for the whole row; K/V stream as
  (TILE_S, hd) tiles: 512 x 128 f32 = 256 KiB each — well inside VMEM.
* scores (g_pad, TILE_S) hit the MXU via jnp.dot with f32 accumulation;
  g is padded to the 8-sublane multiple by the wrapper.
* positions masked by `valid` (causal frontier + sliding window) get -1e30
  before the online max — identical math to the jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .quantize import SMEM_SCALARS, _out_vma, default_interpret

__all__ = ["gqa_decode_pallas", "TILE_S"]

TILE_S = 512


def _kernel(softcap_arr, q_ref, k_ref, v_ref, valid_ref,
            m_ref, l_ref, acc_ref):
    j = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                  # (g_pad, hd)
    k = k_ref[0].astype(jnp.float32)                  # (TILE_S, hd)
    v = v_ref[0].astype(jnp.float32)
    valid = valid_ref[0]                              # (1, TILE_S) bool

    hd = q.shape[-1]
    scale = 1.0 / (hd ** 0.5)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    cap = softcap_arr[0]
    s = jnp.where(cap > 0.0, cap * jnp.tanh(s / jnp.where(cap > 0.0, cap, 1.0)), s)
    s = jnp.where(valid, s, -1e30)                    # (g_pad, TILE_S)

    m_blk = jnp.max(s, axis=-1, keepdims=True)        # (g_pad, 1)
    p = jnp.exp(s - m_blk)
    p = jnp.where(valid, p, 0.0)
    l_blk = jnp.sum(p, axis=-1, keepdims=True)
    acc_blk = jnp.dot(p, v, preferred_element_type=jnp.float32)  # (g_pad, hd)

    @pl.when(j == 0)
    def _init():
        m_ref[0] = m_blk
        l_ref[0] = l_blk
        acc_ref[0] = acc_blk

    @pl.when(j > 0)
    def _combine():
        m_old = m_ref[0]
        l_old = l_ref[0]
        acc_old = acc_ref[0]
        m_new = jnp.maximum(m_old, m_blk)
        c_old = jnp.exp(m_old - m_new)
        c_blk = jnp.exp(m_blk - m_new)
        m_ref[0] = m_new
        l_ref[0] = l_old * c_old + l_blk * c_blk
        acc_ref[0] = acc_old * c_old + acc_blk * c_blk


@functools.partial(jax.jit, static_argnames=("interpret",))
def gqa_decode_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                      valid: jax.Array, softcap=None,
                      interpret: bool | None = None):
    """q: (b, kvh, g, hd); k/v: (b, S, kvh, hd); valid: (S,) bool.

    Returns flash-decode partials (m (b,kvh,g), l (b,kvh,g),
    acc (b,kvh,g,hd)) — combine across shards with
    ``combine_decode_partials``.  Matches ``ref.gqa_decode_ref``.
    """
    if interpret is None:
        interpret = default_interpret()
    b, kvh, g, hd = q.shape
    S = k.shape[1]
    assert S % TILE_S == 0, (S, TILE_S)
    g_pad = max(8, -(-g // 8) * 8)                    # sublane multiple

    qp = jnp.pad(q, ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))
    qp = qp.reshape(b * kvh, g_pad, hd)
    # (b, S, kvh, hd) -> (b*kvh, S, hd)
    kp = k.transpose(0, 2, 1, 3).reshape(b * kvh, S, hd)
    vp = v.transpose(0, 2, 1, 3).reshape(b * kvh, S, hd)
    valid2 = jnp.broadcast_to(valid[None, None, :], (b * kvh, 1, S))
    cap = jnp.reshape(jnp.asarray(
        0.0 if softcap is None else softcap, jnp.float32), (1,))

    qp, kp, vp, valid2, cap = jax.tree.map(lambda x: x, (qp, kp, vp, valid2, cap))
    vma_kw = _out_vma(qp, kp, vp)
    grid = (b * kvh, S // TILE_S)
    out_shape = (
        jax.ShapeDtypeStruct((b * kvh, g_pad, 1), jnp.float32, **vma_kw),
        jax.ShapeDtypeStruct((b * kvh, g_pad, 1), jnp.float32, **vma_kw),
        jax.ShapeDtypeStruct((b * kvh, g_pad, hd), jnp.float32, **vma_kw),
    )
    m, l, acc = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            SMEM_SCALARS,                                           # softcap
            pl.BlockSpec((1, g_pad, hd), lambda i, j: (i, 0, 0)),   # q row
            pl.BlockSpec((1, TILE_S, hd), lambda i, j: (i, j, 0)),  # k tile
            pl.BlockSpec((1, TILE_S, hd), lambda i, j: (i, j, 0)),  # v tile
            pl.BlockSpec((1, 1, TILE_S), lambda i, j: (i, 0, j)),   # valid
        ],
        out_specs=(
            pl.BlockSpec((1, g_pad, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, g_pad, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, g_pad, hd), lambda i, j: (i, 0, 0)),
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(cap, qp, kp, vp, valid2)

    m = m.reshape(b, kvh, g_pad)[:, :, :g]
    l = l.reshape(b, kvh, g_pad)[:, :, :g]
    acc = acc.reshape(b, kvh, g_pad, hd)[:, :, :g]
    return m, l, acc
