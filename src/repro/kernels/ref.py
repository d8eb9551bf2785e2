"""Pure-jnp oracles for every Pallas kernel (the allclose references).

They are also what ``kernels/ops.py`` computes when ``use_pallas`` is off,
and, in interpret mode only, inside ``shard_map(check_vma=True)``.  On a
TPU a ``use_pallas`` call always runs its compiled kernel.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = [
    "quantize_blocks_ref",
    "dequant_combine_ref",
    "gqa_decode_ref",
]


def quantize_blocks_ref(y: jax.Array, noise: jax.Array,
                        fixed_step: jax.Array | None = None
                        ) -> tuple[jax.Array, jax.Array]:
    """Stochastic int8 quantization of (n, block) rows.

    adaptive (fixed_step None): per-row scale = max|y| / 127 (never clips);
    fixed: scale = fixed_step broadcast (paper-faithful grid; clips at +-127,
    the clipping fraction is monitored by the caller).

    code = floor(y/scale) + (noise < frac(y/scale));  E[code*scale] = y.
    Returns (codes int8, scales f32 (n, 1)).
    """
    y32 = y.astype(jnp.float32)
    if fixed_step is None:
        # multiply by the f32 reciprocal (not /127.0): bit-identical to the
        # pallas kernel regardless of how XLA lowers constant division
        scales = jnp.maximum(jnp.max(jnp.abs(y32), axis=-1, keepdims=True),
                             1e-30) * jnp.float32(1.0 / 127.0)
    else:
        scales = jnp.broadcast_to(jnp.asarray(fixed_step, jnp.float32),
                                  (y.shape[0], 1))
    s = y32 / scales
    lo = jnp.floor(s)
    frac = s - lo
    q = lo + (noise < frac).astype(jnp.float32)
    codes = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)
    return codes, scales


def dequant_combine_ref(
    codes_self: jax.Array, scale_self: jax.Array,
    codes_left: jax.Array, scale_left: jax.Array,
    codes_right: jax.Array, scale_right: jax.Array,
    x_tilde: jax.Array, m_agg: jax.Array,
    w_self: float, w_side: float, deamp: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused de-amplify + x_tilde integration + ring consensus combine.

    x_tilde' = x_tilde + deamp * codes_self * scale_self
    m_agg'   = m_agg + w_side * deamp * (codes_l*scale_l + codes_r*scale_r)
    combined = w_self * x_tilde' + m_agg'

    (m_agg incrementally tracks sum_{j != i} W_ij x_tilde_j — O(1) memory in
    node degree, see DESIGN.md.)
    """
    d_self = codes_self.astype(jnp.float32) * scale_self
    d_l = codes_left.astype(jnp.float32) * scale_left
    d_r = codes_right.astype(jnp.float32) * scale_right
    x_t = x_tilde + deamp * d_self
    m = m_agg + w_side * deamp * (d_l + d_r)
    combined = w_self * x_t + m
    return x_t, m, combined


def gqa_decode_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                   valid: jax.Array, softcap: float | None = None
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Single-token GQA flash-decode partials over a cache shard.

    q: (b, kvh, g, hd); k/v: (b, S, kvh, hd); valid: (S,) bool.
    Returns (m, l, acc) partials — (b,kvh,g), (b,kvh,g), (b,kvh,g,hd) — for
    cross-shard log-sum-exp combination.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhgd,bkhd->bhgk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(valid[None, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[None, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhgk,bkhd->bhgd", p, v.astype(jnp.float32))
    return m, l, acc
