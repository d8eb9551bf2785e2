"""Transformer layers with explicit tensor-parallel collectives.

Two attention TP strategies (DESIGN.md "Execution model"):

  * head-sharded  — classic Megatron: q/kv/o projections sharded on the head
                    dim over ``model``; kv heads replicated when
                    n_kv_heads < tp.  Used when n_heads % tp == 0.
  * seq-sharded   — projections replicated over ``model``; the *sequence* is
                    sharded: each rank computes q/k/v for its s/tp chunk,
                    all-gathers K,V, attends its query chunk, all-gathers the
                    output.  Head-count agnostic (whisper 12H, granite 24H,
                    smollm 9H on tp=16).  Decode uses a sequence-sharded KV
                    cache with flash-decode log-sum-exp combine.

All functions take *logical tp-local* parameter dicts (already FSDP-gathered
by the caller) and a :class:`ParallelContext`.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ..kernels.flash_attention import flash_attention
from ..kernels.flash_attention import supports as flash_attention_supports
from ..kernels.quantize import default_interpret
from .config import ModelConfig
from .params import ParamDef
from .sharding import ParallelContext

# ---------------------------------------------------------------------------
# Norms / activations / positions
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """RMSNorm with f32 variance statistics but compute-dtype elementwise.

    The f32 cast feeds only the (fused) square-reduce; the full-size tensors
    and their backward cotangents stay in the compute dtype — in bf16
    training this halves the norm-path HBM traffic (section Perf, yi-9b).
    Identical to the classic all-f32 form when x is f32."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * scale * (1.0 + w.astype(x.dtype))


def _act(name: str, x: jax.Array) -> jax.Array:
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(name)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (b, s, h, hd); positions: (s,) or (b, s)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]   # (s, hd/2)
        ang = ang[None, :, None, :]                                     # (1,s,1,hd/2)
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs          # (b,s,hd/2)
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-math.log(10000.0) / d))
    pe = jnp.zeros((n, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


# ---------------------------------------------------------------------------
# Chunked (flash-style, jnp) attention — never materializes (S x S)
# ---------------------------------------------------------------------------

def _divisor_chunk(s: int, target: int) -> int:
    """Largest chunk size <= target that divides s (whisper's 1488-frame
    encoder sequence is not a multiple of the default 1024 kv chunk)."""
    c = min(target, s)
    while s % c:
        c -= 1
    return c


def _softcap(s: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return s
    return cap * jnp.tanh(s / cap)


def chunked_attention(
    q: jax.Array,                  # (b, sq, kvh, g, hd)  grouped query
    k: jax.Array,                  # (b, sk, kvh, hd)
    v: jax.Array,                  # (b, sk, kvh, hd)
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    q_offset: jax.Array | int = 0,  # global position of q[0]
    k_offset: int = 0,
    chunk_q: int = 512,
    chunk_k: int = 1024,
) -> jax.Array:
    """Online-softmax attention over chunks.  Returns (b, sq, kvh, g, hd)."""
    b, sq, kvh, g, hd = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    cq = _divisor_chunk(sq, chunk_q)
    ck = _divisor_chunk(sk, chunk_k)
    nq, nk = sq // cq, sk // ck

    neg = jnp.asarray(-1e30, jnp.float32)

    def q_step(_, iq_qi):
        iq, qi = iq_qi                                  # qi: (b, cq, kvh, g, hd)
        qpos = q_offset + iq * cq + jnp.arange(cq)      # (cq,)

        def kv_step(carry, ik_kv):
            m, l, acc = carry
            ik, ki, vi = ik_kv                          # ki/vi: (b, ck, kvh, hd)
            kpos = k_offset + ik * ck + jnp.arange(ck)  # (ck,)
            # dots run in the input dtype (bf16 on the MXU in production)
            # with f32 accumulation — flash-attention numerics; softmax
            # statistics stay f32.  Halves the dot operand HBM traffic vs
            # upcasting q/k/p to f32 first (section Perf).
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, ki,
                           preferred_element_type=jnp.float32) * scale
            s = _softcap(s, softcap)
            mask = jnp.ones((cq, ck), bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if window is not None:
                mask &= qpos[:, None] - kpos[None, :] < window
            s = jnp.where(mask[None, None, None], s, neg)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(qi.dtype), vi,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        # carries derived from qi (x0) so vma/varying types match the
        # scan body outputs under shard_map check_vma=True
        qz = jnp.transpose(qi.astype(jnp.float32), (0, 2, 3, 1, 4)) * 0.0
        m0 = qz[..., 0] + neg                       # (b, kvh, g, cq)
        l0 = qz[..., 0]
        a0 = qz

        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (jnp.arange(nk), kc, vc))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.transpose(0, 3, 1, 2, 4)       # (b, cq, kvh, g, hd)

    # flash-attention-style backward: recompute each q-chunk's scores from
    # (qi, K, V) instead of letting the scan transpose stack every chunk's
    # (cq, ck) score/probability residuals across iterations — the stacked
    # residuals are the full (sq, sk) matrix in f32 (section Perf, yi-9b).
    q_body = jax.checkpoint(q_step, prevent_cse=False) if sq > cq else q_step
    # "attention/core": the chunk layout, the two scans and the online
    # softmax, apart from the projections around them
    with jax.named_scope("core"):
        qc = q.reshape(b, nq, cq, kvh, g, hd).transpose(1, 0, 2, 3, 4, 5)
        kc = k.reshape(b, nk, ck, kvh, hd).transpose(1, 0, 2, 3, 4)
        vc = v.reshape(b, nk, ck, kvh, hd).transpose(1, 0, 2, 3, 4)
        _, outs = jax.lax.scan(q_body, None, (jnp.arange(nq), qc))
        out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, kvh, g, hd)
        return out.astype(q.dtype)


def attention_path(q_shape, k_shape, *, causal: bool, window: int | None,
                   softcap: float | None, q_offset, k_offset) -> str:
    """Which attention core runs: ``"kernel"`` (the Pallas flash-attention
    kernels) on a TPU, for causal self-attention from position 0 with no
    window or soft-cap and shapes the kernels take; ``"chunked"``
    (``chunked_attention``) for everything else: the CPU, a traced offset
    (the sequence-sharded path), non-causal attention (whisper's encoder),
    gemma2's window and soft-cap, cross attention over another length."""
    _, sq, _, g, hd = q_shape
    static_zero = all(isinstance(o, int) and o == 0
                      for o in (q_offset, k_offset))
    if (default_interpret() or not causal or window is not None
            or softcap is not None or not static_zero or k_shape[1] != sq
            or not flash_attention_supports(sq, g, hd)):
        return "chunked"
    return "kernel"


def attention_core(q, k, v, *, causal: bool = True, window: int | None = None,
                   softcap: float | None = None,
                   q_offset: jax.Array | int = 0,
                   k_offset: int = 0) -> jax.Array:
    """Self-attention core on the path ``attention_path`` picks; q: (b, sq,
    kvh, g, hd), k/v: (b, sk, kvh, hd) -> (b, sq, kvh, g, hd)."""
    if attention_path(q.shape, k.shape, causal=causal, window=window,
                      softcap=softcap, q_offset=q_offset,
                      k_offset=k_offset) == "kernel":
        # "attention/core", as chunked_attention's own scope
        with jax.named_scope("core"):
            return flash_attention(q, k, v)
    return chunked_attention(q, k, v, causal=causal, window=window,
                             softcap=softcap, q_offset=q_offset,
                             k_offset=k_offset)


def decode_attention_local(
    q: jax.Array,                  # (b, 1, kvh, g, hd)
    k_cache: jax.Array,            # (b, S_local, kvh, hd)
    v_cache: jax.Array,
    valid: jax.Array,              # (S_local,) or (b, S_local) bool
    softcap: float | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Partial flash-decode over a local cache shard.

    Returns (m, l, acc): per-(b,kvh,g) running max, denominator, weighted sum
    — combined across shards with :func:`combine_decode_partials`.
    """
    b, _, kvh, g, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bqhgd,bkhd->bhgk", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    s = _softcap(s, softcap)
    if valid.ndim == 1:
        vmask = valid[None, None, None, :]
    else:
        vmask = valid[:, None, None, :]
    s = jnp.where(vmask, s, -1e30)
    m = jnp.max(s, axis=-1)                              # (b,kvh,g)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(vmask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return m, l, acc


def combine_decode_partials(m, l, acc, ctx: ParallelContext,
                            axes: tuple[str, ...]) -> jax.Array:
    """Log-sum-exp combine of flash-decode partials across mesh axes."""
    if not axes:
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out
    m_glob = ctx.pmax_axes(m, axes)
    corr = jnp.exp(m - m_glob)
    l_glob = ctx.psum_axes(l * corr, axes)
    acc_glob = ctx.psum_axes(acc * corr[..., None], axes)
    return acc_glob / jnp.maximum(l_glob, 1e-30)[..., None]


# ---------------------------------------------------------------------------
# Attention block (param defs + forward)
# ---------------------------------------------------------------------------

def attention_defs(cfg: ModelConfig, ctx: ParallelContext, dtype,
                   cross: bool = False) -> dict[str, ParamDef]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kvh = cfg.n_heads, cfg.n_kv_heads
    head_sharded = ctx.head_sharded and h % max(ctx.tp, 1) == 0
    if head_sharded:
        q_def = ParamDef((d, h * hd), tp_dim=1, fsdp_dim=0, dtype=dtype)
        if kvh >= ctx.tp:
            kv_tp = 1
            k_def = ParamDef((d, kvh * hd), tp_dim=1, fsdp_dim=0, dtype=dtype)
        else:
            kv_tp = None  # replicated; rank slices its kv head(s)
            k_def = ParamDef((d, kvh * hd), tp_dim=None, fsdp_dim=0, dtype=dtype)
        v_def = k_def
        o_def = ParamDef((h * hd, d), tp_dim=0, fsdp_dim=1, dtype=dtype)
    else:
        q_def = ParamDef((d, h * hd), tp_dim=None, fsdp_dim=0, dtype=dtype)
        k_def = ParamDef((d, kvh * hd), tp_dim=None, fsdp_dim=0, dtype=dtype)
        v_def = k_def
        o_def = ParamDef((h * hd, d), tp_dim=None, fsdp_dim=1, dtype=dtype)
    out = {"wq": q_def, "wk": k_def, "wv": v_def, "wo": o_def}
    if cfg.qk_norm:
        out["q_norm"] = ParamDef((hd,), tp_dim=None, fsdp_dim=0, init="zeros", dtype=dtype)
        out["k_norm"] = ParamDef((hd,), tp_dim=None, fsdp_dim=0, init="zeros", dtype=dtype)
    return out


def _project_qkv(p, x, cfg: ModelConfig, ctx: ParallelContext):
    """Returns q (b,s,kvh_eff,g,hd), k, v (b,s,kvh_eff,hd) for the local rank.

    head-sharded: kvh_eff = local kv heads; seq-sharded: full heads but x is
    the rank's sequence chunk (handled by caller).
    """
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    h, kvh = cfg.n_heads, cfg.n_kv_heads
    head_sharded = ctx.head_sharded and h % max(ctx.tp, 1) == 0

    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]

    if head_sharded and ctx.tp > 1:
        h_local = h // ctx.tp
        if kvh >= ctx.tp:
            kv_local = kvh // ctx.tp
            q = q.reshape(b, s, h_local, hd)
            k = k.reshape(b, s, kv_local, hd)
            v = v.reshape(b, s, kv_local, hd)
        else:
            # kv replicated: slice the kv head(s) this rank's q heads use.
            q = q.reshape(b, s, h_local, hd)
            k = k.reshape(b, s, kvh, hd)
            v = v.reshape(b, s, kvh, hd)
            group_full = h // kvh                     # q heads per kv head
            r = ctx.tp_index()
            kv_idx = (r * h_local) // group_full      # first (only) kv head
            k = jax.lax.dynamic_slice_in_dim(k, kv_idx, 1, axis=2)
            v = jax.lax.dynamic_slice_in_dim(v, kv_idx, 1, axis=2)
            kv_local = 1
        g = (h // ctx.tp) // kv_local if kv_local else 1
        g = max(1, (h // ctx.tp) // max(kv_local, 1))
        q = q.reshape(b, s, kv_local, g, hd)
    else:
        q = q.reshape(b, s, h, hd)
        k = k.reshape(b, s, kvh, hd)
        v = v.reshape(b, s, kvh, hd)
        g = h // kvh
        q = q.reshape(b, s, kvh, g, hd)
    return q, k, v


def _maybe_qk_norm(p, q, k, cfg: ModelConfig):
    if not cfg.qk_norm:
        return q, k
    q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k


def attention_forward(
    p: dict[str, jax.Array],
    x: jax.Array,                    # (b, s, d) replicated over model
    cfg: ModelConfig,
    ctx: ParallelContext,
    *,
    kind: str = "A",                 # 'A' full | 'L' sliding window
    mode: str = "train",             # train | prefill | decode
    cache: dict | None = None,
    pos_offset: jax.Array | int = 0,
    cache_seq_axes: tuple[str, ...] = (),
    window_override: int | None = None,
    use_rope: bool = True,
    causal: bool = True,
) -> tuple[jax.Array, dict | None]:
    """Self-attention.  Returns (out (b,s,d) replicated, new_cache)."""
    b, s, d = x.shape
    h = cfg.n_heads
    head_sharded = ctx.head_sharded and h % max(ctx.tp, 1) == 0
    window = window_override if window_override is not None else (
        cfg.sliding_window if kind == "L" else None)
    softcap = cfg.attn_softcap

    if mode == "decode":
        return _attention_decode(p, x, cfg, ctx, cache=cache,
                                 pos_offset=pos_offset, window=window,
                                 softcap=softcap,
                                 cache_seq_axes=cache_seq_axes,
                                 head_sharded=head_sharded,
                                 use_rope=use_rope)

    if head_sharded:
        q, k, v, = _project_qkv(p, x, cfg, ctx)
        q, k = _maybe_qk_norm(p, q, k, cfg)
        if use_rope:
            pos = pos_offset + jnp.arange(s)
            q = apply_rope(q.reshape(b, s, -1, q.shape[-1]), pos, cfg.rope_theta
                           ).reshape(q.shape)
            k = apply_rope(k, pos, cfg.rope_theta)
        out = attention_core(q, k, v, causal=causal, window=window,
                             softcap=softcap, q_offset=pos_offset)
        out = out.reshape(b, s, -1)
        y = ctx.psum_tp(out @ p["wo"])
        new_cache = None
        if mode == "prefill":
            new_cache = _prefill_cache(k, v, cfg, ctx, cache_seq_axes, s,
                                       head_sharded=True)
        return y, new_cache

    # --- sequence-sharded path ---------------------------------------
    tp = max(ctx.tp, 1)
    s_local = s // tp if tp > 1 else s
    r = ctx.tp_index()
    if tp > 1:
        x_chunk = jax.lax.dynamic_slice_in_dim(x, r * s_local, s_local, axis=1)
    else:
        x_chunk = x
    q, k, v = _project_qkv(p, x_chunk, cfg, ctx)
    q, k = _maybe_qk_norm(p, q, k, cfg)
    if use_rope:
        pos_chunk = pos_offset + r * s_local + jnp.arange(s_local)
        q = apply_rope(q.reshape(b, s_local, -1, q.shape[-1]), pos_chunk,
                       cfg.rope_theta).reshape(q.shape)
        k = apply_rope(k, pos_chunk, cfg.rope_theta)
    k_full = ctx.ag_tp(k, axis=1)
    v_full = ctx.ag_tp(v, axis=1)
    out = attention_core(q, k_full, v_full, causal=causal, window=window,
                         softcap=softcap, q_offset=pos_offset + r * s_local,
                         k_offset=0)
    out = out.reshape(b, s_local, -1)
    y_chunk = out @ p["wo"]
    y = ctx.ag_tp(y_chunk, axis=1)
    new_cache = None
    if mode == "prefill":
        new_cache = _prefill_cache(k, v, cfg, ctx, cache_seq_axes, s,
                                   head_sharded=False)
    return y, new_cache


def _prefill_cache(k_local, v_local, cfg, ctx, cache_seq_axes, s,
                   head_sharded: bool):
    """Build the decode cache from prefill K/V.

    head-sharded: k_local is (b, s_full, kv_local, hd) — cache sequence may
    additionally be sharded over `cache_seq_axes` (long-context): each shard
    keeps its slice.  seq-sharded: k_local is already the rank's seq chunk.
    """
    if head_sharded and cache_seq_axes:
        # slice my portion of the sequence for each axis in order
        k_c, v_c = k_local, v_local
        for ax in cache_seq_axes:
            n = ctx.axis_size_of(ax)
            if n == 1:
                continue
            sz = k_c.shape[1] // n
            i = ctx.axis_index_of(ax)
            k_c = jax.lax.dynamic_slice_in_dim(k_c, i * sz, sz, axis=1)
            v_c = jax.lax.dynamic_slice_in_dim(v_c, i * sz, sz, axis=1)
        return {"k": k_c, "v": v_c}
    return {"k": k_local, "v": v_local}


def _attention_decode(p, x, cfg, ctx, *, cache, pos_offset, window, softcap,
                      cache_seq_axes, head_sharded, use_rope):
    """One-token decode against a (possibly sequence-sharded) KV cache."""
    assert cache is not None, "decode requires a cache"
    b, s, d = x.shape
    assert s == 1, "decode processes one token"
    q, k_new, v_new = _project_qkv(p, x, cfg, ctx)
    q, k_new = _maybe_qk_norm(p, q, k_new, cfg)
    pos = pos_offset  # current cache length (tracked at the top level)
    if use_rope:
        pos_arr = jnp.full((1,), pos, jnp.int32)
        q = apply_rope(q.reshape(b, 1, -1, q.shape[-1]), pos_arr, cfg.rope_theta
                       ).reshape(q.shape)
        k_new = apply_rope(k_new, pos_arr, cfg.rope_theta)

    k_cache, v_cache = cache["k"], cache["v"]
    s_shard = k_cache.shape[1]

    # which shard owns position `pos`?  (sequence sharded over cache_seq_axes)
    shard_rank = jnp.asarray(0, jnp.int32)
    n_shards = 1
    for ax in cache_seq_axes:
        n = ctx.axis_size_of(ax)
        shard_rank = shard_rank * n + ctx.axis_index_of(ax)
        n_shards *= n
    local_pos = pos - shard_rank * s_shard
    in_range = (local_pos >= 0) & (local_pos < s_shard)
    write_pos = jnp.clip(local_pos, 0, s_shard - 1)
    k_upd = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k_new.astype(k_cache.dtype), write_pos, axis=1)
    v_upd = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v_new.astype(v_cache.dtype), write_pos, axis=1)
    k_cache = jnp.where(in_range, k_upd, k_cache)
    v_cache = jnp.where(in_range, v_upd, v_cache)

    # validity of each cache slot (global position <= pos, window)
    gpos = shard_rank * s_shard + jnp.arange(s_shard)
    valid = gpos <= pos
    if window is not None:
        valid &= gpos > pos - window
    m, l, acc = decode_attention_local(q, k_cache, v_cache, valid, softcap)
    out = combine_decode_partials(m, l, acc, ctx, cache_seq_axes)  # (b,kvh,g,hd)
    out = out.reshape(b, 1, -1).astype(x.dtype)
    y = out @ p["wo"]
    if head_sharded and ctx.tp > 1:
        y = ctx.psum_tp(y)
    new_cache = {"k": k_cache, "v": v_cache}
    return y, new_cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_defs(cfg: ModelConfig, ctx: ParallelContext, dtype,
             d_ff: int | None = None) -> dict[str, ParamDef]:
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    assert ff % max(ctx.tp, 1) == 0, (ff, ctx.tp)
    return {
        "w_gate": ParamDef((d, ff), tp_dim=1, fsdp_dim=0, dtype=dtype),
        "w_up": ParamDef((d, ff), tp_dim=1, fsdp_dim=0, dtype=dtype),
        "w_down": ParamDef((ff, d), tp_dim=0, fsdp_dim=1, dtype=dtype),
    }


def mlp_forward(p, x, cfg: ModelConfig, ctx: ParallelContext) -> jax.Array:
    h = _act(cfg.mlp_act, x @ p["w_gate"]) * (x @ p["w_up"])
    return ctx.psum_tp(h @ p["w_down"])


# ---------------------------------------------------------------------------
# Embedding + (vocab-sharded) cross-entropy
# ---------------------------------------------------------------------------

def padded_vocab(cfg: ModelConfig, tp: int) -> int:
    v = cfg.vocab_size
    return int(math.ceil(v / (tp * 128)) * tp * 128) if tp > 1 else v


def embed_defs(cfg: ModelConfig, ctx: ParallelContext, dtype) -> dict[str, ParamDef]:
    v = padded_vocab(cfg, ctx.tp)
    out = {"table": ParamDef((v, cfg.d_model), tp_dim=0, fsdp_dim=1,
                             scale=1.0, dtype=dtype)}
    if not cfg.tie_embeddings:
        out["unembed"] = ParamDef((cfg.d_model, v), tp_dim=1, fsdp_dim=0,
                                  dtype=dtype)
    return out


def embed_lookup(p, ids: jax.Array, cfg: ModelConfig, ctx: ParallelContext,
                 dtype=jnp.float32) -> jax.Array:
    """ids (b, s) -> (b, s, d), vocab sharded over model."""
    table = p["table"]
    v_local = table.shape[0]
    r = ctx.tp_index()
    local_ids = ids - r * v_local
    ok = (local_ids >= 0) & (local_ids < v_local)
    safe = jnp.clip(local_ids, 0, v_local - 1)
    emb = jnp.take(table, safe, axis=0) * ok[..., None].astype(table.dtype)
    emb = ctx.psum_tp(emb)
    if cfg.embed_scale:
        emb = emb * jnp.asarray(math.sqrt(cfg.d_model), emb.dtype)
    return emb.astype(dtype)


def logits_local(p, h: jax.Array, cfg: ModelConfig, ctx: ParallelContext) -> jax.Array:
    """(b, s, d) -> local logit shard (b, s, V/tp), softcapped if configured."""
    if cfg.tie_embeddings:
        w = p["table"].T  # (d, V_local)
    else:
        w = p["unembed"]
    logits = h @ w
    if cfg.final_softcap is not None:
        logits = _softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits.astype(jnp.float32)


def sharded_softmax_xent(logits_loc: jax.Array, targets: jax.Array,
                         cfg: ModelConfig, ctx: ParallelContext,
                         z_loss: float = 0.0) -> jax.Array:
    """Mean cross-entropy with vocab sharded over 'model'.

    logits_loc: (b, s, V/tp) fp32; targets: (b, s) global token ids.
    Targets >= real vocab (padding ids) are ignored via masking upstream.
    """
    v_local = logits_loc.shape[-1]
    r = ctx.tp_index()
    # max is only for numerical stability: stop_gradient keeps the exact CE
    # gradient while avoiding pmax's missing differentiation rule.
    m = ctx.pmax_tp(jax.lax.stop_gradient(jnp.max(logits_loc, axis=-1)))
    e = jnp.exp(logits_loc - m[..., None])
    denom = ctx.psum_tp(jnp.sum(e, axis=-1))                # (b, s)
    log_z = jnp.log(denom) + m
    local_t = targets - r * v_local
    ok = (local_t >= 0) & (local_t < v_local)
    safe = jnp.clip(local_t, 0, v_local - 1)
    picked = jnp.take_along_axis(logits_loc, safe[..., None], axis=-1)[..., 0]
    target_logit = ctx.psum_tp(picked * ok.astype(picked.dtype))
    nll = log_z - target_logit
    loss = jnp.mean(nll)
    if z_loss > 0.0:
        loss = loss + z_loss * jnp.mean(log_z**2)
    return loss


def sharded_greedy_sample(logits_loc: jax.Array, ctx: ParallelContext) -> jax.Array:
    """Distributed argmax over the sharded vocab.  (b, s, V/tp) -> (b, s)."""
    v_local = logits_loc.shape[-1]
    r = ctx.tp_index()
    loc_max = jnp.max(logits_loc, axis=-1)
    loc_arg = jnp.argmax(logits_loc, axis=-1) + r * v_local
    glob_max = ctx.pmax_tp(loc_max)
    # ties: lowest global id wins
    cand = jnp.where(loc_max >= glob_max, loc_arg, jnp.iinfo(jnp.int32).max)
    if ctx.tp == 1:
        return cand.astype(jnp.int32)
    return -ctx.pmax_tp(-cand).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Norm defs helper
# ---------------------------------------------------------------------------

def norm_def(cfg: ModelConfig, dtype) -> ParamDef:
    return ParamDef((cfg.d_model,), tp_dim=None, fsdp_dim=0, init="zeros",
                    dtype=dtype)
