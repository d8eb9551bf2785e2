"""Model assembly: block dispatcher + period-scanned stack + enc-dec.

The layer stack is expressed as ``prelude`` (unscanned, heterogeneous first
layers — e.g. deepseek's dense layer 0) followed by ``period * n_periods``
scanned with ``lax.scan`` over stacked parameters (compile-time compact,
FSDP-gathers one period at a time inside the scan).

Public entry points:
  build_defs(cfg, ctx, dtype)                 -> ModelDefs (ParamDef trees)
  init_cache(cfg, ctx, b_local, capacity,...) -> decode cache pytree
  model_apply(params, defs, batch, ...)       -> (logits_loc, cache, aux)
  train_loss(params, defs, batch, ...)        -> (loss, metrics)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from . import mamba2, moe
from .config import ModelConfig
from .layers import (attention_defs, attention_forward, embed_defs,
                     embed_lookup, logits_local, mlp_defs, mlp_forward,
                     norm_def, padded_vocab, rms_norm, sharded_greedy_sample,
                     sharded_softmax_xent, sinusoidal_positions)
from .params import ParamDef, gather_tree, materialize_logical
from .sharding import ParallelContext

__all__ = ["ModelDefs", "build_defs", "init_cache", "model_apply",
           "train_loss", "cache_seq_axes_for"]


# ---------------------------------------------------------------------------
# Param defs
# ---------------------------------------------------------------------------

def _block_defs(code: str, cfg: ModelConfig, ctx, dtype, cross: bool = False):
    d: dict[str, Any] = {"norm1": norm_def(cfg, dtype)}
    if code in ("A", "L", "E", "D"):
        d["attn"] = attention_defs(cfg, ctx, dtype)
    elif code in ("M", "X"):
        d["mamba"] = mamba2.mamba_defs(cfg, ctx, dtype)
    else:
        raise ValueError(code)
    if cross:
        d["norm_cross"] = norm_def(cfg, dtype)
        d["cross"] = attention_defs(cfg, ctx, dtype)
    # FFN
    if code in ("E", "X"):
        d["norm2"] = norm_def(cfg, dtype)
        d["moe"] = moe.moe_defs(cfg, ctx, dtype)
    elif code == "D":
        d["norm2"] = norm_def(cfg, dtype)
        d["mlp"] = mlp_defs(cfg, ctx, dtype, d_ff=cfg.dense_d_ff or cfg.d_ff)
    elif code in ("A", "L") or (code == "M" and cfg.d_ff > 0):
        d["norm2"] = norm_def(cfg, dtype)
        d["mlp"] = mlp_defs(cfg, ctx, dtype)
    if cfg.post_norms:
        d["norm1_post"] = norm_def(cfg, dtype)
        if "norm2" in d:
            d["norm2_post"] = norm_def(cfg, dtype)
    return d


def _stack_defs(defs, n: int):
    """Add a leading stacking dim of size n to every ParamDef in the tree."""
    def stack(d: ParamDef) -> ParamDef:
        return dataclasses.replace(
            d, shape=(n,) + d.shape,
            tp_dim=None if d.tp_dim is None else d.tp_dim + 1,
            fsdp_dim=d.fsdp_dim + 1)
    return jax.tree.map(stack, defs, is_leaf=lambda x: isinstance(x, ParamDef))


@dataclasses.dataclass(frozen=True)
class ModelDefs:
    cfg: ModelConfig
    storage: Any            # full tree of (stacked) ParamDefs — init/shardings
    period: Any             # unstacked defs for one period (gather inside scan)
    prelude: Any            # tuple of per-layer defs
    enc_period: Any = None  # whisper encoder period defs
    dtype: Any = jnp.float32


def build_defs(cfg: ModelConfig, ctx: ParallelContext, dtype=jnp.float32) -> ModelDefs:
    period_defs = tuple(_block_defs(c, cfg, ctx, dtype,
                                    cross=cfg.is_encoder_decoder)
                        for c in cfg.period)
    prelude_defs = tuple(_block_defs(c, cfg, ctx, dtype,
                                     cross=cfg.is_encoder_decoder)
                         for c in cfg.prelude)
    storage: dict[str, Any] = {
        "embed": embed_defs(cfg, ctx, dtype),
        "layers": _stack_defs(period_defs, cfg.n_periods),
        "final_norm": norm_def(cfg, dtype),
    }
    if prelude_defs:
        storage["prelude"] = prelude_defs
    enc_period = None
    if cfg.is_encoder_decoder:
        # decoder uses learned positions (whisper); encoder sinusoidal (no params)
        storage["pos_emb"] = ParamDef((32_768, cfg.d_model), tp_dim=None,
                                      fsdp_dim=0, scale=0.02, dtype=dtype)
        enc_period = tuple(_block_defs("A", cfg, ctx, dtype)
                           for _ in range(1))
        storage["encoder"] = {
            "layers": _stack_defs(enc_period, cfg.n_encoder_layers),
            "final_norm": norm_def(cfg, dtype),
        }
    return ModelDefs(cfg=cfg, storage=storage, period=period_defs,
                     prelude=prelude_defs, enc_period=enc_period, dtype=dtype)


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------

def cache_seq_axes_for(cfg: ModelConfig, ctx: ParallelContext,
                       shape_batch: int) -> tuple[str, ...]:
    """Mesh axes sharding the KV-cache sequence dim.

    seq-sharded attention archs always shard the cache over 'model'.
    When the serving batch is too small to fill the data axis (long_500k
    b=1), the cache is additionally sequence-sharded over 'data'.
    """
    axes: tuple[str, ...] = ()
    head_sharded = ctx.head_sharded and cfg.n_heads % max(ctx.tp, 1) == 0
    if not head_sharded and ctx.tp > 1:
        axes += ("model",)
    if shape_batch < ctx.dp and ctx.data_size > 1:
        axes += ("data",)
        if ctx.pod_axis is not None and ctx.pods > 1:
            axes += ("pod",)
    return axes


def _shard_count(ctx: ParallelContext, axes: tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= ctx.axis_size_of(a)
    return n


def init_cache(cfg: ModelConfig, ctx: ParallelContext, b_local: int,
               capacity: int, cache_seq_axes: tuple[str, ...],
               dtype=jnp.float32, enc_len: int | None = None) -> dict:
    """Zeroed decode cache (pre-prefill).  Shapes are per-device local."""
    hd = cfg.resolved_head_dim
    h, kvh = cfg.n_heads, cfg.n_kv_heads
    head_sharded = ctx.head_sharded and h % max(ctx.tp, 1) == 0
    tp = max(ctx.tp, 1)
    if head_sharded:
        kv_local = max(kvh // tp, 1) if tp > 1 else kvh
    else:
        kv_local = kvh
    cap_local = capacity // _shard_count(ctx, cache_seq_axes)

    def attn_cache():
        return {"k": jnp.zeros((b_local, cap_local, kv_local, hd), dtype),
                "v": jnp.zeros((b_local, cap_local, kv_local, hd), dtype)}

    def mamba_cache():
        d_in = cfg.d_inner
        hl = (cfg.ssm_heads or d_in // cfg.ssm_head_dim) // tp
        k = cfg.ssm_conv
        return {
            "ssm": jnp.zeros((b_local, hl, cfg.ssm_head_dim, cfg.ssm_state), dtype),
            "conv": {
                "x": jnp.zeros((b_local, k - 1, d_in // tp), dtype),
                "b": jnp.zeros((b_local, k - 1, cfg.ssm_state), dtype),
                "c": jnp.zeros((b_local, k - 1, cfg.ssm_state), dtype),
            },
        }

    def cross_cache():
        # cross-attention KV over encoder frames (seq-sharded over model)
        t = (enc_len or cfg.encoder_frames)
        t_local = t // (tp if not head_sharded and tp > 1 else 1)
        kvl = kv_local
        return {"k": jnp.zeros((b_local, t_local, kvl, hd), dtype),
                "v": jnp.zeros((b_local, t_local, kvl, hd), dtype)}

    def block_cache(code: str):
        c: dict[str, Any] = {}
        if code in ("A", "L", "E", "D"):
            c["attn"] = attn_cache()
        else:
            c["mamba"] = mamba_cache()
        if cfg.is_encoder_decoder:
            c["cross"] = cross_cache()
        return c

    period_cache = tuple(block_cache(c) for c in cfg.period)
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (cfg.n_periods,) + x.shape), period_cache)
    cache: dict[str, Any] = {
        "layers": stacked,
        "len": jnp.zeros((), jnp.int32),
    }
    if cfg.prelude:
        cache["prelude"] = tuple(block_cache(c) for c in cfg.prelude)
    return cache


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _block_forward(code: str, p, x, cfg, ctx, *, mode, cache, pos,
                   cache_seq_axes, enc_out=None, use_rope=True,
                   long_serve=False):
    """One transformer block.  Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict[str, Any] = {}
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if code in ("A", "L", "E", "D"):
        window_override = None
        if long_serve and code == "A" and cfg.long_context_window:
            window_override = cfg.long_context_window
        with jax.named_scope("attention"):
            attn_out, c = attention_forward(
                p["attn"], h, cfg, ctx, kind=code, mode=mode,
                cache=cache.get("attn") if cache else None, pos_offset=pos,
                cache_seq_axes=cache_seq_axes,
                window_override=window_override, use_rope=use_rope)
        if c is not None:
            new_cache["attn"] = c
    else:
        attn_out, c = mamba2.mamba_forward(
            p["mamba"], h, cfg, ctx, mode=mode,
            cache=cache.get("mamba") if cache else None)
        if c is not None:
            new_cache["mamba"] = c
    if cfg.post_norms:
        attn_out = rms_norm(attn_out, p["norm1_post"], cfg.norm_eps)
    x = x + attn_out

    if "cross" in p and (enc_out is not None or
                         (cache is not None and "cross" in cache)):
        hc = rms_norm(x, p["norm_cross"], cfg.norm_eps)
        cross_out, c = _cross_attention(p["cross"], hc, cfg, ctx, mode=mode,
                                        enc_out=enc_out,
                                        cache=cache.get("cross") if cache else None)
        if c is not None:
            new_cache["cross"] = c
        x = x + cross_out

    if "norm2" in p:
        h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
        if "moe" in p:
            ffn_out, aux = moe.moe_forward(p["moe"], h2, cfg, ctx)
        else:
            with jax.named_scope("mlp"):
                ffn_out = mlp_forward(p["mlp"], h2, cfg, ctx)
        if cfg.post_norms:
            ffn_out = rms_norm(ffn_out, p["norm2_post"], cfg.norm_eps)
        x = x + ffn_out
    return x, (new_cache or None), aux


def _cross_attention(p, x, cfg, ctx, *, mode, enc_out, cache):
    """Encoder-decoder cross attention (whisper).  Non-causal over frames."""
    from .layers import (_maybe_qk_norm, _project_qkv, chunked_attention,
                         combine_decode_partials, decode_attention_local)
    b, s, d = x.shape
    head_sharded = ctx.head_sharded and cfg.n_heads % max(ctx.tp, 1) == 0
    if mode in ("train", "prefill") or cache is None:
        # compute fresh K,V from encoder output
        q, _, _ = _project_qkv(p, x, cfg, ctx)
        _, k, v = _project_qkv(p, enc_out, cfg, ctx)
        if not head_sharded and ctx.tp > 1:
            # q is full-heads on the rank's seq chunk in the self-attn path;
            # for cross attention we keep q full-seq (simplest correct form)
            pass
        out = chunked_attention(q, k, v, causal=False, softcap=None,
                                chunk_q=min(512, s), chunk_k=min(1024, k.shape[1]))
        out = out.reshape(b, s, -1)
        y = out @ p["wo"]
        if head_sharded and ctx.tp > 1:
            y = ctx.psum_tp(y)
        elif ctx.tp > 1:
            pass  # q used full heads + full kv: replicated compute, no psum
        new_cache = None
        if mode == "prefill":
            if not head_sharded and ctx.tp > 1:
                # shard cross-KV over model on the frame dim
                t = k.shape[1] // ctx.tp
                r = ctx.tp_index()
                k = jax.lax.dynamic_slice_in_dim(k, r * t, t, axis=1)
                v = jax.lax.dynamic_slice_in_dim(v, r * t, t, axis=1)
            new_cache = {"k": k, "v": v}
        return y, new_cache
    # decode: attend over cached cross KV
    q, _, _ = _project_qkv(p, x, cfg, ctx)
    valid = jnp.ones((cache["k"].shape[1],), bool)
    m, l, acc = decode_attention_local(q, cache["k"], cache["v"], valid, None)
    axes = ("model",) if (not head_sharded and ctx.tp > 1) else ()
    out = combine_decode_partials(m, l, acc, ctx, axes)
    y = out.reshape(b, 1, -1).astype(x.dtype) @ p["wo"]
    if head_sharded and ctx.tp > 1:
        y = ctx.psum_tp(y)
    return y, {"k": cache["k"], "v": cache["v"]}


def _encoder_apply(params, defs: ModelDefs, frames, cfg, ctx):
    """Whisper encoder: sinusoidal pos + bidirectional blocks (scanned)."""
    b, t, d = frames.shape
    x = frames + sinusoidal_positions(t, d)[None].astype(frames.dtype)
    x = ctx.pvary_tp(x)

    def body(x, p_slice):
        p = gather_tree(p_slice, defs.enc_period, ctx)[0]
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        attn_out, _ = attention_forward(p["attn"], h, cfg, ctx, kind="A",
                                        mode="train", use_rope=False,
                                        causal=False)
        x = x + attn_out
        h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
        x = x + mlp_forward(p["mlp"], h2, cfg, ctx)
        return x, None

    x, _ = jax.lax.scan(body, x, params["encoder"]["layers"])
    fn = gather_tree({"w": params["encoder"]["final_norm"]},
                     {"w": defs.storage["encoder"]["final_norm"]}, ctx)["w"]
    return rms_norm(x, fn, cfg.norm_eps)


def model_apply(params, defs: ModelDefs, batch: dict, ctx: ParallelContext,
                *, mode: str = "train", cache: dict | None = None,
                compute_dtype=jnp.float32, remat: bool = True,
                long_serve: bool = False,
                cache_seq_axes: tuple[str, ...] | None = None):
    """Returns (logits_loc (b, s, V/tp) fp32, new_cache, aux_loss)."""
    cfg = defs.cfg
    tokens = batch["tokens"]
    b, s = tokens.shape
    embed_p = gather_tree(params["embed"], defs.storage["embed"], ctx)
    with jax.named_scope("embed"):
        x = embed_lookup(embed_p, tokens, cfg, ctx, dtype=compute_dtype)
    x = ctx.pvary_tp(x)  # vma consistency for the period-scan carry

    enc_out = None
    if cfg.is_encoder_decoder and "enc_frames" in batch:
        enc_out = _encoder_apply(params, defs, batch["enc_frames"].astype(compute_dtype),
                                 cfg, ctx)
    if cfg.is_encoder_decoder:
        pos_emb = gather_tree({"pe": params["pos_emb"]},
                              {"pe": defs.storage["pos_emb"]}, ctx)["pe"]
        if mode == "decode":
            pos_idx = cache["len"] + jnp.arange(s)
        else:
            pos_idx = jnp.arange(s)
        x = x + jnp.take(pos_emb, pos_idx, axis=0)[None].astype(x.dtype)
        use_rope = False
    else:
        use_rope = True

    pos = cache["len"] if (cache is not None and mode == "decode") else 0
    cs_axes = (cache_seq_axes if cache_seq_axes is not None
               else cache_seq_axes_for(cfg, ctx, b * ctx.dp))

    aux_total = jnp.zeros((), jnp.float32)
    new_prelude_cache = []
    for i, code in enumerate(cfg.prelude):
        p = gather_tree(params["prelude"][i], defs.prelude[i], ctx)
        c_in = cache["prelude"][i] if cache is not None and "prelude" in cache else None
        x, c_out, aux = _block_forward(code, p, x, cfg, ctx, mode=mode,
                                       cache=c_in, pos=pos,
                                       cache_seq_axes=cs_axes, enc_out=enc_out,
                                       use_rope=use_rope, long_serve=long_serve)
        aux_total = aux_total + aux
        new_prelude_cache.append(c_out)

    def period_body(x, slices):
        p_slice, c_slice = slices
        p = gather_tree(p_slice, defs.period, ctx)
        new_cs = []
        aux_p = jnp.zeros((), jnp.float32)
        for j, code in enumerate(cfg.period):
            cj = None
            if c_slice is not None:
                cj = jax.tree.map(lambda a: a, c_slice[j])
            x, cj_new, aux = _block_forward(
                code, p[j], x, cfg, ctx, mode=mode, cache=cj, pos=pos,
                cache_seq_axes=cs_axes, enc_out=enc_out, use_rope=use_rope,
                long_serve=long_serve)
            aux_p = aux_p + aux
            new_cs.append(cj_new if cj_new is not None else
                          (jax.tree.map(lambda a: a, cj) if cj is not None else None))
        ys = (tuple(new_cs), aux_p) if cache is not None or mode == "prefill" \
            else (None, aux_p)
        return x, ys

    body = period_body
    if remat and mode == "train":
        # remat=True -> full recompute; remat="dots" -> keep matmul outputs
        # resident (less recompute HBM traffic at ~1.3x activation memory;
        # see EXPERIMENTS.md section Perf)
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if remat == "dots" else None)
        body = jax.checkpoint(period_body, prevent_cse=False, policy=policy)

    layer_cache = cache["layers"] if cache is not None else None
    # "layers": the stack's own work around its blocks' attention and mlp
    # (norms, residual adds, slicing the stacked weights per layer)
    with jax.named_scope("layers"):
        x, (new_layer_cache, aux_per) = jax.lax.scan(
            body, x, (params["layers"], layer_cache))
    aux_total = aux_total + jnp.sum(aux_per)

    final_w = gather_tree({"w": params["final_norm"]},
                          {"w": defs.storage["final_norm"]}, ctx)["w"]
    x = rms_norm(x, final_w, cfg.norm_eps)
    with jax.named_scope("head_loss"):
        logits = logits_local(embed_p, x, cfg, ctx)

    new_cache = None
    if mode in ("prefill", "decode"):
        new_cache = {"layers": new_layer_cache,
                     "len": (cache["len"] + s) if cache is not None else
                            jnp.asarray(s, jnp.int32)}
        if cfg.prelude:
            new_cache["prelude"] = tuple(new_prelude_cache)
    return logits, new_cache, aux_total


def train_loss(params, defs: ModelDefs, batch: dict, ctx: ParallelContext,
               compute_dtype=jnp.float32, remat: bool = True):
    logits, _, aux = model_apply(params, defs, batch, ctx, mode="train",
                                 compute_dtype=compute_dtype, remat=remat)
    cfg = defs.cfg
    with jax.named_scope("head_loss"):
        loss = sharded_softmax_xent(logits, batch["labels"], cfg, ctx)
    # aux is replicated compute but vma-varying over 'model'; it MUST be made
    # invariant before differentiation or every gradient is scaled by tp
    # (grad-inside-shard_map of a varying scalar sums the per-rank replicas).
    aux = ctx.invariant_mean_tp(aux)
    total = loss + cfg.router_aux_weight * aux
    return total, {"ce": loss, "aux": aux}


def greedy_decode_step(params, defs: ModelDefs, tokens, cache, ctx,
                       compute_dtype=jnp.float32, long_serve: bool = False,
                       cache_seq_axes: tuple[str, ...] | None = None):
    logits, new_cache, _ = model_apply(params, defs,
                                       {"tokens": tokens}, ctx, mode="decode",
                                       cache=cache, compute_dtype=compute_dtype,
                                       remat=False, long_serve=long_serve,
                                       cache_seq_axes=cache_seq_axes)
    next_ids = sharded_greedy_sample(logits[:, -1:, :], ctx)
    return next_ids, new_cache


def init_params(defs: ModelDefs, key, ctx: ParallelContext | None = None):
    """Materialize logical (tp-local, single-node) params — CPU tests."""
    tp = ctx.tp if ctx is not None else 1
    return materialize_logical(defs.storage, key, tp=tp)
