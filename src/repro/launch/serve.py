"""Serving: batched single-token decode with a sharded KV/SSM cache.

``build_serve_setup`` produces the jit'd ``serve_step``:

    state = {params, cache, tokens}  ->  state'   (greedy next token)

Sharding rules (DESIGN.md):
  * batch over (pod, data) when global_batch >= dp; otherwise the cache
    *sequence* is sharded over data(+pod) and batch is replicated
    (long_500k b=1) with flash-decode log-sum-exp combine;
  * head-sharded archs: kv-head dim over `model`; seq-sharded archs
    (whisper/granite/smollm): cache sequence over `model`;
  * mamba: SSM state heads over `model`.

Decode serving uses consensus-complete parameters: a single replica layout
(n_nodes=1) — serving does not run the consensus exchange (DESIGN.md
§Arch-applicability).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.models.params import ParamDef
from repro.models.sharding import ParallelContext, make_context

__all__ = ["ServeSetup", "build_serve_setup", "build_prefill_setup",
           "cache_partition_specs"]


@dataclasses.dataclass
class ServeSetup:
    cfg: ModelConfig
    ctx: ParallelContext
    defs: T.ModelDefs
    mesh: jax.sharding.Mesh
    serve_step: Any
    state_shape: Any
    state_sharding: Any
    cache_seq_axes: tuple[str, ...]
    b_local: int


def _batch_axes(ctx: ParallelContext):
    return ("pod", "data") if ctx.pod_axis is not None else ("data",)


def cache_partition_specs(cfg: ModelConfig, ctx: ParallelContext,
                          batch_sharded: bool, cache_seq_axes: tuple[str, ...]):
    """PartitionSpec tree matching transformer.init_cache's structure."""
    head_sharded = ctx.head_sharded and cfg.n_heads % max(ctx.tp, 1) == 0
    baxes = _batch_axes(ctx)
    b_spec = (baxes if len(baxes) > 1 else baxes[0]) if batch_sharded else None
    seq_spec = (cache_seq_axes if len(cache_seq_axes) > 1
                else (cache_seq_axes[0] if cache_seq_axes else None))
    kv_spec = "model" if (head_sharded and ctx.tp > 1) else None
    # when the seq axes already include 'model' (seq-sharded archs) the kv
    # head dim must not also use it
    if cache_seq_axes and "model" in cache_seq_axes:
        kv_spec = None

    def attn():
        s = P(b_spec, seq_spec, kv_spec, None)
        return {"k": s, "v": s}

    def mamba():
        h_spec = "model" if ctx.tp > 1 else None
        return {
            "ssm": P(b_spec, h_spec, None, None),
            "conv": {
                "x": P(b_spec, None, "model" if ctx.tp > 1 else None),
                "b": P(b_spec, None, None),
                "c": P(b_spec, None, None),
            },
        }

    def cross():
        t_spec = "model" if (not head_sharded and ctx.tp > 1) else None
        s = P(b_spec, t_spec, kv_spec, None)
        return {"k": s, "v": s}

    def block(code: str):
        c: dict[str, Any] = {}
        if code in ("A", "L", "E", "D"):
            c["attn"] = attn()
        else:
            c["mamba"] = mamba()
        if cfg.is_encoder_decoder:
            c["cross"] = cross()
        return c

    def stack_spec(spec: P) -> P:
        return P(None, *spec)

    period = tuple(jax.tree.map(stack_spec, block(c),
                                is_leaf=lambda x: isinstance(x, P))
                   for c in cfg.period)
    out: dict[str, Any] = {"layers": period, "len": P()}
    if cfg.prelude:
        out["prelude"] = tuple(block(c) for c in cfg.prelude)
    return out


def build_serve_setup(
    cfg: ModelConfig,
    mesh: jax.sharding.Mesh,
    *,
    global_batch: int,
    capacity: int,
    compute_dtype=jnp.float32,
    cache_dtype=None,
    long_serve: bool = False,
    param_layout: str = "fsdp",     # 'fsdp' | 'replicated'
) -> ServeSetup:
    """param_layout:

    'fsdp'       — params sharded over data x model (min HBM); every decode
                   step all-gathers each layer's weights over the data
                   subgroup — collective-bound for single-token decode.
    'replicated' — weight-stationary decode: params sharded over `model`
                   only, replicated across `data`.  No per-step param
                   gathers; HBM/chip grows by the fsdp factor.  The section
                   Perf hillclimb on jamba decode_32k motivates this.
    """
    ctx = make_context(mesh, consensus_nodes=1)
    if param_layout == "replicated":
        # fsdp degree 1: gather_replica becomes a no-op inside the step
        ctx = dataclasses.replace(ctx, n_nodes=ctx.data_size)
    defs = T.build_defs(cfg, ctx, dtype=compute_dtype)
    cache_dtype = cache_dtype or compute_dtype

    cs_axes = T.cache_seq_axes_for(cfg, ctx, global_batch)
    batch_sharded = global_batch % ctx.dp == 0 and global_batch >= ctx.dp
    b_local = global_batch // ctx.dp if batch_sharded else global_batch

    # param specs / shapes
    if param_layout == "replicated":
        from repro.models.params import (ParamDef, storage_partition_spec,
                                         storage_shape_dtype)
        is_def = lambda x: isinstance(x, ParamDef)
        p_shapes = jax.tree.map(
            lambda d: storage_shape_dtype(d, ctx.tp, 1, 1),
            defs.storage, is_leaf=is_def)
        p_specs = jax.tree.map(
            lambda d: storage_partition_spec(d, data_axes=()),
            defs.storage, is_leaf=is_def)
    else:
        from repro.launch.train import _param_shapes, _param_specs
        p_shapes = _param_shapes(defs.storage, ctx)
        p_specs = _param_specs(defs.storage, ctx)

    cache_spec = cache_partition_specs(cfg, ctx, batch_sharded, cs_axes)
    # global cache shapes: local shapes expanded by the spec'd axis sizes
    cache_local = jax.eval_shape(
        lambda: T.init_cache(cfg, ctx, b_local, capacity, cs_axes,
                             dtype=cache_dtype))

    def expand(shape_struct, spec):
        shape = list(shape_struct.shape)
        for d, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            for a in axes:
                shape[d] *= ctx.axis_size_of(a)
        return jax.ShapeDtypeStruct(tuple(shape), shape_struct.dtype)

    cache_shape = jax.tree.map(expand, cache_local, cache_spec,
                               is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    tok_spec = P(_batch_axes(ctx) if len(_batch_axes(ctx)) > 1
                 else _batch_axes(ctx)[0], None) if batch_sharded else P(None, None)
    state_shape = {"params": p_shapes, "cache": cache_shape,
                   "tokens": jax.ShapeDtypeStruct((global_batch, 1), jnp.int32)}
    state_spec = {"params": p_specs, "cache": cache_spec, "tokens": tok_spec}

    def step_body(state):
        tokens = state["tokens"]
        next_ids, new_cache = T.greedy_decode_step(
            state["params"], defs, tokens, state["cache"], ctx,
            compute_dtype=compute_dtype, long_serve=long_serve,
            cache_seq_axes=cs_axes)
        return {"params": state["params"], "cache": new_cache,
                "tokens": next_ids}

    step_sm = jax.shard_map(step_body, mesh=mesh, in_specs=(state_spec,),
                            out_specs=state_spec, check_vma=False)
    serve_step = jax.jit(step_sm, donate_argnums=(0,))

    return ServeSetup(
        cfg=cfg, ctx=ctx, defs=defs, mesh=mesh, serve_step=serve_step,
        state_shape=state_shape,
        state_sharding=jax.tree.map(
            lambda s: NamedSharding(mesh, s), state_spec,
            is_leaf=lambda x: isinstance(x, P)),
        cache_seq_axes=cs_axes, b_local=b_local)


@dataclasses.dataclass
class PrefillSetup:
    cfg: ModelConfig
    ctx: ParallelContext
    defs: T.ModelDefs
    mesh: jax.sharding.Mesh
    prefill_step: Any
    params_shape: Any
    params_sharding: Any
    batch_sharding: Any


def build_prefill_setup(cfg: ModelConfig, mesh: jax.sharding.Mesh, *,
                        global_batch: int, seq_len: int,
                        compute_dtype=jnp.float32) -> PrefillSetup:
    """Inference prefill: full-sequence forward building the decode cache."""
    ctx = make_context(mesh, consensus_nodes=1)
    defs = T.build_defs(cfg, ctx, dtype=compute_dtype)
    from repro.launch.train import _param_shapes, _param_specs
    p_shapes = _param_shapes(defs.storage, ctx)
    p_specs = _param_specs(defs.storage, ctx)
    cs_axes = T.cache_seq_axes_for(cfg, ctx, global_batch)
    baxes = _batch_axes(ctx)
    batch_sharded = global_batch % ctx.dp == 0 and global_batch >= ctx.dp
    b_spec = (baxes if len(baxes) > 1 else baxes[0]) if batch_sharded else None
    batch_spec = {"tokens": P(b_spec, None)}
    if cfg.frontend == "audio_frames":
        batch_spec["enc_frames"] = P(b_spec, None, None)
    cache_spec = cache_partition_specs(cfg, ctx, batch_sharded, cs_axes)
    cache_spec.pop("len", None)
    cache_spec["len"] = P()

    def step_body(params, batch):
        logits, cache, _ = T.model_apply(
            params, defs, batch, ctx, mode="prefill", cache=None,
            compute_dtype=compute_dtype, remat=False, cache_seq_axes=cs_axes)
        from repro.models.layers import sharded_greedy_sample
        next_ids = sharded_greedy_sample(logits[:, -1:, :], ctx)
        return next_ids, cache

    tok_out_spec = P(b_spec, None)
    step_sm = jax.shard_map(
        step_body, mesh=mesh, in_specs=(p_specs, batch_spec),
        out_specs=(tok_out_spec, cache_spec), check_vma=False)
    prefill_step = jax.jit(step_sm)
    return PrefillSetup(
        cfg=cfg, ctx=ctx, defs=defs, mesh=mesh, prefill_step=prefill_step,
        params_shape=p_shapes,
        params_sharding=jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                                     p_specs, is_leaf=lambda x: isinstance(x, P)),
        batch_sharding=jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                                    batch_spec, is_leaf=lambda x: isinstance(x, P)))
