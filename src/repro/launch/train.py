"""Distributed train-step builder + CLI training driver.

``build_train_step`` assembles the full decentralized training step:

    shard_map over the production mesh
      ├─ per-device microbatch forward/backward (FSDP gather inside the
      │  period scan; tensor-parallel collectives inside the model)
      ├─ local optimizer step (per consensus node)
      └─ ADC-DGD compressed consensus exchange (core.distributed)

Storage layout / shardings come from the ParamDef trees (models.params).

CLI:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --algorithm adc_dgd --steps 50 --nodes 2 ...
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import wire, wireplan
from repro.core.distributed import ConsensusConfig, ConsensusRuntime
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.models.params import (ParamDef, local_block_shape,
                                 storage_partition_spec, storage_shape_dtype)
from repro.models.sharding import ParallelContext, make_context
from repro.optim import by_name as opt_by_name
from repro.optim.schedules import (constant_schedule, cosine_warmup_schedule,
                                   inverse_power_schedule)

__all__ = ["TrainSetup", "build_train_setup", "train_state_specs",
           "batch_partition_spec", "build_exchange_probe",
           "measure_consensus_overhead", "main"]


@dataclasses.dataclass
class TrainSetup:
    cfg: ModelConfig
    ctx: ParallelContext
    defs: T.ModelDefs
    mesh: jax.sharding.Mesh
    consensus: ConsensusRuntime
    optimizer: Any
    schedule: Any
    compute_dtype: Any
    train_step: Any          # jit'd (state, batch) -> (state, metrics)
    state_shape: Any         # ShapeDtypeStructs of the train state
    state_sharding: Any
    batch_sharding: Any


def _data_axes(ctx: ParallelContext) -> tuple[str, ...]:
    return ("pod", "data") if ctx.pod_axis is not None else ("data",)


def batch_partition_spec(ctx: ParallelContext, global_batch: int,
                         extra_dims: int = 1) -> P:
    """Batch sharded over (pod, data) when divisible, else replicated."""
    axes = _data_axes(ctx)
    if global_batch % ctx.dp == 0 and global_batch >= ctx.dp:
        lead = axes if len(axes) > 1 else axes[0]
        return P(lead, *([None] * extra_dims))
    return P(*([None] * (extra_dims + 1)))


def _model_axes(ctx: ParallelContext) -> tuple[str, ...]:
    """The model axis, where it shards something.  The train-state specs
    never name a size-1 ``model`` axis: named, it would type every leaf
    the exchange unpacks from the packed buffer as varying over it, and the
    model-replicated leaves could then not leave ``shard_map`` without a
    collective that moves nothing (cf. ``_invariant_over_model``)."""
    return (ctx.tp_axis,) if ctx.tp > 1 else ()


def _param_specs(defs_tree, ctx: ParallelContext):
    data_axes = _data_axes(ctx)
    tp_axis = ctx.tp_axis if _model_axes(ctx) else None
    return jax.tree.map(
        lambda d: storage_partition_spec(d, data_axes=data_axes,
                                         tp_axis=tp_axis),
        defs_tree, is_leaf=lambda x: isinstance(x, ParamDef))


def _param_shapes(defs_tree, ctx: ParallelContext):
    return jax.tree.map(
        lambda d: storage_shape_dtype(d, ctx.tp, ctx.total_consensus_nodes,
                                      ctx.fsdp),
        defs_tree, is_leaf=lambda x: isinstance(x, ParamDef))


def _mesh_lead_axes(ctx: ParallelContext) -> tuple[str, ...]:
    """Every mesh axis that shards something, pod-major — the leading dim
    of the packed consensus buffers is sharded over all of them (each
    device owns its own packing of its local parameter shard)."""
    return (*_data_axes(ctx), *_model_axes(ctx))


def _invariant_over_model(x_next, defs: T.ModelDefs, ctx: ParallelContext):
    """Retype the exchange's model-replicated leaves as invariant over
    ``model`` (their out_specs do not name it).

    The exchange packs every leaf of a device's shard into one buffer that
    varies over all mesh axes, so each leaf it returns is typed varying
    over ``model``.  The model-replicated ones (``ParamDef.tp_dim is None``:
    norms, replicated projections) hold equal values on every model rank,
    because the quantization noise is shared across ``model``
    (``core.distributed._device_key``).  One ``pmax`` over ``model`` of
    their concatenation returns those same values, typed invariant: exact,
    and the one collective over ``model`` that the exchange adds.  On
    ``tp == 1`` meshes no spec names ``model`` and nothing is done."""
    if ctx.tp == 1:
        return x_next
    leaves, treedef = jax.tree.flatten(x_next)
    flat_defs = jax.tree.leaves(defs.storage,
                                is_leaf=lambda x: isinstance(x, ParamDef))
    rep = [i for i, (d, x) in enumerate(zip(flat_defs, leaves))
           if d.tp_dim is None and ctx.tp_axis in jax.typeof(x).vma]
    if not rep:
        return x_next
    flat = jax.lax.pmax(wire.lift_concat(
        [leaves[i].astype(jnp.float32).reshape(-1) for i in rep]),
        ctx.tp_axis)
    start = 0
    for i in rep:
        x = leaves[i]
        leaves[i] = flat[start:start + x.size].reshape(x.shape).astype(
            x.dtype)
        start += x.size
    return jax.tree.unflatten(treedef, leaves)


def consensus_wire_layout(defs: T.ModelDefs, ctx: ParallelContext,
                          consensus: ConsensusRuntime | None = None
                          ) -> wire.WireLayout:
    """The static packing plan for one device's local parameter shard.

    Pass the runtime when one exists: ``ConsensusRuntime.state_layout``
    applies the mixed-plan grouped placement (core.wireplan), and the
    heterogeneous payload size — e.g. the async in-flight buffer shape —
    must be computed on the SAME buffer order the exchange packs."""
    local = jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(
            local_block_shape(d, ctx.tp, ctx.fsdp), d.dtype),
        defs.storage, is_leaf=lambda x: isinstance(x, ParamDef))
    if consensus is not None:
        return consensus.state_layout(local)
    return wire.WireLayout.for_tree(local)


def train_state_specs(defs: T.ModelDefs, ctx: ParallelContext,
                      consensus: ConsensusRuntime, optimizer):
    """(ShapeDtypeStruct tree, PartitionSpec tree) for the full train state."""
    p_shapes = _param_shapes(defs.storage, ctx)
    p_specs = _param_specs(defs.storage, ctx)
    state_shape = {"params": p_shapes, "step": jax.ShapeDtypeStruct((), jnp.int32)}
    state_spec = {"params": p_specs, "step": P()}
    # consensus shadows live PACKED (core.wire): per device one
    # (n_rows, BLOCK) fp32 buffer per shadow; globally a leading device
    # dim sharded over every mesh axis.
    if consensus.cfg.algorithm == "adc_dgd":
        layout = consensus_wire_layout(defs, ctx, consensus)
        lead = _mesh_lead_axes(ctx)
        n_dev = ctx.pods * ctx.data_size * ctx.tp
        packed = jax.ShapeDtypeStruct((n_dev, layout.n_rows, layout.block),
                                      jnp.float32)
        packed_spec = P(lead, None, None)
        state_shape["consensus"] = {"x_tilde": packed, "m_agg": packed}
        state_spec["consensus"] = {"x_tilde": packed_spec,
                                   "m_agg": packed_spec}
        if consensus.cfg.push_sum_enabled:
            # push-sum weight scalar + last-seen neighbor weights (the
            # stale fallback under link loss) — per device, device-major
            state_shape["consensus"]["ps_w"] = jax.ShapeDtypeStruct(
                (n_dev, 1), jnp.float32)
            state_shape["consensus"]["ps_nbr"] = jax.ShapeDtypeStruct(
                (n_dev, 2), jnp.float32)
            state_spec["consensus"]["ps_w"] = P(lead, None)
            state_spec["consensus"]["ps_nbr"] = P(lead, None)
        if consensus.cfg.wire_packing == "async":
            # the async exchange's in-flight payload triple (core.wire
            # INFLIGHT_KEYS): one flat uint8 wire payload per entry,
            # carried across the step boundary
            nbytes = consensus.wire_plan_for(layout).payload_bytes
            if consensus.cfg.push_sum_enabled:
                nbytes += wireplan.PUSH_SUM_TRAILER_BYTES
            fly = jax.ShapeDtypeStruct((n_dev, nbytes), jnp.uint8)
            for fk in wire.INFLIGHT_KEYS:
                state_shape["consensus"][fk] = fly
                state_spec["consensus"][fk] = P(lead, None)
    else:
        state_shape["consensus"] = {}
        state_spec["consensus"] = {}
    # optimizer state mirrors params (structurally — see Optimizer.state_spec)
    state_shape["opt"] = jax.eval_shape(optimizer.init, p_shapes)
    state_spec["opt"] = optimizer.state_spec(p_specs)
    return state_shape, state_spec


def build_train_setup(
    cfg: ModelConfig,
    mesh: jax.sharding.Mesh,
    *,
    consensus_nodes: int = 4,
    algorithm: str = "adc_dgd",
    gamma: float = 1.0,
    quant_mode: str = "fixed",
    fixed_step0: float = 1e-3,
    optimizer: str = "sgd",
    schedule: str = "constant",
    lr: float = 1e-2,
    eta: float = 0.5,
    warmup: int = 100,
    total_steps: int = 1000,
    compute_dtype=jnp.float32,
    remat: bool | str = True,           # True | 'dots' | False (see model_apply)
    use_pallas: bool = False,
    track_consensus_error: bool = False,
    global_batch: int | None = None,
    seq_len: int | None = None,
    microbatches: int = 1,              # gradient accumulation (activation
                                        # memory / microbatches per step)
    ring_strides: tuple[int, ...] = (1,),  # time-varying node-ring schedule
    schedule_period: int = 1,              # steps between ring re-wirings
    wire_packing: str = "packed",          # packed | pipelined | per_leaf | async
    pipeline_chunks: int = 4,              # chunks for wire_packing="pipelined"
    staleness: int = 1,                    # async gossip staleness (0 = eager)
    wire_codec: str = "int8",              # codec name | "mixed:..." plan spec
    byte_budget: float | None = None,      # bytes/step target (controller)
    seed: int = 0,                         # consensus quantization-noise seed
    topology: str = "ring",                # ring | directed-ring (push-sum)
    forward_weight: float | None = None,   # directed-ring upstream in-weight
    link_loss: float | None = None,        # Bernoulli packet-loss rate
    loss_seed: int = 0,                    # loss-mask seed (core.faults)
    push_sum: bool | None = None,          # force push-sum weight threading
    link_loss_model: str = "bernoulli",    # bernoulli | gilbert:p=..,r=..
    resync_retries: int = 3,               # bounded resync handshake retries
    straggle_rate: float | None = None,    # async deadline-miss rate
    straggle_seed: int = 0,                # straggler-mask seed (core.faults)
    membership: tuple | None = None,       # per-epoch active-node masks
    telemetry: bool = False,               # in-trace telemetry counters
    hierarchy=None,                        # two-level consensus: "pods=P" |
                                           # int | HierarchySpec (core.hierarchy)
) -> TrainSetup:
    ctx = make_context(mesh, consensus_nodes)
    defs = T.build_defs(cfg, ctx, dtype=compute_dtype)
    ccfg = ConsensusConfig(
        algorithm=algorithm, gamma=gamma, quant_mode=quant_mode,
        fixed_step0=fixed_step0, use_pallas=use_pallas,
        track_consensus_error=track_consensus_error,
        ring_strides=tuple(ring_strides), schedule_period=schedule_period,
        wire_packing=wire_packing, pipeline_chunks=pipeline_chunks,
        staleness=staleness,
        wire_codec=wire_codec, byte_budget=byte_budget,
        topology=topology, forward_weight=forward_weight,
        link_loss=link_loss, loss_seed=loss_seed, push_sum=push_sum,
        link_loss_model=link_loss_model, resync_retries=resync_retries,
        straggle_rate=straggle_rate, straggle_seed=straggle_seed,
        membership=membership, telemetry=telemetry, hierarchy=hierarchy)
    consensus = ConsensusRuntime(ccfg, ctx)
    opt = opt_by_name(optimizer)
    if schedule == "constant":
        sched = constant_schedule(lr)
    elif schedule == "inverse_power":
        sched = inverse_power_schedule(lr, eta)
    else:
        sched = cosine_warmup_schedule(lr, warmup, total_steps)

    state_shape, state_spec = train_state_specs(defs, ctx, consensus, opt)
    batch_spec = {
        "tokens": batch_partition_spec(ctx, global_batch or ctx.dp),
        "labels": batch_partition_spec(ctx, global_batch or ctx.dp),
    }
    if cfg.frontend == "audio_frames":
        batch_spec["enc_frames"] = batch_partition_spec(
            ctx, global_batch or ctx.dp, extra_dims=2)

    def step_body(state, batch):
        """Per-device code (inside shard_map)."""
        k = state["step"] + 1

        def loss_fn(params, mb):
            return T.train_loss(params, defs, mb, ctx,
                                compute_dtype=compute_dtype, remat=remat)

        if microbatches > 1:
            # gradient accumulation: scan over microbatch slices so only one
            # microbatch's activations are live at a time (the section Perf
            # memory-term lever for the biggest train combos)
            def split(x):
                b = x.shape[0]
                assert b % microbatches == 0, (b, microbatches)
                return x.reshape(microbatches, b // microbatches, *x.shape[1:])

            mbs = jax.tree.map(split, batch)

            def mb_step(acc, mb):
                (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    state["params"], mb)
                g_acc, l_acc = acc
                return (jax.tree.map(jnp.add, g_acc, g), l_acc + l), None

            # first microbatch outside the scan: its (grads, loss) carry the
            # correct vma types for the scan carry (zeros would be invariant
            # and fail the carry type check under check_vma=True)
            (l0, _), g0 = jax.value_and_grad(loss_fn, has_aux=True)(
                state["params"], jax.tree.map(lambda x: x[0], mbs))
            (grads, loss), _ = jax.lax.scan(
                mb_step, (g0, l0), jax.tree.map(lambda x: x[1:], mbs))
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = loss / microbatches
            parts = None
        else:
            (loss, parts), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state["params"], batch)
        # fsdp-transposed grads arrive summed over the node's microbatch
        # shards; normalize to the node-mean objective f_i.
        if ctx.fsdp > 1:
            grads = jax.tree.map(lambda g: g / ctx.fsdp, grads)
        lr_k = sched(k)
        with jax.named_scope("optimizer"):
            x_half, opt_state = opt.step(state["opt"], state["params"], grads,
                                         lr_k)
        # consensus noise stream rooted at the run seed (folded per step;
        # _device_key folds in the node coordinates) — independent runs must
        # not share quantization noise or their stochastic-rounding errors
        # would be correlated across replicas of an experiment
        key = jax.random.fold_in(jax.random.PRNGKey(seed), k)
        # packed consensus shadows carry a leading per-device dim of 1
        # inside shard_map (the global buffers are device-major)
        cons_in = jax.tree.map(lambda a: a[0], state["consensus"])
        x_next, cons_state, cmetrics = consensus.exchange(
            state["params"], x_half, cons_in, k, key)
        x_next = _invariant_over_model(x_next, defs, ctx)
        cons_state = jax.tree.map(
            lambda a: wire.pvary_to(a, _mesh_lead_axes(ctx))[None],
            cons_state)
        new_state = {"params": x_next, "opt": opt_state,
                     "consensus": cons_state, "step": k}
        # metrics: average over exactly the axes each value varies on
        metrics = {"loss": ctx.mean_metric(loss), "lr": lr_k}
        if parts is not None and cfg.router_aux_weight:
            metrics["aux"] = ctx.mean_metric(parts["aux"])
        for k2, v in cmetrics.items():
            metrics[k2] = ctx.mean_metric(v)
        return new_state, metrics

    in_specs = (state_spec, batch_spec)
    out_specs = (state_spec, {"loss": P(), "lr": P(),
                              "collectives_per_step": P(),
                              "wire_bytes_per_step": P(),
                              **({"aux": P()} if cfg.router_aux_weight and microbatches == 1 else {}),
                              **({"overflow_frac": P(), "residual_norm": P()}
                                 if algorithm == "adc_dgd" else {}),
                              **({"push_sum_weight": P()}
                                 if ccfg.push_sum_enabled else {}),
                              **({"wire_bytes_delivered": P(),
                                  "delivered_frac": P()}
                                 if ccfg.faults_enabled else {}),
                              **({"deadline_miss_frac": P()}
                                 if ccfg.straggle_rate is not None else {}),
                              **({"active_nodes": P()}
                                 if ccfg.membership is not None else {}),
                              **{k: P() for k in ccfg.telemetry_metric_keys()},
                              **({"consensus_err": P()} if track_consensus_error else {})})

    step_sm = jax.shard_map(step_body, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=True)
    train_step = jax.jit(step_sm, donate_argnums=(0,))

    return TrainSetup(
        cfg=cfg, ctx=ctx, defs=defs, mesh=mesh, consensus=consensus,
        optimizer=opt, schedule=sched, compute_dtype=compute_dtype,
        train_step=train_step, state_shape=state_shape,
        state_sharding=jax.tree.map(
            lambda s: NamedSharding(mesh, s), state_spec,
            is_leaf=lambda x: isinstance(x, P)),
        batch_sharding=jax.tree.map(
            lambda s: NamedSharding(mesh, s), batch_spec,
            is_leaf=lambda x: isinstance(x, P)),
    )


def init_consensus_state(setup: TrainSetup, params) -> Any:
    """Packed consensus shadows for global storage params: pack each
    device's local shard inside shard_map (the layout is device-local)."""
    if setup.consensus.cfg.algorithm != "adc_dgd":
        return {}
    ctx = setup.ctx
    _, state_spec = train_state_specs(setup.defs, ctx, setup.consensus,
                                      setup.optimizer)
    lead = _mesh_lead_axes(ctx)

    def pack_local(p):
        st = setup.consensus.init_state(p)
        return jax.tree.map(lambda a: wire.pvary_to(a, lead)[None], st)

    init_sm = jax.shard_map(pack_local, mesh=setup.mesh,
                            in_specs=(state_spec["params"],),
                            out_specs=state_spec["consensus"])
    return jax.jit(init_sm)(params)


def init_train_state(setup: TrainSetup, key: jax.Array | int):
    """Materialize a real train state (small configs / examples / tests).

    ``key`` may be a PRNG key or a plain int seed (CLI ``--seed``)."""
    from repro.models.params import materialize_storage_host
    if isinstance(key, int):
        key = jax.random.PRNGKey(key)
    ctx = setup.ctx
    host_params = materialize_storage_host(
        setup.defs.storage, key, ctx.tp, ctx.total_consensus_nodes, ctx.fsdp)
    params = jax.tree.map(jnp.asarray, host_params)
    state = {
        "params": params,
        "opt": setup.optimizer.init(params),
        "consensus": init_consensus_state(setup, params),
        "step": jnp.zeros((), jnp.int32),
    }
    return jax.device_put(state, setup.state_sharding)


def build_exchange_probe(setup: TrainSetup):
    """A compiled consensus-exchange-only step (no model fwd/bwd): the
    numerator of the ``consensus_overhead_frac`` runtime metric (exchange
    time / step time).  Returns None when the setup runs no adc_dgd
    exchange."""
    ctx = setup.ctx
    cons = setup.consensus
    if cons.cfg.algorithm != "adc_dgd" or ctx.total_consensus_nodes <= 1:
        return None
    _, state_spec = train_state_specs(setup.defs, ctx, cons, setup.optimizer)
    lead = _mesh_lead_axes(ctx)

    def body(params, cons_state, k):
        key = jax.random.fold_in(jax.random.PRNGKey(0), k)
        cons_in = jax.tree.map(lambda a: a[0], cons_state)
        x_next, cons_out, _ = cons.exchange(params, params, cons_in, k, key)
        x_next = _invariant_over_model(x_next, setup.defs, ctx)
        cons_out = jax.tree.map(
            lambda a: wire.pvary_to(a, lead)[None], cons_out)
        return x_next, cons_out

    sm = jax.shard_map(
        body, mesh=setup.mesh,
        in_specs=(state_spec["params"], state_spec["consensus"], P()),
        out_specs=(state_spec["params"], state_spec["consensus"]),
        check_vma=True)
    return jax.jit(sm)


def measure_consensus_overhead(setup: TrainSetup, state,
                               step_time_s: float | None,
                               repeats: int = 5) -> dict:
    """Time the exchange alone against the measured full-step time.

    Returns {"consensus_exchange_s": median exchange seconds} plus, when a
    step time is supplied, {"consensus_overhead_frac": exchange / step} —
    the fraction the async transport is designed to drive toward zero
    (an upper bound for overlapped modes: the wall-clock the exchange
    *can* take, not what the step actually serializes on).
    """
    probe = build_exchange_probe(setup)
    if probe is None:
        return {}
    k = jnp.asarray(int(state["step"]) + 1, jnp.int32)
    out = probe(state["params"], state["consensus"], k)   # compile + warm
    jax.block_until_ready(out)
    times = []
    for _ in range(repeats):
        t = time.perf_counter()
        out = probe(state["params"], state["consensus"], k)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t)
    res = {"consensus_exchange_s": float(np.median(times))}
    if step_time_s:
        res["consensus_overhead_frac"] = res["consensus_exchange_s"] / step_time_s
    return res


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _step_range(text: str) -> tuple[int, int]:
    """``A:B`` -> (A, B), the steps A to B-1."""
    try:
        a, b = (int(t) for t in text.split(":"))
    except ValueError:
        raise argparse.ArgumentTypeError(f"expected A:B, got {text!r}")
    if not 0 <= a < b:
        raise argparse.ArgumentTypeError(f"need 0 <= A < B, got {text!r}")
    return a, b


def main(argv=None):
    from repro.configs import get_config, reduced
    from repro.data import SyntheticLMDataset
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_cpu_mesh

    ap = argparse.ArgumentParser(description="decentralized LM training")
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true", help="smoke-size model")
    ap.add_argument("--algorithm", default="adc_dgd",
                    choices=["adc_dgd", "dgd", "compressed_dgd", "allreduce", "none"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--nodes", type=int, default=1)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-2)
    ap.add_argument("--gamma", type=float, default=1.0)
    ap.add_argument("--ring-strides", default="1",
                    help="comma-separated node-ring strides cycled per "
                         "schedule epoch (time-varying topology), e.g. 1,2")
    ap.add_argument("--schedule-period", type=int, default=1,
                    help="steps between ring re-wirings")
    ap.add_argument("--wire-packing", default="packed",
                    choices=["packed", "pipelined", "per_leaf", "async"],
                    help="consensus wire strategy (pipelined = chunked "
                         "double-buffered exchange; async = one-step-stale "
                         "exchange overlapped with the next step's fwd/bwd, "
                         "DESIGN.md §Async overlap)")
    ap.add_argument("--pipeline-chunks", type=int, default=4,
                    help="chunk count for --wire-packing=pipelined")
    ap.add_argument("--staleness", type=int, default=1, choices=[0, 1],
                    help="gossip staleness of --wire-packing=async: 1 "
                         "retires the previous step's in-flight payload "
                         "(overlapped); 0 is the eager bit-identity fixture")
    ap.add_argument("--wire-codec", default="int8",
                    help="packed-exchange payload codec (DESIGN.md §Wire "
                         "codecs): int8 | int4 | int2 | topk | topk:k=<int> "
                         "| adaptive; 'adaptive' hands the choice to the "
                         "AdaptiveBitController, which re-selects the bit "
                         "budget every --codec-period steps from residual/"
                         "overflow/consensus-error feedback and "
                         "--byte-budget")
    ap.add_argument("--wire-plan", default=None,
                    help="wire-plan spec (DESIGN.md §Wire plans): a codec "
                         "name or 'mixed:pattern=codec,...' mapping leaf "
                         "paths to codecs, e.g. "
                         "'mixed:norm=int2,embed=int4,*=int8'.  Overrides "
                         "--wire-codec; with --wire-codec adaptive the "
                         "controller shifts the plan's hot-slot tier and "
                         "pins the cold slots")
    ap.add_argument("--byte-budget", type=float, default=None,
                    help="bytes/step ring budget (both directions) for the "
                         "adaptive controller's candidate filter")
    ap.add_argument("--codec-period", type=int, default=25,
                    help="steps per adaptive-controller epoch")
    ap.add_argument("--topology", default="ring",
                    choices=["ring", "directed-ring"],
                    help="consensus graph of the node ring: directed-ring "
                         "is column-stochastic only and switches the "
                         "exchange to push-sum (ratio) consensus "
                         "(DESIGN.md §Push-sum wire)")
    ap.add_argument("--forward-weight", type=float, default=None,
                    help="directed-ring upstream in-weight in "
                         "(0, 1 - self_weight); default 2(1-w_ii)/3")
    ap.add_argument("--link-loss", type=float, default=None,
                    help="per-directed-edge Bernoulli packet-loss rate in "
                         "[0, 1); dropped payloads fall back to the stale "
                         "x_tilde estimate (core.faults.LossModel)")
    ap.add_argument("--loss-seed", type=int, default=0,
                    help="seed of the deterministic loss masks")
    ap.add_argument("--link-loss-model", default="bernoulli",
                    help="link-loss process: 'bernoulli' (i.i.d., rate from "
                         "--link-loss) or 'gilbert:p=..,r=..[,h=..][,g=..]' "
                         "— a two-state Markov burst-loss channel "
                         "(core.faults.GilbertElliottLoss)")
    ap.add_argument("--resync-retries", type=int, default=3,
                    help="bounded retransmit attempts of the epoch-boundary "
                         "resync handshake under link loss (a failed "
                         "handshake keeps the stale m_agg one more epoch)")
    ap.add_argument("--straggle", type=float, default=None,
                    help="per-node-direction deadline-miss rate in [0, 1) "
                         "for --wire-packing=async: an in-flight payload "
                         "that misses its one-step deadline is treated as "
                         "dropped (stale x_tilde reuse, core.faults."
                         "StragglerModel)")
    ap.add_argument("--straggle-seed", type=int, default=0,
                    help="seed of the deterministic straggler masks")
    ap.add_argument("--hierarchy", default=None,
                    help="two-level consensus spec 'pods=P' (DESIGN.md "
                         "§Hierarchical consensus): every pod of nodes/P "
                         "consecutive nodes psum-averages its optimizer "
                         "delta (uncompressed fp32 inner level), then one "
                         "representative per pod runs the compressed ADC "
                         "exchange over the P-pod outer ring (any "
                         "--wire-packing / wire plan; --node-failures then "
                         "churns PODS, so masks index the outer ring).  "
                         "pods=nodes is the flat ring bit-for-bit; pods=1 "
                         "is --algorithm allreduce bit-for-bit")
    ap.add_argument("--codec-ladder", default=None,
                    help="comma-separated AdaptiveBitController ladder, "
                         "coarsest first — e.g. 'topk:k=16,topk:k=32,"
                         "topk:k=64,topk:k=128,topk:k=256' for "
                         "variance-adaptive top-k (rungs ranked by "
                         "code_max * coverage; priced at 64+k+2 bytes/row); "
                         "default int2,int4,int8")
    ap.add_argument("--node-failures", default=None,
                    help="elastic-membership spec 'node@start:end[;...]' — "
                         "node inactive for schedule epochs [start, end), "
                         "e.g. '2@1:3;0@4:6' (topology.MembershipSchedule); "
                         "survivors re-form a compacted ring with "
                         "Metropolis-Hastings weights")
    ap.add_argument("--seed", type=int, default=0,
                    help="run seed: parameter init AND the consensus "
                         "quantization-noise stream")
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--schedule", default="constant")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--telemetry", action="store_true",
                    help="structured telemetry (core.telemetry, DESIGN.md "
                         "§Observability): per-step counter records + host "
                         "events to obs/telemetry-{run_id}.jsonl (schema "
                         "telemetry/v1); also turns on the in-trace "
                         "telemetry counters of the exchange")
    ap.add_argument("--telemetry-dir", default="obs",
                    help="sink directory for --telemetry")
    ap.add_argument("--run-id", default=None,
                    help="telemetry run id (default: a wall-clock stamp)")
    ap.add_argument("--profile-dir", default=None,
                    help="write a JAX profiler trace of --profile-steps "
                         "here: the device timeline with the program's "
                         "named scopes, for Perfetto or TensorBoard")
    ap.add_argument("--profile-steps", type=_step_range, default=(2, 4),
                    metavar="A:B",
                    help="with --profile-dir: trace steps A to B-1 "
                         "(default 2:4, past the compiling first steps)")
    args = ap.parse_args(argv)
    if args.profile_dir and args.profile_steps[0] >= args.steps:
        raise SystemExit(f"--profile-steps {args.profile_steps[0]}:... "
                         f"starts past the run's {args.steps} steps")
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.wire_codec != "adaptive" and args.wire_plan is None:
        from repro.core import codec as wcodec
        try:
            wcodec.by_name(args.wire_codec)       # fail at the CLI, clearly
        except KeyError as e:
            raise SystemExit(f"--wire-codec: {e.args[0]}") from None
    mesh = make_cpu_mesh(data=args.data, model=args.model)

    hierarchy_spec = None
    if args.hierarchy:
        from repro.core.hierarchy import HierarchySpec
        hierarchy_spec = HierarchySpec.from_spec(args.hierarchy)
        hierarchy_spec.pod_size(args.nodes)  # divisibility: fail at the CLI

    membership_masks = None
    epoch_events = {}
    if args.node_failures:
        from repro.core.topology import MembershipSchedule
        # under hierarchy the churn unit is the POD: masks index the outer
        # ring of pod representatives, not individual nodes
        ring_n = hierarchy_spec.pods if hierarchy_spec is not None else args.nodes
        sched = MembershipSchedule.from_spec(args.node_failures, ring_n)
        membership_masks = sched.masks
        epoch_events = {ev["epoch"]: ev for ev in sched.epoch_events()}

    tel = None
    if args.telemetry:
        from repro.core import telemetry as tele
        run_id = args.run_id or time.strftime("%Y%m%d-%H%M%S")
        git_sha = None
        try:
            import subprocess
            git_sha = subprocess.run(
                ["git", "rev-parse", "HEAD"], capture_output=True,
                text=True, timeout=5).stdout.strip() or None
        except Exception:
            pass
        tel = tele.Telemetry(run_id, out_dir=args.telemetry_dir,
                             config=dict(vars(args)), git_sha=git_sha)
        print(f"[telemetry] -> {tel.path}")

    setups: dict[str, TrainSetup] = {}

    def setup_for(codec_name: str) -> TrainSetup:
        # one cached setup (and thus one compiled step trace) per codec:
        # ppermute payload widths are static, so codec switches swap the
        # whole trace at epoch boundaries instead of re-tracing in-graph
        if codec_name not in setups:
            setups[codec_name] = build_train_setup(
                cfg, mesh, consensus_nodes=args.nodes,
                algorithm=args.algorithm, optimizer=args.optimizer,
                schedule=args.schedule, lr=args.lr, gamma=args.gamma,
                global_batch=args.batch, seq_len=args.seq,
                microbatches=args.microbatches,
                ring_strides=tuple(int(s)
                                   for s in args.ring_strides.split(",")),
                schedule_period=args.schedule_period,
                wire_packing=args.wire_packing,
                pipeline_chunks=args.pipeline_chunks,
                staleness=args.staleness,
                wire_codec=codec_name, byte_budget=args.byte_budget,
                seed=args.seed, topology=args.topology,
                forward_weight=args.forward_weight,
                link_loss=args.link_loss, loss_seed=args.loss_seed,
                link_loss_model=args.link_loss_model,
                resync_retries=args.resync_retries,
                straggle_rate=args.straggle,
                straggle_seed=args.straggle_seed,
                membership=membership_masks,
                telemetry=args.telemetry,
                hierarchy=hierarchy_spec,
                track_consensus_error=(args.algorithm != "allreduce"))
        return setups[codec_name]

    from repro.core import wireplan
    plan_spec = (wireplan.parse_spec(args.wire_plan)
                 if args.wire_plan else None)

    def spec_for(tier: str) -> str:
        """Map a controller ladder tier to the wire_codec string the setup
        is built with (plan mode: shift the hot slots, pin the cold).
        The hot codec comes from the BUILT plan when the controller holds
        one — a spec rule that matches no slot of the real layout must not
        absorb the re-tier while the shipped slots stay pinned."""
        if plan_spec is None:
            return tier
        hot = (controller.plan.hot_codec
               if controller is not None and controller.plan is not None
               else None)
        return plan_spec.with_hot_tier(tier, hot=hot).to_string()

    controller = None
    n_elements_global = None
    codec_name = args.wire_codec
    if plan_spec is not None and args.wire_codec != "adaptive":
        codec_name = plan_spec.to_string()
    if args.wire_codec == "adaptive":
        from repro.core.codec import AdaptiveBitController
        if args.algorithm != "adc_dgd":
            raise SystemExit("--wire-codec adaptive requires adc_dgd")
        if args.wire_packing == "per_leaf":
            # fail now, not at the controller's first sub-byte pick N
            # steps in (per-leaf speaks int8 only)
            raise SystemExit("--wire-codec adaptive requires the packed or "
                             "pipelined transport (per_leaf is int8-only)")
        probe_ctx = make_context(mesh, args.nodes)
        probe_defs = T.build_defs(cfg, probe_ctx)
        probe_layout = consensus_wire_layout(probe_defs, probe_ctx)
        n_rows = probe_layout.n_rows
        n_elements_global = (probe_layout.n_elements * probe_ctx.fsdp
                             * probe_ctx.tp)
        ladder_kw = {}
        if args.codec_ladder:
            ladder_kw["ladder"] = tuple(
                s.strip() for s in args.codec_ladder.split(",") if s.strip())
        controller = AdaptiveBitController(byte_budget=args.byte_budget,
                                           gamma=args.gamma, **ladder_kw)
        if plan_spec is not None and not plan_spec.is_uniform:
            # plan mode: candidates re-tier the hot slots of this plan;
            # price on the grouped buffer order the runtime actually ships
            codecs = tuple(plan_spec.codec_for_path(s.path)
                           for s in probe_layout.slots)
            placement = wireplan.grouped_placement(probe_layout, codecs)
            if placement is not None:
                probe_layout = probe_layout.with_placement(placement)
            controller.plan = plan_spec.build(probe_layout)
        tier = controller.initial(n_rows)
        codec_name = spec_for(tier)
        print(f"[codec] controller start: {codec_name} "
              f"(budget={args.byte_budget})")

    setup = setup_for(codec_name)

    def emit_wire_plan_event(at_step: int) -> None:
        """Host-side snapshot of the shipped wire geometry (telemetry/v1
        ``wire_plan`` event): plan runs + layout slots + the unified byte
        accounting the in-trace counters are derived from."""
        if tel is None or args.algorithm != "adc_dgd":
            return
        layout = consensus_wire_layout(setup.defs, setup.ctx,
                                       setup.consensus)
        acct = setup.consensus.wire_accounting(layout.n_elements,
                                               layout=layout)
        data = dict(codec=codec_name, layout=layout.describe())
        if acct is not None:
            data.update(wire_bytes_per_step=acct.shipped_per_step,
                        shipped_payload=acct.shipped_payload,
                        trailer_bytes=acct.trailer_bytes,
                        inner_bytes=acct.inner_bytes)
        if hierarchy_spec is not None:
            data["hierarchy"] = hierarchy_spec.describe(args.nodes)
        if args.wire_packing in ("packed", "pipelined", "async"):
            plan = setup.consensus.wire_plan_for(layout)
            data["plan"] = plan.describe()
            chunks = (args.pipeline_chunks
                      if args.wire_packing == "pipelined" else None)
            fb = plan.fallback_fragments(chunks)
            data["fallback_fragments"] = fb
            if fb:
                # grouped placement could not align every codec-run edge:
                # these fragments take the jnp reference path even when
                # the Pallas kernels are on
                tel.event("kernel_fallback", step=at_step, codec=codec_name,
                          fragments=fb, reordered=bool(layout.placement),
                          use_pallas=setup.consensus.cfg.use_pallas)
        if setup.consensus.loss is not None:
            data["channel"] = setup.consensus.loss.describe()
        if setup.consensus.straggler is not None:
            data["straggler"] = setup.consensus.straggler.describe()
        tel.event("wire_plan", step=at_step, **data)

    emit_wire_plan_event(0)
    state = init_train_state(setup, args.seed)
    ds_kw = {}
    if cfg.frontend == "audio_frames":
        ds_kw = dict(enc_frames=cfg.encoder_frames, d_model=cfg.d_model)
    ds = SyntheticLMDataset(cfg.vocab_size, args.seq, args.batch,
                            n_shards=setup.ctx.dp, **ds_kw)

    t0 = time.time()
    ep_res, ep_ovf, ep_ce = [], [], []
    step_times: list[float] = []
    overhead = {}
    overhead_setup = None
    prev_epoch = 0
    if tel is not None and membership_masks is not None:
        tel.event("membership_epoch", step=0, epoch=0,
                  active=int(sum(membership_masks[0])),
                  mask=list(membership_masks[0]))
    profile_from, profile_to = args.profile_steps
    for step in range(args.steps):
        if args.profile_dir and step == profile_from:
            jax.profiler.start_trace(args.profile_dir)
        with jax.profiler.StepTraceAnnotation("train", step_num=step):
            rows = ds.global_batch_arrays(step)
            with jax.profiler.TraceAnnotation("input.transfer"):
                batch = jax.device_put(rows, setup.batch_sharding)
            ts = time.perf_counter()
            state, metrics = setup.train_step(state, batch)
            jax.block_until_ready(metrics)
            dur = time.perf_counter() - ts
        if args.profile_dir and step + 1 == min(profile_to, args.steps):
            jax.profiler.stop_trace()
            print(f"[profile] steps {profile_from}:{step + 1} -> "
                  f"{args.profile_dir}")
        if step >= 2:                 # skip compile + cache-warm steps
            step_times.append(dur)
        if tel is not None:
            mfloat = {k: float(v) for k, v in metrics.items()}
            mfloat["step_s"] = dur
            tel.record_step(step + 1, mfloat)
            if mfloat.get("resync_fired", 0.0) > 0.5:
                tel.event("resync", step=step + 1,
                          ok=mfloat.get("resync_ok", 0.0) > 0.5)
            if membership_masks is not None:
                e = min((step + 1) // max(args.schedule_period, 1),
                        len(membership_masks) - 1)
                if e != prev_epoch:
                    ev = epoch_events.get(e, {})
                    tel.event("membership_epoch", step=step + 2, epoch=e,
                              active=int(sum(membership_masks[e])),
                              mask=list(membership_masks[e]),
                              joined=ev.get("joined", []),
                              departed=ev.get("departed", []))
                    prev_epoch = e
        if controller is not None:
            ep_res.append(float(metrics["residual_norm"]))
            ep_ovf.append(float(metrics["overflow_frac"]))
            if "consensus_err" in metrics:
                # squared disagreement summed over shards -> per-element
                # RMS, the scale target()'s fidelity need works on
                ep_ce.append(float(np.sqrt(
                    max(float(metrics["consensus_err"]), 0.0)
                    / max(n_elements_global, 1))))
            if (step + 1) % args.codec_period == 0:
                tier = controller.select(
                    next_step=step + 2,
                    residual_rms=float(np.mean(ep_res)),
                    overflow_frac=float(np.mean(ep_ovf)),
                    n_rows=n_rows,
                    consensus_err=(float(np.mean(ep_ce)) if ep_ce else None))
                new = spec_for(tier)
                if tel is not None:
                    tel.event(
                        "codec_decision", step=step + 1,
                        old=codec_name, new=new, tier=tier,
                        residual_rms=float(np.mean(ep_res)),
                        overflow_frac=float(np.mean(ep_ovf)),
                        consensus_rms=(float(np.mean(ep_ce))
                                       if ep_ce else None),
                        candidates=controller.candidate_table(n_rows))
                if new != codec_name:
                    print(f"[codec] step {step + 1}: {codec_name} -> {new} "
                          f"(residual_rms={np.mean(ep_res):.3g}, "
                          f"overflow={np.mean(ep_ovf):.3g}"
                          + (f", consensus_rms={np.mean(ep_ce):.3g}"
                             if ep_ce else "") + ")")
                    if tel is not None and controller.plan is not None:
                        tel.event("plan_retier", step=step + 1,
                                  old=codec_name, new=new, tier=tier)
                    codec_name = new
                    setup = setup_for(new)
                    emit_wire_plan_event(step + 2)
                ep_res, ep_ovf, ep_ce = [], [], []
        if step % max(1, args.steps // 10) == 0 or step == args.steps - 1:
            m = jax.tree.map(float, metrics)
            if step_times and args.algorithm == "adc_dgd":
                # exchange time / step time, measured on the live state; the
                # compiled probe is rebuilt only when the controller swaps
                # the step trace (codec re-tier)
                if overhead_setup is not setup:
                    overhead = measure_consensus_overhead(
                        setup, state, float(np.median(step_times)))
                    overhead_setup = setup
                elif "consensus_exchange_s" in overhead:
                    overhead["consensus_overhead_frac"] = (
                        overhead["consensus_exchange_s"]
                        / float(np.median(step_times)))
                m.update(overhead)
            extra = " ".join(f"{k}={v:.4g}" for k, v in m.items() if k != "loss")
            print(f"step {step:5d} loss={m['loss']:.4f} "
                  f"codec={codec_name} {extra}")
        if (args.checkpoint_dir and args.checkpoint_every
                and (step + 1) % args.checkpoint_every == 0):
            from repro.checkpoint import save_checkpoint
            save_checkpoint(args.checkpoint_dir, step + 1, jax.device_get(state))
    print(f"done: {args.steps} steps in {time.time()-t0:.1f}s")
    if tel is not None:
        tel.event("run_end", step=args.steps,
                  wall_s=time.time() - t0,
                  steps_per_s=(1.0 / float(np.median(step_times))
                               if step_times else None),
                  **{k: v for k, v in overhead.items()})
        tel.close()
        print(f"[telemetry] wrote {tel.path}")


if __name__ == "__main__":
    main()
