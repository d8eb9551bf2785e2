"""Distributed ADC-DGD runtime: compressed parameter consensus inside shard_map.

The consensus graph is a ring over the flattened ``(pod, data)`` device axes
factored by the intra-node FSDP degree:

    node(flat_idx) = flat_idx // fsdp,   flat ring shift = +-fsdp

so every device exchanges *only its own FSDP x TP parameter shard* with the
peer holding the same shard coordinates in the neighbor node — consensus
traffic is fully sharded, and inter-pod ring edges land on the slow links
the paper targets.

Per step k (paper Algorithm 2, k^gamma folded into the quantizer step —
DESIGN.md §Hardware adaptation):

    y_i   = x_i^k - x_tilde_i                (x^{k+1/2} = after local opt step)
    codes = StochasticQuant(y_i; step_k)      step_k = step0 / k^gamma (fixed
                                              mode) or per-block max (adaptive)
    ppermute codes+scales to ring neighbors (int8 wire)
    x_tilde_i += dec(codes)                   (identical on sender & receivers)
    m_i       += w_side * (dec(left) + dec(right))
    x_i^{k+1}  = w_self * x_tilde_i + m_i + (x^{k+1/2} - x_i^k)  [gradient step
                 applied on top of the consensus combine, cf. Eq. (6)]

State: x_tilde (self estimate) and m_agg (incremental
sum_{j!=i} W_ij x_tilde_j) — O(1) memory in node degree (DESIGN.md) — held
**persistently in packed wire form**: one ``(n_rows, BLOCK)`` fp32 buffer
spanning every leaf of the parameter tree (:class:`repro.core.wire.
WireLayout`).  The default ``wire_packing="packed"`` hot path therefore
runs ONE quantize launch, ONE byte-payload ``ppermute`` per ring direction
(two collectives per step total, independent of leaf count), and ONE fused
dequant-combine launch per step.  ``wire_packing="pipelined"`` splits the
packed buffer into ``pipeline_chunks`` tile-aligned row slices
(:class:`repro.core.wire.ChunkedLayout`) and double-buffers the exchange:
chunk i's payload is in flight on both ring directions while chunk i+1 is
quantized and chunk i-1 is dequant-combined, hiding transfer latency
behind Pallas compute at the cost of 2 x pipeline_chunks collectives
(same wire bytes; bit-identical results for every chunk count).
``wire_packing="per_leaf"`` keeps the historical per-leaf wire path
(4 x n_leaves collectives per step) as a bit-identical reference for
tests and the ``consensus_step_latency`` benchmark (DESIGN.md §Hardware
adaptation).  ``wire_packing="async"`` double-buffers the *whole
exchange* across the step boundary (DESIGN.md §10): the step-k payload
is launched after the combine and retired at step k+1 (one-step-stale
gossip, ``staleness=1``), so the two ppermutes overlap the next step's
fwd/bwd; ``staleness=0`` dispatches to the eager packed path and is
bit-identical to it.  Epoch-boundary resyncs drain the in-flight
payload before rebuilding ``m_agg``.  The byte format of the packed/pipelined payload is set by
``wire_codec``, a **wire-plan spec** (:mod:`repro.core.wireplan`,
DESIGN.md §Wire plans): a bare codec name — int8 (historical), int4/int2
(sub-byte bit-packed) or topk (sparse bitmap + values) — is the uniform
back-compat plan, while ``"mixed:<pattern=codec,...>"`` assigns codecs per
leaf by path pattern.  Mixed plans keep ONE flat byte payload per ring
direction (per-run grouped kernel launches, prefix-sum byte offsets) and
pipeline chunks snap so none straddles a codec change; ``byte_budget``
feeds the epoch-level AdaptiveBitController that re-selects the plan's hot
tier from runtime feedback (launch/train.py).

Algorithms:
  adc_dgd        — the paper's contribution (wire = int8 codes + scales)
  dgd            — uncompressed DGD (wire = fp32 x)
  compressed_dgd — Eq. (5) direct compression (diverges; negative control)
  allreduce      — W = (1/N)11^T: psum-mean of the optimizer delta (classic
                   synchronous data parallelism; consensus error == 0)
  none           — isolated nodes (debugging control)

Time-varying topology (DESIGN.md §Topology schedules): ``ring_strides``
cycles the node ring's neighbor stride every ``schedule_period`` steps —
the shard_map counterpart of :class:`repro.core.topology.TopologySchedule`.
Each stride's ring permutation is a static ppermute wiring, so the runtime
dispatches between stride-specialized exchange traces with ``lax.switch``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec as wire_codec
from repro.core import faults, telemetry, wire, wireplan
from repro.core.hierarchy import HierarchySpec
from repro.kernels import ops as kops
from repro.models.sharding import ParallelContext

__all__ = ["ConsensusConfig", "ConsensusRuntime", "HierarchySpec"]


def _device_key(key, ctx: ParallelContext, group: int = 1):
    """Fold the device's data/pod coordinates into the PRNG key so
    quantization noise is independent across consensus nodes and FSDP shards.

    The ``model`` axis index is deliberately NOT folded in: parameter leaves
    that are replicated over the model axis (norms, replicated projections)
    must receive bit-identical stochastic rounding on every model rank or
    the replicas would drift apart.  Sharing the key across tp ranks is
    harmless for tp-sharded leaves (noise is still i.i.d. across *elements*;
    Definition 1 unbiasedness is per-element).

    ``group > 1`` (hierarchical consensus, DESIGN.md §14) folds the POD
    index instead of the node index: all ``group`` members of a pod hold
    identical post-inner-average parameters and must draw bit-identical
    quantization noise, or their x_tilde shadows would diverge and break
    the pod-replica invariant the outer exchange rests on.  FSDP ranks
    within a node still get independent streams.
    """
    if group > 1:
        flat = jnp.zeros((), jnp.int32)
        if ctx.data_size > 1:
            flat = jax.lax.axis_index(ctx.data_axis)
        if ctx.pod_axis is not None and ctx.pods > 1:
            flat = flat + ctx.data_size * jax.lax.axis_index(ctx.pod_axis)
        pod = flat // (ctx.fsdp * group)
        return jax.random.fold_in(key, pod * ctx.fsdp + flat % ctx.fsdp)
    if ctx.data_size > 1:
        key = jax.random.fold_in(key, jax.lax.axis_index(ctx.data_axis))
    if ctx.pod_axis is not None and ctx.pods > 1:
        key = jax.random.fold_in(key, jax.lax.axis_index(ctx.pod_axis))
    return key


@dataclasses.dataclass(frozen=True)
class ConsensusConfig:
    algorithm: str = "adc_dgd"     # adc_dgd | dgd | compressed_dgd | allreduce | none
    gamma: float = 1.0             # amplification exponent (paper gamma)
    self_weight: float = 0.5       # ring W_ii; each side gets (1 - W_ii)/2
    quant_mode: str = "fixed"      # fixed (paper-faithful) | adaptive
    fixed_step0: float = 1e-3      # Delta_0; effective step = Delta_0 / k^gamma
    use_pallas: bool = False       # interpret-mode kernels (tests) vs jnp ref
    wire_dtype: Any = jnp.float32  # uncompressed-exchange dtype (dgd baseline)
    track_consensus_error: bool = False
    #: time-varying ring schedule (DESIGN.md §Topology schedules): the node
    #: ring's neighbor stride cycles through ``ring_strides``, holding each
    #: for ``schedule_period`` steps.  stride s connects node i with i±s —
    #: every stride keeps W symmetric doubly stochastic with the same
    #: (self_weight, side_weight), so each epoch is a valid Section III-A
    #: matrix.  Individual epochs may be disconnected (gcd(s, n) > 1); the
    #: union over one cycle is jointly connected iff gcd(strides..., n) == 1,
    #: which ConsensusRuntime enforces.  (1,) == the static paper ring.
    ring_strides: tuple[int, ...] = (1,)
    schedule_period: int = 1       # steps between ring re-wirings
    #: wire strategy for the compressed exchanges (DESIGN.md §Hardware
    #: adaptation): "packed" flat-packs the whole parameter tree into one
    #: lane-aligned buffer — one quantize launch + one byte-payload
    #: ppermute per ring direction per step; "pipelined" splits the packed
    #: buffer into ``pipeline_chunks`` tile-aligned row slices and
    #: double-buffers them so chunk i's payload is in flight on both ring
    #: directions while chunk i+1 is quantized and chunk i-1 is
    #: dequant-combined (transfer hidden behind Pallas compute;
    #: bit-identical to "packed"); "per_leaf" is the historical
    #: bit-identical per-leaf reference (4 x n_leaves collectives/step),
    #: kept for equivalence tests and the consensus_step_latency benchmark;
    #: "async" is the one-step-stale exchange (DESIGN.md §Async overlap):
    #: step k's payload is put on the wire at the END of step k's exchange
    #: and its dequant-combine lands at the START of step k+1's, so the
    #: transfer has the whole of step k+1's fwd/bwd to complete behind —
    #: still exactly 2 ppermutes per step, gossip one step stale (CEDAS,
    #: arXiv:2301.05872; reference rule in core.consensus.CEDAS).
    wire_packing: str = "packed"   # packed | pipelined | per_leaf | async
    #: gossip staleness of the "async" transport: 1 retires the PREVIOUS
    #: step's in-flight payload (the overlapped mode); 0 retires the payload
    #: the same step it is launched — bit-identical to "packed" (the
    #: exactness fixture, tests/test_wire.py::test_async_*).
    staleness: int = 1
    #: chunk count for ``wire_packing="pipelined"`` (clamped to the packed
    #: buffer's TILE_N-tile count; ragged tails allowed).  More chunks hide
    #: more transfer latency but pay more launch/collective overhead —
    #: benchmarks/consensus_step.py sweeps this (EXPERIMENTS.md §Perf).
    pipeline_chunks: int = 4
    #: wire-plan spec of the packed/pipelined ADC exchange (DESIGN.md §Wire
    #: plans): a bare codec name — "int8" (historical, BLOCK codes + fp32
    #: scale per row), "int4"/"int2" (sub-byte bit-packed codes + bf16
    #: scale), "topk" (sparse bitmap + int8 values + bf16 scale) — is the
    #: back-compat uniform plan; "mixed:<pattern=codec,...>" assigns codecs
    #: per leaf by path pattern (core.wireplan grammar), e.g.
    #: "mixed:norm=int2,embed=int4,*=int8".  The per-leaf reference path
    #: and the compressed_dgd negative control speak uniform int8 only.
    wire_codec: str = "int8"
    #: optional bytes/step target (both ring directions) consumed by the
    #: AdaptiveBitController's candidate filter (core.codec) and surfaced
    #: alongside the wire accounting; the static exchange itself never
    #: reads it.
    byte_budget: float | None = None
    #: consensus graph of the node ring (DESIGN.md §Push-sum wire):
    #: "ring" is the historical symmetric doubly-stochastic ring;
    #: "directed-ring" makes the SAME ppermute wiring column-stochastic
    #: only — the upstream (i - stride) in-edge carries ``forward_weight``
    #: and the downstream one ``1 - self_weight - forward_weight`` — and
    #: switches the exchange to push-sum (ratio) consensus, mirroring
    #: :func:`repro.core.topology.directed_ring`.
    topology: str = "ring"
    #: directed-ring in-weight of the payload arriving from the upstream
    #: neighbor; None = the topology.directed_ring default
    #: 2 (1 - self_weight) / 3.
    forward_weight: float | None = None
    #: per-directed-edge Bernoulli packet-loss rate (core.faults.LossModel).
    #: ``None`` keeps the loss machinery out of the trace entirely; ``0.0``
    #: traces it but never drops (bit-identical values — tests pin this).
    link_loss: float | None = None
    loss_seed: int = 0
    #: loss-model family (core.faults.parse_loss_spec): "bernoulli" is the
    #: i.i.d. model whose rate comes from ``link_loss``;
    #: "gilbert:p=..,r=..[,h=..][,g=..]" selects the two-state Markov
    #: burst channel (GilbertElliottLoss) — its parameters live in the
    #: spec, so ``link_loss`` must stay None.  Either way the
    #: one-decision-per-direction-per-step packet contract holds, keeping
    #: packed and pipelined bit-identical under loss.
    link_loss_model: str = "bernoulli"
    #: retransmit budget of the epoch-boundary resync handshake: each ring
    #: direction's fp32 x_tilde transfer is retried up to this many times
    #: (core.faults._ResyncRetries); a node whose resync fails in either
    #: direction keeps its stale m_agg until the next boundary.  Only
    #: reachable when a loss model is configured — lossless resyncs always
    #: succeed.
    resync_retries: int = 3
    #: straggler-deadline miss probability of the async transport
    #: (core.faults.StragglerModel): an in-flight payload that has not
    #: arrived by its one-step retire deadline is treated as dropped
    #: (stale-x_tilde reuse, same decode path as link loss; independent
    #: PRNG domain).  None keeps the machinery out of the trace; requires
    #: wire_packing="async" with staleness=1 (the eager transports have no
    #: deadline to miss).
    straggle_rate: float | None = None
    straggle_seed: int = 0
    #: elastic membership (DESIGN.md §Elastic membership): a tuple of
    #: per-epoch active-node masks (tuple[tuple[bool, ...], ...], e.g.
    #: ``topology.MembershipSchedule.from_spec(...).masks``).  Epoch e uses
    #: ``masks[min(e, len-1)]`` — the last mask persists.  Inactive nodes
    #: are routed around (the ring permutation compacts over survivors),
    #: freeze their parameters/shadows in place, and carry zero payloads;
    #: the epoch-boundary resync rebuilds m_agg over each new active set.
    #: The surviving ring keeps the (self_weight, side, side) row rule,
    #: which IS Metropolis-Hastings reweighting at self_weight=1/3 (every
    #: compacted-ring degree is 2, so MH gives the uniform 1/3 row).
    #: ``None`` = no membership machinery; a single all-active mask is
    #: traced but inert (bit-identical values — tests pin this).
    membership: tuple | None = None
    #: push-sum weight threading: None = auto (on iff topology is
    #: directed); True forces the weight machinery on a symmetric ring
    #: (where it provably stays == 1 — the exactness fixture).
    push_sum: bool | None = None
    #: in-trace telemetry (core.telemetry, DESIGN.md §Observability):
    #: True adds the extra per-step counters — bytes shipped, raw
    #: saturation census, resync fired/ok, async staleness retirements —
    #: as metric outputs of the exchange (see telemetry_metric_keys()).
    #: False keeps the step trace BIT-IDENTICAL to a telemetry-less
    #: build: no extra outputs, no extra ops (tests/test_wire.py pins
    #: the jaxpr).
    telemetry: bool = False
    #: two-level hierarchical consensus (DESIGN.md §14, core.hierarchy):
    #: a :class:`~repro.core.hierarchy.HierarchySpec`, an int pod count,
    #: or the ``"pods=P"`` CLI grammar (normalized in __post_init__).
    #: Every pod of ``m = n // pods`` consecutive nodes psum-averages its
    #: optimizer delta (uncompressed fp32, the fast interconnect), then
    #: one representative per pod runs the compressed ADC exchange on the
    #: POD ring — the effective mixing is ``W_outer (x) (1/m) 11^T``.
    #: ``pods == n`` is bit-identical to the flat ring; ``pods == 1`` is
    #: bit-identical to ``algorithm="allreduce"``.  ``membership`` masks
    #: (and the fault models' receiver ids) then index PODS, not nodes.
    #: None = flat single-level consensus.
    hierarchy: "HierarchySpec | int | str | None" = None

    @property
    def schedule_varying(self) -> bool:
        """Does the wiring (stride or membership) ever change at an epoch
        boundary?  This is what makes the resync machinery necessary."""
        return (len(self.ring_strides) > 1
                or (self.membership is not None
                    and len(self.membership) > 1))

    def telemetry_metric_keys(self) -> tuple:
        """The extra metric keys the ADC exchange emits when
        ``telemetry=True`` — ONE source of truth shared by every
        exchange return path and train.py's out_specs (the shard_map
        pytree contract: every declared key on every path)."""
        if not self.telemetry or self.algorithm != "adc_dgd":
            return ()
        keys = ["wire_bytes_shipped", "saturated_count"]
        if self.hierarchy is not None:
            # per-level traffic split (DESIGN.md §14): intra-pod fp32
            # all-reduce bytes vs compressed inter-pod ring bytes
            keys += ["wire_bytes_inner", "wire_bytes_outer"]
        if self.schedule_varying:
            keys += ["resync_fired", "resync_ok"]
        if self.wire_packing == "async" and self.staleness == 1:
            keys.append("staleness_retired")
        return tuple(keys)

    @property
    def side_weight(self) -> float:
        return (1.0 - self.self_weight) / 2.0

    @property
    def in_weights(self) -> tuple[float, float]:
        """(upstream, downstream) receive weights of the node ring — equal
        ``side_weight`` for the symmetric ring, (forward, backward) for the
        directed one.  ``_ppermute_ring(+stride)`` delivers the upstream
        (i - stride) payload, whose directed-ring weight is the forward
        edge weight W[i, i-stride]."""
        if self.topology == "directed-ring":
            fwd = (2.0 * (1.0 - self.self_weight) / 3.0
                   if self.forward_weight is None else self.forward_weight)
            return (fwd, (1.0 - self.self_weight) - fwd)
        return (self.side_weight, self.side_weight)

    @property
    def push_sum_enabled(self) -> bool:
        if self.push_sum is not None:
            return self.push_sum
        return self.topology == "directed-ring"

    @property
    def loss_model(self):
        """The i.i.d. Bernoulli model (back-compat accessor; burst models
        need the node count — use :meth:`loss_model_for`)."""
        if self.link_loss is None:
            return None
        return faults.LossModel(rate=self.link_loss, seed=self.loss_seed)

    @property
    def loss_enabled(self) -> bool:
        """Any link-loss machinery in the trace (Bernoulli or burst)?"""
        return (self.link_loss is not None
                or faults.parse_loss_spec(self.link_loss_model)["kind"]
                != "bernoulli")

    @property
    def faults_enabled(self) -> bool:
        """Anything that can drop a payload (loss or straggler deadlines)
        — the gate for the delivered-bytes/fraction metrics."""
        return self.loss_enabled or self.straggle_rate is not None

    def loss_model_for(self, n_nodes: int):
        """The configured loss model bound to the consensus-node count
        (GilbertElliottLoss realizes one Markov chain per directed edge,
        so it needs ``n_nodes``), or None."""
        spec = faults.parse_loss_spec(self.link_loss_model)
        if spec["kind"] == "gilbert":
            return faults.GilbertElliottLoss(
                p=spec["p"], r=spec["r"], h=spec["h"], g=spec["g"],
                seed=self.loss_seed, n_nodes=n_nodes)
        if self.link_loss is None:
            return None
        return faults.LossModel(rate=self.link_loss, seed=self.loss_seed)

    @property
    def straggler_model(self):
        if self.straggle_rate is None:
            return None
        return faults.StragglerModel(rate=self.straggle_rate,
                                     seed=self.straggle_seed)

    def __post_init__(self):
        if not self.ring_strides:
            raise ValueError("ring_strides must be non-empty")
        if self.schedule_period < 1:
            raise ValueError(f"schedule_period must be >= 1, got "
                             f"{self.schedule_period}")
        if self.wire_packing not in ("packed", "pipelined", "per_leaf",
                                     "async"):
            raise ValueError(f"wire_packing must be 'packed', 'pipelined', "
                             f"'per_leaf' or 'async', got "
                             f"{self.wire_packing!r}")
        if self.pipeline_chunks < 1:
            raise ValueError(f"pipeline_chunks must be >= 1, got "
                             f"{self.pipeline_chunks}")
        if self.staleness not in (0, 1):
            raise ValueError(f"staleness must be 0 or 1, got "
                             f"{self.staleness}")
        if self.wire_packing == "async" and self.algorithm != "adc_dgd":
            raise ValueError(
                "wire_packing='async' is the one-step-stale ADC exchange; "
                f"algorithm={self.algorithm!r} does not support it")
        spec = wireplan.parse_spec(self.wire_codec)   # raises on bad specs
        if self.wire_packing == "per_leaf":
            if not spec.is_uniform:
                raise ValueError(
                    f"wire_codec={self.wire_codec!r} mixes codecs; the "
                    "per-leaf reference transport ships one uniform int8 "
                    "wire per leaf and cannot address a heterogeneous "
                    "payload — use the packed or pipelined transport")
            if spec.uniform_codec != "int8":
                raise ValueError(
                    f"wire_codec={self.wire_codec!r} requires the packed "
                    "or pipelined transport; the per-leaf reference path "
                    "speaks int8 only")
        if spec.uniform_codec != "int8" and self.algorithm == "compressed_dgd":
            raise ValueError(
                "compressed_dgd (the Eq. (5) negative control) is pinned "
                f"to the int8 wire; got wire_codec={self.wire_codec!r}")
        if self.byte_budget is not None and self.byte_budget <= 0:
            raise ValueError(f"byte_budget must be positive, got "
                             f"{self.byte_budget}")
        if self.topology not in ("ring", "directed-ring"):
            raise ValueError(f"topology must be 'ring' or 'directed-ring', "
                             f"got {self.topology!r}")
        directed = self.topology == "directed-ring"
        if directed and self.push_sum is False:
            raise ValueError(
                "directed-ring mixing is column-stochastic only; disabling "
                "push_sum would leave the iterates biased — drop "
                "push_sum=False or use topology='ring'")
        if self.forward_weight is not None:
            if not directed:
                raise ValueError("forward_weight only applies to the "
                                 "directed-ring topology")
            if not 0.0 < self.forward_weight < 1.0 - self.self_weight:
                raise ValueError(
                    f"forward_weight must be in (0, 1 - self_weight) = "
                    f"(0, {1.0 - self.self_weight}), got "
                    f"{self.forward_weight}")
        if self.link_loss is not None and not 0.0 <= self.link_loss < 1.0:
            raise ValueError(f"link_loss must be in [0, 1), got "
                             f"{self.link_loss}")
        loss_spec = faults.parse_loss_spec(self.link_loss_model)  # raises
        if loss_spec["kind"] != "bernoulli" and self.link_loss is not None:
            raise ValueError(
                "link_loss sets the Bernoulli rate; the gilbert burst "
                "model takes its parameters in link_loss_model — set one "
                "or the other, not both")
        if self.resync_retries < 1:
            raise ValueError(f"resync_retries must be >= 1, got "
                             f"{self.resync_retries}")
        if self.straggle_rate is not None:
            if not 0.0 <= self.straggle_rate < 1.0:
                raise ValueError(f"straggle_rate must be in [0, 1), got "
                                 f"{self.straggle_rate}")
            if self.wire_packing != "async" or self.staleness != 1:
                raise ValueError(
                    "straggler deadlines are a property of the one-step-"
                    "stale transport: straggle_rate requires "
                    "wire_packing='async' with staleness=1")
        if self.membership is not None:
            masks = self.membership
            if (not masks or not all(isinstance(m, tuple) for m in masks)
                    or len({len(m) for m in masks}) != 1):
                raise ValueError(
                    "membership must be a non-empty tuple of equal-length "
                    "per-epoch mask tuples (MembershipSchedule.masks)")
            for e, m in enumerate(masks):
                if sum(bool(b) for b in m) < 2:
                    raise ValueError(
                        f"membership epoch {e} keeps "
                        f"{sum(bool(b) for b in m)} active nodes; the "
                        "surviving ring needs >= 2")
            if self.wire_packing == "per_leaf":
                raise ValueError(
                    "membership requires the packed/pipelined/async "
                    "transports; the per-leaf reference path predates "
                    "elasticity")
            if self.push_sum_enabled or directed:
                raise ValueError(
                    "runtime membership supports the symmetric ring only; "
                    "push-sum mass handoff under churn is reference-side "
                    "(topology.MembershipSchedule.handoff_at + "
                    "consensus.run_elastic)")
        if self.hierarchy is not None:
            # normalize int / "pods=P" CLI specs into a HierarchySpec
            # (frozen dataclass, hence object.__setattr__)
            object.__setattr__(
                self, "hierarchy", HierarchySpec.from_spec(self.hierarchy))
            if self.algorithm != "adc_dgd":
                raise ValueError(
                    "hierarchy composes the inner all-reduce with the "
                    "compressed adc_dgd outer exchange; algorithm="
                    f"{self.algorithm!r} does not support it")
            if directed or self.push_sum_enabled:
                raise ValueError(
                    "hierarchical consensus supports the symmetric outer "
                    "ring only; directed/push-sum pod rings are a "
                    "follow-up (ROADMAP)")
            if self.wire_packing == "per_leaf":
                raise ValueError(
                    "hierarchy requires the packed/pipelined/async "
                    "transports; the per-leaf reference path predates it")
        if ((directed or self.push_sum or self.link_loss is not None
             or loss_spec["kind"] != "bernoulli"
             or self.straggle_rate is not None
             or self.membership is not None)
                and self.algorithm != "adc_dgd"):
            raise ValueError(
                "directed topology, push_sum, link loss, straggler "
                "deadlines and membership are features of the adc_dgd "
                f"wire; algorithm={self.algorithm!r} does not support them")


def _flat_ring_perm(ctx: ParallelContext, shift: int, group: int = 1):
    """Ring permutation over flattened (pod, data) in ring-element steps.

    ``group`` is the node count of one ring element (1 = the flat node
    ring; the hierarchical pod size otherwise): the permutation steps in
    units of ``group * fsdp`` devices, so every pod member exchanges with
    the SAME-offset member of the neighbor pod and the pod-replica
    invariant survives the transfer."""
    total = ctx.pods * ctx.data_size
    step = shift * ctx.fsdp * group
    return [(i, (i + step) % total) for i in range(total)]


def _flat_ring_perm_masked(ctx: ParallelContext, shift: int, mask,
                           group: int = 1):
    """Ring permutation compacted over the ACTIVE elements of ``mask``
    (nodes on the flat ring, pods under hierarchy).

    Survivors form a stride-``|shift|`` ring in active-position order;
    inactive elements' devices appear as neither source nor destination —
    ``ppermute`` delivers ZEROS to absent destinations, which is exactly
    the dropped-packet decode path (zero payload -> zero differential),
    so routing around a node and losing its packets share one mechanism.
    A stride that has no meaning on the smaller ring (s % m == 0, or
    gcd(s, m) > 1 which would disconnect the survivors) falls back to
    stride 1.  ``mask=None`` / all-active delegates to the unmasked
    permutation — identical pairs, bit-identical trace.
    """
    if mask is None or all(mask):
        return _flat_ring_perm(ctx, shift, group)
    active = [v for v, a in enumerate(mask) if a]
    m = len(active)
    sign = 1 if shift >= 0 else -1
    s_eff = abs(shift) % m
    if s_eff == 0 or math.gcd(s_eff, m) != 1:
        s_eff = 1
    pos = {node: p for p, node in enumerate(active)}
    total = ctx.pods * ctx.data_size
    unit = ctx.fsdp * group
    pairs = []
    for i in range(total):
        node = i // unit
        p = pos.get(node)
        if p is None:
            continue
        tgt = active[(p + sign * s_eff) % m]
        pairs.append((i, tgt * unit + i % unit))
    return pairs


def _ring_axes(ctx: ParallelContext):
    return (("pod", "data") if ctx.pod_axis is not None else ("data",))


def _ppermute_ring(x, ctx: ParallelContext, shift: int, mask=None,
                   group: int = 1):
    if ctx.total_consensus_nodes // group <= 1:
        return x
    axes = _ring_axes(ctx)
    with jax.named_scope("permute"):
        return jax.lax.ppermute(x, axes if len(axes) > 1 else axes[0],
                                _flat_ring_perm_masked(ctx, shift, mask,
                                                       group))


@contextlib.contextmanager
def _unit_scope(stage: str, c: int, n_units: int):
    """The named scope of one transfer unit's ``stage`` (``encode`` or
    ``combine``, under the exchange's ``exchange`` scope); each chunk of
    the pipelined transport gets its own (``exchange/encode/chunk1``)."""
    with jax.named_scope(stage):
        if n_units == 1:
            yield
        else:
            with jax.named_scope(f"chunk{c}"):
                yield


def _pipeline_schedule(n_units: int, launch, retire, inspect=None) -> list:
    """Double-buffered transfer schedule shared by the wire exchanges.

    Emission order at iteration c is ``launch(c+1)`` BEFORE ``retire(c)``,
    so unit c's payload transfer has no data dependence on — and can
    overlap with — unit c+1's quantize launch; unit c-1 was retired in
    the previous iteration while unit c was in flight.  ``inspect(c,
    inflight)`` (optional) observes each in-flight value before it is
    retired (overflow accounting).  Returns ``[retire(c, ...) for c]``.
    """
    outs = []
    inflight = launch(0)
    for c in range(n_units):
        if inspect is not None:
            inspect(c, inflight)
        nxt = launch(c + 1) if c + 1 < n_units else None
        outs.append(retire(c, inflight))
        inflight = nxt
    return outs


class ConsensusRuntime:
    """Stateless helper bound to (config, ctx); state lives in the train state."""

    def __init__(self, config: ConsensusConfig, ctx: ParallelContext):
        self.cfg = config
        self.ctx = ctx
        #: layout-independent wire-plan recipe (§Wire plans); bare codec
        #: names normalize to uniform plans (back-compat shim)
        self.plan_spec = wireplan.parse_spec(config.wire_codec)
        #: the single codec of a uniform plan (None for mixed plans — use
        #: ``wire_plan_for(layout)`` for anything geometric)
        self.codec = (wire_codec.by_name(self.plan_spec.uniform_codec)
                      if self.plan_spec.is_uniform else None)
        self._plan_cache: dict = {}
        n = ctx.total_consensus_nodes
        #: hierarchical grouping (DESIGN.md §14): ring elements are PODS
        #: of ``pod_size`` consecutive nodes; the flat ring is pod_size=1.
        #: Every per-element concept below — loss receiver ids, membership
        #: masks, stride connectivity — indexes the ``ring_len`` ring.
        hier = config.hierarchy
        self.pod_size = 1 if hier is None else hier.pod_size(n)
        self.ring_len = n // self.pod_size
        if hier is not None and ctx.pod_axis is not None and ctx.pods > 1:
            raise ValueError(
                "hierarchy partitions the flattened node ring; combining "
                "it with a physical multi-pod mesh axis is unsupported — "
                "build the mesh over the data axis only")
        #: the loss model bound to this mesh's ring-element count
        #: (GilbertElliott realizes per-edge Markov chains) and the
        #: straggler-deadline model of the async transport; None keeps
        #: either out of the trace
        self.loss = config.loss_model_for(self.ring_len)
        self.straggler = config.straggler_model
        if config.membership is not None:
            for e, m in enumerate(config.membership):
                if len(m) != self.ring_len:
                    raise ValueError(
                        f"membership mask {e} covers {len(m)} ring elements "
                        f"but the mesh has {self.ring_len} "
                        f"({'pods' if self.pod_size > 1 else 'nodes'})")
        if (self.ring_len > 1
                and config.algorithm in ("adc_dgd", "dgd", "compressed_dgd")):
            rl = self.ring_len
            for s in config.ring_strides:
                if s % rl == 0:
                    raise ValueError(
                        f"ring stride {s} is a self-loop on {rl} ring "
                        "elements — the exchange would silently carry no "
                        "communication; drop it from ring_strides")
            # joint connectivity: the union graph over one schedule cycle is
            # the circulant with connection set {±s}; it is connected iff
            # gcd(s_1, ..., s_k, ring_len) == 1.
            g = rl
            for s in config.ring_strides:
                g = math.gcd(g, s)
            if g != 1:
                raise ValueError(
                    f"ring_strides {config.ring_strides} on {rl} ring "
                    f"elements share the common factor {g}: the union of "
                    "all schedule epochs splits the network into disjoint "
                    "components and consensus can never be reached")

    # -- state ---------------------------------------------------------
    def init_state(self, params: Any) -> Any:
        """Consensus shadows for the *local* parameter shard tree.

        For ``adc_dgd`` the shadows are returned **packed**: one
        ``(n_rows, BLOCK)`` fp32 buffer per shadow spanning all leaves
        (:class:`repro.core.wire.WireLayout`), so no per-step blockify of
        the state ever appears in the exchange trace.  Must be called on
        per-device leaves (inside shard_map, or on the logical tree in
        single-process use) — the packing is a device-local layout.
        """
        if self.cfg.algorithm in ("allreduce", "none", "compressed_dgd", "dgd"):
            return {}
        # All nodes start from the same x0 (shared init seed), so every
        # neighbor estimate x_tilde_j,0 = x0 and the incremental aggregate
        # m_0 = sum_{j != i} W_ij x_tilde_j,0 = (1 - W_ii) * x0.
        side_total = 1.0 - self.cfg.self_weight
        layout = self.state_layout(params)
        x_tilde = layout.pack(params)
        st = {"x_tilde": x_tilde, "m_agg": side_total * x_tilde}
        if self.cfg.push_sum_enabled:
            # push-sum weight w_0 = 1 and the last-seen neighbor weights
            # [upstream, downstream] (the stale fallback under link loss).
            # x_tilde / m_agg then live in the NUMERATOR domain w * x —
            # at w == 1 every numerator op is a bitwise identity.
            st["ps_w"] = jnp.ones((1,), jnp.float32)
            st["ps_nbr"] = jnp.ones((2,), jnp.float32)
        if self.cfg.wire_packing == "async":
            # the async double buffer: step k retires these (launched at
            # step k-1) before launching its own payload.  Zero bytes
            # decode to zero differentials on every codec, so the step-1
            # retire is an exact no-op gossip; the push-sum trailer
            # pre-encodes w_0 = 1 (a zero trailer would decode to w = 0
            # and break mass conservation).
            trailer = None
            if self.cfg.push_sum_enabled:
                trailer = jax.lax.bitcast_convert_type(
                    st["ps_w"], jnp.uint8).reshape(-1)
            fly = wire.inflight_init(
                self.wire_plan_for(layout).payload_bytes, trailer)
            for k in wire.INFLIGHT_KEYS:
                st[k] = fly
        return st

    def state_layout(self, params: Any) -> wire.WireLayout:
        """The static packing plan for a (local) parameter tree.

        Mixed plans get a **grouped placement**: same-codec leaves are
        packed adjacently (stable, first-occurrence codec order —
        wireplan.grouped_placement), collapsing the plan to one codec run
        per codec so the tile-aligned run interiors stay on the Pallas
        kernel path instead of shattering into ragged row-granular
        fragments.  Uniform plans keep leaf order (placement is moot: one
        run either way, bit-identical to the historical buffer)."""
        layout = wire.WireLayout.for_tree(params)
        if not self.plan_spec.is_uniform:
            codecs = tuple(self.plan_spec.codec_for_path(s.path)
                           for s in layout.slots)
            placement = wireplan.grouped_placement(layout, codecs)
            if placement is not None:
                layout = layout.with_placement(placement)
        return layout

    def wire_plan_for(self, layout: wire.WireLayout) -> wireplan.WirePlan:
        """The (cached) WirePlan binding this runtime's plan spec to a
        layout's slots — the single source of payload geometry for the
        packed/pipelined exchanges and the wire accounting."""
        plan = self._plan_cache.get(layout)
        if plan is None:
            plan = self.plan_spec.build(layout)
            self._plan_cache[layout] = plan
        return plan

    def noise_cols_for(self, layout: wire.WireLayout) -> int:
        """Columns of the quantization-noise buffer one exchange consumes
        (the max over the plan's codecs; see core.wireplan)."""
        return self.wire_plan_for(layout).noise_cols(layout.block)

    # -- wire accounting (static; used by rooflines & benchmarks) --------
    def wire_accounting(self, n_params_local: int,
                        layout: wire.WireLayout | None = None
                        ) -> telemetry.WireAccounting | None:
        """The unified byte accounting of this runtime's wire
        (core.telemetry.WireAccounting): the ONE source the static
        ``wire_bytes_per_step`` metric, the traced delivered/shipped
        metrics and the benchmark MB/step math all read, so
        shipped == delivered + dropped holds everywhere by construction.

        ``layout`` (when available) gives the exact heterogeneous payload
        size via the WirePlan prefix sum; otherwise rows are estimated
        from the contiguous element count (exact when the tree packs as
        one leaf; mixed plans without a layout fall back to the hot
        codec's width — an upper bound).  The per-leaf wire path ships
        each leaf padded to the historical TILE_N-aligned blockify
        height, so it puts MORE rows on the wire than the row-granular
        packed payload for the same tree.  Returns None for algorithms
        with no compressed wire.
        """
        cfg = self.cfg
        if cfg.algorithm in ("adc_dgd", "compressed_dgd"):
            push = cfg.algorithm == "adc_dgd" and cfg.push_sum_enabled
            hier = cfg.hierarchy if cfg.algorithm == "adc_dgd" else None
            inner = (0.0 if hier is None else hier.inner_bytes_per_step(
                n_params_local, self.ctx.total_consensus_nodes))
            if hier is not None and self.ring_len <= 1:
                # one pod spans every node: nothing rides the compressed
                # wire; the inner all-reduce is the whole exchange
                return telemetry.WireAccounting(
                    payload_bytes=0, inner_bytes=inner)
            if layout is not None and cfg.wire_packing == "per_leaf":
                rows = sum(kops.padded_block_rows(s.size)
                           for s in layout.slots)
                payload = rows * kops.payload_width()
            elif layout is not None:
                payload = self.wire_plan_for(layout).payload_bytes
                rows = layout.n_rows
            else:
                rows = kops.padded_block_rows(n_params_local)
                width = (self.codec.payload_width() if self.codec is not None
                         else wire_codec.by_name(self.plan_spec.hot_codec)
                         .payload_width())
                payload = rows * width
            resync = 0.0
            if cfg.algorithm == "adc_dgd" and self._schedule_varying():
                # amortized epoch-boundary resync: one fp32 x_tilde exchange
                # per re-wiring (both ring directions; membership schedules
                # stop paying it once clamped, so this is an upper bound)
                resync = 2.0 * rows * kops.BLOCK * 4 / cfg.schedule_period
            # the fp32 push-sum weight: a payload trailer on the packed
            # wire, its own tiny ppermute on the per-leaf reference —
            # 4 bytes per ring direction either way
            return telemetry.WireAccounting(
                payload_bytes=int(payload),
                trailer_bytes=(wireplan.PUSH_SUM_TRAILER_BYTES
                               if push else 0),
                resync_bytes_amortized=resync,
                inner_bytes=inner)
        if cfg.algorithm == "dgd":
            return telemetry.WireAccounting.uncompressed(
                n_params_local, jnp.dtype(cfg.wire_dtype).itemsize)
        return None

    def wire_bytes_per_step(self, n_params_local: int,
                            layout: wire.WireLayout | None = None) -> float:
        """Bytes this device puts on the ring per step (see
        :meth:`wire_accounting` for the underlying arithmetic)."""
        acct = self.wire_accounting(n_params_local, layout=layout)
        return 0.0 if acct is None else acct.shipped_per_step

    def _chunks_for(self, layout: wire.WireLayout) -> wire.ChunkedLayout:
        """Uniform-int8 chunk split for the compressed_dgd packed path (the
        ADC exchange chunks through its WirePlan instead): the
        tile-count-clamped configured count for ``wire_packing=
        "pipelined"``, one chunk for the monolithic paths."""
        return wire.ChunkedLayout.split(
            layout, self.cfg.pipeline_chunks
            if self.cfg.wire_packing == "pipelined" else 1)

    def pipeline_chunks_for(self, layout: wire.WireLayout) -> int:
        """Effective pipeline chunk count for a layout: 1 for the
        monolithic paths; for ``wire_packing="pipelined"`` the plan's
        snapped chunk count (tile-clamped, >= the plan's codec-run count —
        chunks never straddle a codec change)."""
        if self.cfg.wire_packing != "pipelined":
            return 1
        return self.wire_plan_for(layout).n_chunks(self.cfg.pipeline_chunks)

    def collectives_per_step(self, n_leaves: int = 1,
                             n_chunks: int | None = None,
                             layout: wire.WireLayout | None = None) -> float:
        """Ring collectives this device issues per training step (static).

        The packed wire path is leaf-count independent: exactly one
        payload ``ppermute`` per ring direction (+ the amortized fp32
        resync exchange for time-varying rings).  The pipelined path pays
        one payload ``ppermute`` per ring direction PER CHUNK (2 x
        pipeline_chunks — the price of overlapping transfer with compute;
        wire bytes are unchanged).  The per-leaf reference pays 4
        collectives per leaf (codes/scales x two directions).

        The traced chunk count is clamped to the buffer's tile count, so
        for exact pipelined accounting pass ``layout`` (or an explicit
        ``n_chunks``); with neither, the unclamped configured count is the
        best static estimate available.
        """
        cfg = self.cfg
        n = self.ctx.total_consensus_nodes
        if cfg.algorithm == "none" or (n <= 1 and cfg.algorithm != "allreduce"):
            return 0.0
        resync_amort = (1.0 / cfg.schedule_period
                        if self._schedule_varying() else 0.0)
        if cfg.wire_packing == "pipelined":
            if n_chunks is None and layout is not None:
                n_chunks = self.pipeline_chunks_for(layout)
            chunks = float(cfg.pipeline_chunks if n_chunks is None
                           else n_chunks)
        else:
            chunks = 1.0
        if cfg.algorithm == "adc_dgd":
            if cfg.hierarchy is not None and self.ring_len <= 1:
                # one pod spans every node: the rotation all-reduce IS
                # the whole exchange (cf. the allreduce branch below)
                return float(n - 1) * n_leaves
            # the intra-pod delta psum of the hierarchical inner level
            inner = 1.0 if self.pod_size > 1 else 0.0
            # push-sum weight: free on the packed wire (payload trailer)
            # except 2 scalar ppermutes inside the amortized resync cond;
            # 2 scalar ppermutes every step on the per-leaf reference
            ps = 2.0 if cfg.push_sum_enabled else 0.0
            if cfg.wire_packing in ("packed", "pipelined", "async"):
                return (inner + 2.0 * chunks
                        + (2.0 * chunks + ps) * resync_amort)
            return 4.0 * n_leaves + ps + 2.0 * n_leaves * resync_amort
        if cfg.algorithm == "compressed_dgd":
            return (2.0 * chunks if cfg.wire_packing in ("packed", "pipelined")
                    else 4.0 * n_leaves)
        if cfg.algorithm == "dgd":
            return 2.0 * n_leaves
        assert cfg.algorithm == "allreduce", cfg.algorithm
        return float(n - 1) * n_leaves        # ppermute-rotation all-reduce

    # -- the exchange ----------------------------------------------------
    def exchange(self, x_prev: Any, x_half: Any, state: Any, step, key,
                 noise: Any = None):
        """x_prev: params at step k; x_half: after the local optimizer step.

        Its ops run under the named scope ``exchange``, with ``noise``,
        ``encode``, ``permute`` and ``combine`` inside it (DESIGN.md §13).

        ``noise``: optional pre-generated uniform noise buffer of shape
        ``(layout.n_rows, BLOCK)`` consumed row-for-row by the quantizer.
        When ``None`` (production) each wire path generates its own stream:
        packed draws ONE buffer from the device-folded key; per_leaf draws
        per-leaf buffers from split keys (the historical path's cost and
        stream).  Tests inject one shared buffer into both paths to assert
        bit-for-bit equivalence of the wire transformation itself.

        Returns (x_next, new_state, metrics).
        """
        with jax.named_scope("exchange"):
            return self._exchange(x_prev, x_half, state, step, key, noise)

    def _exchange(self, x_prev, x_half, state, step, key, noise):
        alg = self.cfg.algorithm
        ctx = self.ctx
        layout = self.state_layout(x_half)

        def base_metrics(x_out):
            # every key train.py's out_specs declares for this config must
            # be present on every return path (shard_map pytree contract)
            m = self._wire_metrics(layout)
            if alg == "adc_dgd":
                m["overflow_frac"] = jnp.zeros((), jnp.float32)
                m["residual_norm"] = jnp.zeros((), jnp.float32)
                if self.cfg.push_sum_enabled:
                    m["push_sum_weight"] = jnp.ones((), jnp.float32)
                if self.cfg.faults_enabled:
                    m["wire_bytes_delivered"] = jnp.zeros((), jnp.float32)
                    m["delivered_frac"] = jnp.ones((), jnp.float32)
                if self.cfg.straggle_rate is not None:
                    m["deadline_miss_frac"] = jnp.zeros((), jnp.float32)
                if self.cfg.membership is not None:
                    m["active_nodes"] = jnp.asarray(
                        float(ctx.total_consensus_nodes), jnp.float32)
                # telemetry extras: nothing was exchanged on this path
                for tk in self.cfg.telemetry_metric_keys():
                    m[tk] = jnp.zeros((), jnp.float32)
            if self.cfg.track_consensus_error:
                m["consensus_err"] = _consensus_error(x_out, ctx)
            return m

        if alg == "none" or ctx.total_consensus_nodes <= 1 and alg != "allreduce":
            return x_half, state, base_metrics(x_half)
        if alg == "allreduce":
            # W = (1/N)11^T via psum over node subgroups (same fsdp rank
            # across nodes & pods) — classic synchronous data parallelism.
            x_next = _allreduce_mean_delta(x_prev, x_half, ctx)
            return x_next, state, base_metrics(x_next)
        if alg == "adc_dgd" and self.cfg.hierarchy is not None:
            if self.ring_len <= 1:
                # one pod spans every node: the inner level IS the whole
                # exchange — delegate to the same rotation all-reduce as
                # algorithm="allreduce", making the pods==1 degeneracy
                # bit-identical to it by construction (nothing rides the
                # compressed wire, so the shadows pass through untouched)
                x_next = _allreduce_mean_delta(x_prev, x_half, ctx)
                return x_next, state, base_metrics(x_next)
            if self.pod_size > 1:
                # inner level first: pod members average their optimizer
                # delta and enter the outer compressed exchange as bitwise
                # replicas of their pod representative (same parameters,
                # same noise key, same fault draws) — the broadcast-back
                # of the outer combine is therefore implicit and free
                x_half = self._pod_mean_delta(x_prev, x_half)
        packed = self.cfg.wire_packing in ("packed", "pipelined")
        if alg == "dgd":
            impl = lambda s: self._dgd_exchange(  # noqa: E731
                x_prev, x_half, state, step=step, key=key, stride=s,
                layout=layout)
        elif alg == "compressed_dgd":
            fn = (self._cdgd_exchange_packed if packed
                  else self._cdgd_exchange_per_leaf)
            impl = lambda s: fn(  # noqa: E731
                x_prev, x_half, state, step=step, key=key, stride=s,
                noise=noise, layout=layout)
        else:
            assert alg == "adc_dgd", alg
            if self.cfg.wire_packing == "async":
                fn = self._adc_exchange_async
            elif packed:
                fn = self._adc_exchange
            else:
                fn = self._adc_exchange_per_leaf
            impl = lambda s, mask=None: fn(  # noqa: E731
                x_prev, x_half, state, step, key, stride=s, noise=noise,
                layout=layout, mask=mask)
        return self._dispatch_stride(impl, step)

    # ------------------------------------------------------------------
    def _dispatch_stride(self, impl, step):
        """Run ``impl(stride)`` — or ``impl(stride, mask=...)`` under
        elastic membership — for this step's schedule epoch.  ppermute
        permutations are static per trace, so both the time-varying ring
        AND the membership schedule are a ``lax.switch`` over one
        wiring-specialized branch per DISTINCT (stride, mask) pair (a
        static table deduplicates repeats: e.g. identical masks across
        epochs, or an all-active mask recurring after a churn window; all
        branches return the same state/metric pytree).  The stride index
        cycles with the epoch; the mask index CLAMPS to the last mask —
        membership stabilizes."""
        strides = self.cfg.ring_strides
        masks = self.cfg.membership
        if masks is None:
            if len(strides) == 1:
                return impl(strides[0])
            epoch = ((jnp.asarray(step, jnp.int32) - 1)
                     // self.cfg.schedule_period)
            branches = [partial(impl, s) for s in strides]
            return jax.lax.switch(epoch % len(strides), branches)
        pairs, index = [], {}
        table = np.empty((len(strides), len(masks)), np.int32)
        for si, s in enumerate(strides):
            for mi, m in enumerate(masks):
                if (s, m) not in index:
                    index[(s, m)] = len(pairs)
                    pairs.append((s, m))
                table[si, mi] = index[(s, m)]
        if len(pairs) == 1:
            s, m = pairs[0]
            return impl(s, mask=m)
        epoch = (jnp.asarray(step, jnp.int32) - 1) // self.cfg.schedule_period
        si = epoch % len(strides)
        mi = jnp.minimum(epoch, len(masks) - 1)
        branches = [partial(impl, s, mask=m) for s, m in pairs]
        return jax.lax.switch(jnp.asarray(table)[si, mi], branches)

    # ------------------------------------------------------------------
    def _schedule_varying(self) -> bool:
        """Does the wiring (stride or membership) ever change at an epoch
        boundary?  This is what makes the resync machinery necessary."""
        return self.cfg.schedule_varying

    def _resync_flag(self, step):
        """Epoch-boundary m_agg resync predicate for time-varying rings
        and membership changes: the incremental aggregate
        m_agg = sum_j W_ij x_tilde_j is only valid for a fixed neighbor
        set, so on the first step of every schedule epoch the NEW
        neighbors exchange their fp32 x_tilde once and m_agg is rebuilt
        exactly (amortized in wire_bytes_per_step).  Once a pure
        membership schedule has clamped to its last mask the wiring never
        changes again, so the resync stops firing."""
        if not self._schedule_varying():
            return None
        step_i32 = jnp.asarray(step, jnp.int32)
        flag = jnp.logical_and(
            (step_i32 - 1) % self.cfg.schedule_period == 0, step_i32 > 1)
        if (self.cfg.membership is not None
                and len(self.cfg.ring_strides) == 1):
            epoch = (step_i32 - 1) // self.cfg.schedule_period
            flag = jnp.logical_and(
                flag, epoch <= len(self.cfg.membership) - 1)
        return flag

    def _resync_ok(self, resync, step):
        """Success flag of the bounded-retry resync handshake (ok in BOTH
        ring directions), or None when resyncs cannot fail (no loss model,
        or no resync at all).  A node whose handshake fails keeps its
        stale m_agg — the next boundary repairs it."""
        if resync is None or self.loss is None:
            return None
        ok_up, ok_dn = self.loss.resync_keep(
            jnp.asarray(step, jnp.int32), self._node_index(),
            self.cfg.resync_retries)
        return jnp.logical_and(ok_up, ok_dn)

    def _ring(self, x, shift, mask=None):
        """This runtime's ring transfer: the flat node ring, or — under
        hierarchy — the POD ring (permutation steps in units of
        ``pod_size`` nodes, so every pod member exchanges with its
        same-offset counterpart in the neighbor pod).  Still exactly one
        ppermute per call; bit-identical to the flat helper at
        pod_size == 1."""
        return _ppermute_ring(x, self.ctx, shift, mask=mask,
                              group=self.pod_size)

    def _pod_mean_delta(self, x_prev, x_half):
        """Inner hierarchy level (DESIGN.md §14): psum-average the
        optimizer delta ``x_half - x_prev`` across each pod's members so
        every member enters the outer compressed exchange holding the
        pod-mean parameters (the ``(1/m) 11^T`` Kronecker factor of the
        effective mixing).  Groups hold SAME-fsdp-rank devices across one
        pod — different fsdp ranks hold different parameter shards.  One
        psum per step; uncompressed fp32 (the fast intra-pod
        interconnect)."""
        ctx = self.ctx
        m = self.pod_size
        groups = self.cfg.hierarchy.pod_psum_groups(
            ctx.total_consensus_nodes, ctx.fsdp)
        axes = _ring_axes(ctx)
        axis = axes if len(axes) > 1 else axes[0]

        def avg(xp, xh):
            delta = (xh - xp).astype(jnp.float32)
            s = jax.lax.psum(delta, axis, axis_index_groups=groups)
            return (xp.astype(jnp.float32) + s / m).astype(xh.dtype)

        return jax.tree.map(avg, x_prev, x_half)

    def _node_index(self):
        """Traced ring-element index of this device (shared by all its
        FSDP shards — and, under hierarchy, by every member of its pod —
        so one drop decision covers the whole sharded/replicated
        payload) — the LossModel's receiver id and the membership mask
        index.  Matches the flattened (pod, data) // (fsdp * pod_size)
        element numbering of ``_flat_ring_perm``."""
        ctx = self.ctx
        idx = jnp.zeros((), jnp.int32)
        if ctx.data_size > 1:
            idx = jax.lax.axis_index(ctx.data_axis)
        if ctx.pod_axis is not None and ctx.pods > 1:
            idx = idx + ctx.data_size * jax.lax.axis_index(ctx.pod_axis)
        return idx // (ctx.fsdp * self.pod_size)

    def _keep_flags(self, step):
        """(keep_upstream, keep_downstream) boolean scalars of this step's
        loss draw, or (None, None) when no loss model is configured (the
        machinery then never enters the trace)."""
        lm = self.loss
        if lm is None:
            return None, None
        node = self._node_index()
        s = jnp.asarray(step, jnp.int32)
        return (lm.keep(s, faults.FROM_UPSTREAM, node),
                lm.keep(s, faults.FROM_DOWNSTREAM, node))

    def _deadline_flags(self, launch_step):
        """(meet_upstream, meet_downstream) straggler-deadline draws of the
        async transport, keyed — like the loss draw — by the LAUNCH step
        of the in-flight payload; (None, None) without a straggler
        model."""
        sm = self.straggler
        if sm is None:
            return None, None
        node = self._node_index()
        s = jnp.asarray(launch_step, jnp.int32)
        return (sm.keep(s, faults.FROM_UPSTREAM, node),
                sm.keep(s, faults.FROM_DOWNSTREAM, node))

    @staticmethod
    def _and_flags(a, b):
        """Combine two optional keep-flag scalars (None = always keep)."""
        if a is None:
            return b
        if b is None:
            return a
        return jnp.logical_and(a, b)

    def _step_k(self, step):
        """fixed mode: effective grid step Delta_k = Delta_0 / k^gamma — this
        IS the amplified-differential trick with amplification folded into
        the quantizer (transmit C(k^g y)/k^g == round-to-grid(Delta_0/k^g))."""
        if self.cfg.quant_mode != "fixed":
            return None
        k = jnp.maximum(1.0, step.astype(jnp.float32))
        return jnp.asarray(self.cfg.fixed_step0, jnp.float32) / k**self.cfg.gamma

    def _wire_metrics(self, layout: wire.WireLayout) -> dict:
        """Static per-step wire accounting, surfaced so benchmarks and
        rooflines report the packed-path reduction without hand-derived
        constants."""
        return {
            "collectives_per_step": jnp.asarray(
                self.collectives_per_step(
                    layout.n_leaves,
                    n_chunks=self.pipeline_chunks_for(layout)), jnp.float32),
            "wire_bytes_per_step": jnp.asarray(
                self.wire_bytes_per_step(layout.n_elements, layout=layout),
                jnp.float32),
        }

    # ------------------------------------------------------------------
    def _adc_exchange(self, x_prev, x_half, state, step, key, stride=1,
                      noise=None, layout=None, mask=None):
        """Packed / pipelined ADC-DGD exchange: the whole parameter tree as
        ONE wire problem whose payload geometry comes from the runtime's
        :class:`~repro.core.wireplan.WirePlan`.

        ``wire_packing="packed"`` moves ONE flat byte payload per ring
        direction per step: every codec run of the plan is encoded with one
        grouped kernel launch over its contiguous row range and the
        flattened run payloads concatenate at the plan's prefix-sum byte
        offsets — two collectives per step no matter how many codecs the
        plan mixes (for a uniform plan this is exactly the monolithic PR 2
        path).  ``wire_packing="pipelined"`` splits the buffer into
        ``pipeline_chunks`` row slices — snapped so no chunk straddles a
        codec change — and double-buffers the stages: chunk i+1's payload
        is quantized and put on the wire BEFORE chunk i's in-flight payload
        is consumed, so in steady state the interconnect moves chunk i
        while the VPU quantizes chunk i+1 and dequant-combines chunk i-1
        (DESIGN.md §Hardware adaptation).  Every codec is row-local, so
        every chunking is bit-identical to the monolithic path given the
        same noise buffer — and, for uniform int8 plans, to
        ``_adc_exchange_per_leaf`` too.
        """
        cfg, ctx = self.cfg, self.ctx
        if layout is None:
            layout = self.state_layout(x_half)
        plan = self.wire_plan_for(layout)
        units = plan.transfer_units(
            cfg.pipeline_chunks if cfg.wire_packing == "pipelined" else None)
        resync = self._resync_flag(step)
        step_k = self._step_k(step)
        key = _device_key(key, ctx, group=self.pod_size)
        push = cfg.push_sum_enabled
        w_fwd, w_bwd = cfg.in_weights
        directed = w_fwd != w_bwd
        keep_up, keep_dn = self._keep_flags(step)
        resync_ok = self._resync_ok(resync, step)
        last_unit = len(units) - 1
        # activity scalar of THIS device's node (None when every node is
        # active — the all-active mask must stay bitwise inert): inactive
        # nodes freeze their parameters and shadows and zero their metrics
        act_b = None
        if mask is not None and not all(mask):
            act_b = jnp.asarray(np.asarray(mask, np.bool_))[
                self._node_index()]

        xt = state["x_tilde"]                       # (n_rows, BLOCK) packed
        mb = state["m_agg"]
        # the shadows estimate the iterate x^k, not x^{k+1/2}: the local
        # step is added once, after the combine (paper Algorithm 2)
        xp_p = layout.pack(x_prev)
        if push:
            # numerator domain: the wire carries w_i * x_i and the weight
            # scalar; both are mixed by the same column-stochastic W and
            # the de-biased iterate is their ratio (subgradient-push).
            # At w == 1 the multiply is a bitwise identity, so the
            # symmetric exactness contracts survive unchanged.
            ps_w = state["ps_w"]                    # (1,) fp32
            xp_p = xp_p * ps_w[0]
            trailer = jax.lax.bitcast_convert_type(
                ps_w.astype(jnp.float32), jnp.uint8).reshape(-1)
        y = xp_p - xt                               # packed differential
        if noise is None:
            # ONE noise buffer sized for the plan's widest codec (top-k
            # consumes a second BLOCK-wide region for its selection race);
            # each run's kernels read their leading columns in place
            with jax.named_scope("noise"):
                noise = jax.random.uniform(
                    key, (layout.n_rows, plan.noise_cols(layout.block)),
                    jnp.float32)

        def launch(c):
            """Encode unit c straight out of the full differential (one
            grouped launch per codec run; the kernels read the row ranges
            in place), flatten to the unit's 1-D wire buffer and put it on
            both ring directions: 2 collectives per unit regardless of how
            many codec runs the unit carries."""
            with _unit_scope("encode", c, len(units)):
                pay = plan.encode_unit(units[c], y, noise, fixed_step=step_k,
                                       use_pallas=cfg.use_pallas)
            if push and c == last_unit:
                # the push-sum weight rides the LAST unit's payload as a
                # 4-byte fp32 trailer — no extra collective; fragment byte
                # offsets address the payload from 0 and never see it
                pay = wire.lift_concat([pay, trailer])
            return (pay, self._ring(pay, +stride, mask=mask),
                    self._ring(pay, -stride, mask=mask))

        recv_w = {}
        dense = {"l": [], "r": []} if directed else None

        def retire(c, inflight):
            """Per-fragment fused dequant + shadow update + combine for
            unit c's in-flight payloads (persistent shadows viewed at each
            fragment's row offset; unit-level epoch-boundary m_agg
            resync)."""
            pay, p_l, p_r = inflight
            unit = units[c]
            if push and c == last_unit:
                recv_w["l"] = jax.lax.bitcast_convert_type(
                    p_l[-wireplan.PUSH_SUM_TRAILER_BYTES:],
                    jnp.float32).reshape(1)
                recv_w["r"] = jax.lax.bitcast_convert_type(
                    p_r[-wireplan.PUSH_SUM_TRAILER_BYTES:],
                    jnp.float32).reshape(1)
            if keep_up is not None:
                # a dropped packet zeroes the whole unit payload: every
                # codec decodes all-zero bytes to a zero differential, so
                # the receiver reuses its last x_tilde_j estimate
                p_l = jnp.where(keep_up, p_l, jnp.zeros_like(p_l))
                p_r = jnp.where(keep_dn, p_r, jnp.zeros_like(p_r))
            mb_u = None
            if resync is not None:
                xt_u = jax.lax.slice_in_dim(xt, unit.row_start, unit.row_end)

                def _rebuild(xt_u=xt_u, unit=unit):
                    xt_l = self._ring(xt_u, +stride, mask=mask)
                    xt_r = self._ring(xt_u, -stride, mask=mask)
                    if directed:
                        built = (jnp.float32(w_fwd) * xt_l
                                 + jnp.float32(w_bwd) * xt_r)
                    else:
                        built = jnp.float32(cfg.side_weight) * (xt_l + xt_r)
                    if resync_ok is not None:
                        # bounded-retry handshake failed in a direction:
                        # keep the stale aggregate, repaired next boundary
                        built = jnp.where(
                            resync_ok, built, jax.lax.slice_in_dim(
                                mb, unit.row_start, unit.row_end))
                    return built

                mb_u = jax.lax.cond(
                    resync, _rebuild,
                    lambda u=unit: jax.lax.slice_in_dim(
                        mb, u.row_start, u.row_end))
            outs = []
            with _unit_scope("combine", c, len(units)):
                for f in unit.fragments:
                    cd = wire_codec.by_name(f.codec)
                    if directed:
                        # the asymmetric correction term needs the two
                        # dense neighbor differentials (post loss-zeroing)
                        dense["l"].append(cd.decode_payload(
                            plan.fragment_payload(p_l, f, unit.byte_start),
                            layout.block))
                        dense["r"].append(cd.decode_payload(
                            plan.fragment_payload(p_r, f, unit.byte_start),
                            layout.block))
                    if mb_u is None:
                        m_in = mb                   # full-height in-kernel view
                    else:
                        m_in = jax.lax.slice_in_dim(
                            mb_u, f.row_start - unit.row_start,
                            f.row_end - unit.row_start)
                    outs.append(cd.decode_combine(
                        plan.fragment_payload(pay, f, unit.byte_start),
                        plan.fragment_payload(p_l, f, unit.byte_start),
                        plan.fragment_payload(p_r, f, unit.byte_start),
                        xt, m_in, cfg.self_weight, cfg.side_weight,
                        jnp.float32(1.0), use_pallas=cfg.use_pallas,
                        row_offset=f.row_start, n_rows=f.n_rows))
                return tuple(
                    wire.lift_concat([o[i] for o in outs]) for i in range(3))

        clipped = [jnp.zeros((), jnp.float32)]

        def count_overflow(c, inflight):
            # overflow monitoring (paper §IV-D: bounded transmitted
            # values); integer counts, so per-fragment sums are exact.
            # Sub-byte codecs count grid saturation from the differential
            # itself — on coarse alphabets boundary codes are usually
            # legitimate values, not clips (core.codec.count_saturated)
            unit = units[c]
            for f in unit.fragments:
                cd = wire_codec.by_name(f.codec)
                clipped[0] = clipped[0] + cd.count_saturated(
                    jax.lax.slice_in_dim(y, f.row_start, f.row_end), step_k,
                    plan.fragment_payload(inflight[0], f, unit.byte_start),
                    layout.block)

        parts = _pipeline_schedule(
            len(units), launch, retire,
            inspect=count_overflow if cfg.quant_mode == "fixed" else None)
        xt_new = wire.lift_concat([p[0] for p in parts])
        m_new = wire.lift_concat([p[1] for p in parts])
        comb = wire.lift_concat([p[2] for p in parts])
        overflow = clipped[0] / float(plan.codes_total(layout.block))
        if directed:
            # asymmetric in-weights WITHOUT touching the symmetric fused
            # kernels: they mixed both sides at side_weight s, so adding
            # the antisymmetric term t = (w_fwd - s)(d_l - d_r) to both
            # the aggregate and the combine realizes (w_fwd, w_bwd)
            # exactly (w_bwd = 2s - w_fwd); symmetric paths never pay it
            d_l = wire.lift_concat(dense["l"])
            d_r = wire.lift_concat(dense["r"])
            t = jnp.float32(w_fwd - cfg.side_weight) * (d_l - d_r)
            m_new = m_new + t
            comb = comb + t
        if push:
            w_l, w_r = recv_w["l"], recv_w["r"]
            if keep_up is not None:
                # stale-weight fallback mirrors the stale-x_tilde reuse
                w_l = jnp.where(keep_up, w_l, state["ps_nbr"][0:1])
                w_r = jnp.where(keep_dn, w_r, state["ps_nbr"][1:2])
            if resync is not None:
                # epoch boundary: new neighbors — refresh the weights over
                # the bounded-retry control plane alongside the m_agg
                # rebuild (a failed handshake keeps the stale weights)
                def _refresh(w_l=w_l, w_r=w_r):
                    fresh_l = self._ring(ps_w, +stride, mask=mask)
                    fresh_r = self._ring(ps_w, -stride, mask=mask)
                    if resync_ok is not None:
                        return (jnp.where(resync_ok, fresh_l, w_l),
                                jnp.where(resync_ok, fresh_r, w_r))
                    return fresh_l, fresh_r

                w_l, w_r = jax.lax.cond(
                    resync, _refresh, lambda w_l=w_l, w_r=w_r: (w_l, w_r))
            # w + fwd (w_l - w) + bwd (w_r - w) == self w + fwd w_l +
            # bwd w_r (column-stochastic), but is EXACT (x + 0 = x) when
            # all weights agree — on the homogeneous device ring w stays
            # bit-identically 1 forever, even under loss
            ps_new = ps_w + (jnp.float32(w_fwd) * (w_l - ps_w)
                             + jnp.float32(w_bwd) * (w_r - ps_w))
            # de-bias: the combine lives in the numerator domain w * x;
            # the parameters handed back are the ratio z = (W x) / (W w)
            comb = comb / ps_new[0]
        if act_b is not None:
            # inactive node: freeze the shadows in place (nothing was
            # truly sent or received — the masked ring never addressed it)
            xt_new = jnp.where(act_b, xt_new, xt)
            m_new = jnp.where(act_b, m_new, mb)
        # gradient step applied per leaf while unpacking (x_prev never
        # needs packing; identical elementwise ops to the per-leaf path)
        comb_leaves = layout.unpack(comb, cast=False)
        x_next = jax.tree.map(
            lambda c, h, p: (c + (h.astype(jnp.float32)
                                  - p.astype(jnp.float32))).astype(h.dtype),
            comb_leaves, x_half, x_prev)
        if act_b is not None:
            # inactive node: parameters freeze at their pre-departure
            # value (it neither gossips nor takes gradient steps)
            x_next = jax.tree.map(
                lambda nx, p: jnp.where(act_b, nx, p), x_next, x_prev)
        new_state = {"x_tilde": xt_new, "m_agg": m_new}
        if push:
            new_state["ps_w"] = ps_new
            new_state["ps_nbr"] = jnp.concatenate([w_l, w_r])
        # residual RMS of the packed differential: the controller's fidelity
        # feedback (core.codec.AdaptiveBitController) and a convergence
        # diagnostic in its own right (padding rows are exact zeros)
        residual = jnp.sqrt(jnp.sum(y * y)
                            / float(layout.n_rows * layout.block))
        if act_b is not None:
            overflow = jnp.where(act_b, overflow, 0.0)
            residual = jnp.where(act_b, residual, 0.0)
        metrics = {"overflow_frac": overflow, "residual_norm": residual,
                   **self._wire_metrics(layout)}
        acct = self.wire_accounting(layout.n_elements, layout=layout)
        if push:
            metrics["push_sum_weight"] = ps_new[0]
        if keep_up is not None:
            # bytes accounting excludes dropped payloads (one flat payload
            # + trailer per surviving ring direction)
            delivered = (keep_up.astype(jnp.float32)
                         + keep_dn.astype(jnp.float32))
            if act_b is not None:
                delivered = jnp.where(act_b, delivered, 0.0)
            metrics["wire_bytes_delivered"] = acct.delivered_bytes(delivered)
            metrics["delivered_frac"] = delivered / 2.0
        if cfg.membership is not None:
            metrics["active_nodes"] = jnp.asarray(
                float(sum(mask) if mask is not None
                      else self.ctx.total_consensus_nodes), jnp.float32)
        self._telemetry_metrics(metrics, acct, clipped[0], resync,
                                resync_ok, act_b)
        if cfg.track_consensus_error:
            metrics["consensus_err"] = _consensus_error(x_next, self.ctx)
        return x_next, new_state, metrics

    def _telemetry_metrics(self, metrics, acct, saturated, resync,
                           resync_ok, act_b, retired=None):
        """The ``ConsensusConfig(telemetry=True)`` metric extras, shared
        by every ADC wire path (zeroed when this node is inactive):

          wire_bytes_shipped   payload bytes this node put on the ring
          saturated_count      raw clipped-value census (fixed mode)
          resync_fired         1 when this step ran the epoch resync
          resync_ok            1 when it ran AND both handshakes landed
          staleness_retired    async in-flight buffers drained (0/1/2)
        """
        keys = self.cfg.telemetry_metric_keys()
        if not keys:
            return
        act = (jnp.ones((), jnp.float32) if act_b is None
               else act_b.astype(jnp.float32))
        metrics["wire_bytes_shipped"] = act * jnp.float32(
            acct.shipped_payload)
        metrics["saturated_count"] = act * saturated
        if "wire_bytes_inner" in keys:
            # per-level split (DESIGN.md §14): the intra-pod fp32 level
            # is lossless and always paid by an active member; the outer
            # value is per POD (every member reports its representative's
            # payload — sum over distinct pods, not devices)
            metrics["wire_bytes_inner"] = act * jnp.float32(
                acct.inner_bytes)
            metrics["wire_bytes_outer"] = act * jnp.float32(
                acct.shipped_payload)
        if "resync_fired" in keys:
            fired = (jnp.zeros((), jnp.float32) if resync is None
                     else resync.astype(jnp.float32))
            ok = fired if resync_ok is None else (
                fired * resync_ok.astype(jnp.float32))
            metrics["resync_fired"] = act * fired
            metrics["resync_ok"] = act * ok
        if "staleness_retired" in keys:
            metrics["staleness_retired"] = act * (
                jnp.float32(2.0) if retired is None else retired)

    # ------------------------------------------------------------------
    def _adc_exchange_async(self, x_prev, x_half, state, step, key,
                            stride=1, noise=None, layout=None, mask=None):
        """One-step-stale packed ADC exchange (``wire_packing="async"``,
        DESIGN.md §Async overlap; reference rule: core.consensus.CEDAS).

        The eager exchange launches and retires a payload within one step,
        so the ring transfer serializes with the training step.  Here the
        two halves are split across the step boundary via the in-flight
        double buffer ``wire.INFLIGHT_KEYS`` carried in the consensus
        state:

          RETIRE  decode + combine the payloads LAUNCHED AT STEP k-1
                  (grid Delta_{k-1}, loss draw of step k-1) into
                  x_tilde / m_agg, exactly as the eager retire would have;
          LAUNCH  encode this step's differential against the
                  POST-retire shadow (all nodes agree on the shadow
                  sequence), put it on both ring directions, and carry
                  the three payloads to step k+1.

        Between a step's launch and the next step's retire sits the whole
        of the model's fwd/bwd — XLA's async collectives give the transfer
        that full window to complete.  Still exactly 2 ppermutes per step.
        The step-1 retire consumes the all-zero init payload (a no-op
        gossip: every codec decodes zero bytes to a zero differential).
        On epoch-boundary re-wirings the in-flight payload was permuted by
        the PREVIOUS stride, so the resync rebuild runs AFTER the retire —
        draining the buffer into the exact ``m_agg = sum_j W_ij x_tilde_j``
        of the new ring.  ``staleness=0`` delegates to the eager packed
        exchange (bit-identity by construction), passing the idle buffer
        through.
        """
        cfg, ctx = self.cfg, self.ctx
        if cfg.staleness == 0:
            x_next, ns, metrics = self._adc_exchange(
                x_prev, x_half, state, step, key, stride=stride,
                noise=noise, layout=layout, mask=mask)
            for fk in wire.INFLIGHT_KEYS:
                ns[fk] = state[fk]
            return x_next, ns, metrics
        if layout is None:
            layout = self.state_layout(x_half)
        plan = self.wire_plan_for(layout)
        unit = plan.transfer_units(None)[0]      # monolithic packed payload
        resync = self._resync_flag(step)
        key = _device_key(key, ctx, group=self.pod_size)
        push = cfg.push_sum_enabled
        w_fwd, w_bwd = cfg.in_weights
        directed = w_fwd != w_bwd
        step_i32 = jnp.asarray(step, jnp.int32)
        # the in-flight transfer was launched at step k-1: its loss draw
        # AND its straggler-deadline draw are keyed by the LAUNCH step; a
        # payload that misses its one-step retire deadline is treated
        # exactly like a dropped packet (stale-x_tilde reuse)
        keep_up, keep_dn = self._keep_flags(step_i32 - 1)
        meet_up, meet_dn = self._deadline_flags(step_i32 - 1)
        eff_up = self._and_flags(keep_up, meet_up)
        eff_dn = self._and_flags(keep_dn, meet_dn)
        resync_ok = self._resync_ok(resync, step)
        act_b = None
        if mask is not None and not all(mask):
            act_b = jnp.asarray(np.asarray(mask, np.bool_))[
                self._node_index()]

        xt = state["x_tilde"]                    # (n_rows, BLOCK) packed
        mb = state["m_agg"]
        pay = state["fly_self"]
        p_l = state["fly_up"]
        p_r = state["fly_dn"]
        if push:
            ps_w = state["ps_w"]
            recv_w = {
                "l": jax.lax.bitcast_convert_type(
                    p_l[-wireplan.PUSH_SUM_TRAILER_BYTES:],
                    jnp.float32).reshape(1),
                "r": jax.lax.bitcast_convert_type(
                    p_r[-wireplan.PUSH_SUM_TRAILER_BYTES:],
                    jnp.float32).reshape(1),
            }
        if eff_up is not None:
            p_l = jnp.where(eff_up, p_l, jnp.zeros_like(p_l))
            p_r = jnp.where(eff_dn, p_r, jnp.zeros_like(p_r))

        # ---- RETIRE: drain the step-(k-1) payloads into the shadows -----
        dense = {"l": [], "r": []} if directed else None
        outs = []
        with jax.named_scope("combine"):
            for f in unit.fragments:
                cd = wire_codec.by_name(f.codec)
                if directed:
                    dense["l"].append(cd.decode_payload(
                        plan.fragment_payload(p_l, f, unit.byte_start),
                        layout.block))
                    dense["r"].append(cd.decode_payload(
                        plan.fragment_payload(p_r, f, unit.byte_start),
                        layout.block))
                outs.append(cd.decode_combine(
                    plan.fragment_payload(pay, f, unit.byte_start),
                    plan.fragment_payload(p_l, f, unit.byte_start),
                    plan.fragment_payload(p_r, f, unit.byte_start),
                    xt, mb, cfg.self_weight, cfg.side_weight,
                    jnp.float32(1.0), use_pallas=cfg.use_pallas,
                    row_offset=f.row_start, n_rows=f.n_rows))
            xt_new = wire.lift_concat([o[0] for o in outs])
            m_new = wire.lift_concat([o[1] for o in outs])
            comb = wire.lift_concat([o[2] for o in outs])
        if directed:
            d_l = wire.lift_concat(dense["l"])
            d_r = wire.lift_concat(dense["r"])
            t = jnp.float32(w_fwd - cfg.side_weight) * (d_l - d_r)
            m_new = m_new + t
            comb = comb + t
        if resync is not None:
            # epoch boundary: the retired payload came from the OLD ring's
            # neighbors, so drain it FIRST, then rebuild m_agg from the
            # NEW neighbors' post-retire x_tilde (all nodes' shadows are
            # consistent at this point — the buffer is fully drained)
            def _rebuild():
                xt_l = self._ring(xt_new, +stride, mask=mask)
                xt_r = self._ring(xt_new, -stride, mask=mask)
                if directed:
                    built = (jnp.float32(w_fwd) * xt_l
                             + jnp.float32(w_bwd) * xt_r)
                else:
                    built = jnp.float32(cfg.side_weight) * (xt_l + xt_r)
                if resync_ok is not None:
                    built = jnp.where(resync_ok, built, m_new)
                return built

            m_drained = jax.lax.cond(resync, _rebuild, lambda: m_new)
            comb = comb + (m_drained - m_new)
            m_new = m_drained
        if push:
            w_l, w_r = recv_w["l"], recv_w["r"]
            if eff_up is not None:
                w_l = jnp.where(eff_up, w_l, state["ps_nbr"][0:1])
                w_r = jnp.where(eff_dn, w_r, state["ps_nbr"][1:2])
            if resync is not None:
                def _refresh(w_l=w_l, w_r=w_r):
                    fresh_l = self._ring(ps_w, +stride, mask=mask)
                    fresh_r = self._ring(ps_w, -stride, mask=mask)
                    if resync_ok is not None:
                        return (jnp.where(resync_ok, fresh_l, w_l),
                                jnp.where(resync_ok, fresh_r, w_r))
                    return fresh_l, fresh_r

                w_l, w_r = jax.lax.cond(
                    resync, _refresh, lambda w_l=w_l, w_r=w_r: (w_l, w_r))
            ps_new = ps_w + (jnp.float32(w_fwd) * (w_l - ps_w)
                             + jnp.float32(w_bwd) * (w_r - ps_w))
            comb = comb / ps_new[0]
        if act_b is not None:
            # inactive node: shadows freeze (its fly_self was zeroed at
            # launch, so the retire above was already a no-op gossip; the
            # rejoin-boundary resync rebuilds m_agg exactly afterwards)
            xt_new = jnp.where(act_b, xt_new, xt)
            m_new = jnp.where(act_b, m_new, mb)
        comb_leaves = layout.unpack(comb, cast=False)
        x_next = jax.tree.map(
            lambda c, h, p: (c + (h.astype(jnp.float32)
                                  - p.astype(jnp.float32))).astype(h.dtype),
            comb_leaves, x_half, x_prev)
        if act_b is not None:
            x_next = jax.tree.map(
                lambda nx, p: jnp.where(act_b, nx, p), x_next, x_prev)

        # ---- LAUNCH: encode step k against the drained shadow -----------
        step_k = self._step_k(step)
        xh_p = layout.pack(x_half)
        if push:
            xh_p = xh_p * ps_new[0]
            trailer = jax.lax.bitcast_convert_type(
                ps_new.astype(jnp.float32), jnp.uint8).reshape(-1)
        y = xh_p - xt_new
        if noise is None:
            with jax.named_scope("noise"):
                noise = jax.random.uniform(
                    key, (layout.n_rows, plan.noise_cols(layout.block)),
                    jnp.float32)
        with jax.named_scope("encode"):
            new_pay = plan.encode_unit(unit, y, noise, fixed_step=step_k,
                                       use_pallas=cfg.use_pallas)
        if push:
            new_pay = wire.lift_concat([new_pay, trailer])
        if act_b is not None:
            # an inactive node carries a zero-differential payload: its
            # next retire decodes to an exact no-op even if it rejoins
            new_pay = jnp.where(act_b, new_pay, jnp.zeros_like(new_pay))
        new_l = self._ring(new_pay, +stride, mask=mask)
        new_r = self._ring(new_pay, -stride, mask=mask)

        clipped = jnp.zeros((), jnp.float32)
        if cfg.quant_mode == "fixed":
            # overflow is a property of the ENCODE, so the census reads
            # this step's freshly launched payload (its retire-side twin
            # at step k+1 would count the identical integers)
            for f in unit.fragments:
                cd = wire_codec.by_name(f.codec)
                clipped = clipped + cd.count_saturated(
                    jax.lax.slice_in_dim(y, f.row_start, f.row_end), step_k,
                    plan.fragment_payload(new_pay, f, unit.byte_start),
                    layout.block)
        overflow = clipped / float(plan.codes_total(layout.block))

        new_state = {"x_tilde": xt_new, "m_agg": m_new,
                     "fly_self": new_pay, "fly_up": new_l, "fly_dn": new_r}
        if push:
            new_state["ps_w"] = ps_new
            new_state["ps_nbr"] = jnp.concatenate([w_l, w_r])
        residual = jnp.sqrt(jnp.sum(y * y)
                            / float(layout.n_rows * layout.block))
        if act_b is not None:
            overflow = jnp.where(act_b, overflow, 0.0)
            residual = jnp.where(act_b, residual, 0.0)
        metrics = {"overflow_frac": overflow, "residual_norm": residual,
                   **self._wire_metrics(layout)}
        acct = self.wire_accounting(layout.n_elements, layout=layout)
        retired = None
        if push:
            metrics["push_sum_weight"] = ps_new[0]
        if eff_up is not None:
            # accounting for the transfer retired this step (step k-1's
            # draws): a deadline miss is billed exactly like a drop
            delivered = (eff_up.astype(jnp.float32)
                         + eff_dn.astype(jnp.float32))
            if act_b is not None:
                delivered = jnp.where(act_b, delivered, 0.0)
            retired = delivered
            metrics["wire_bytes_delivered"] = acct.delivered_bytes(delivered)
            metrics["delivered_frac"] = delivered / 2.0
        if meet_up is not None:
            miss = ((1.0 - meet_up.astype(jnp.float32))
                    + (1.0 - meet_dn.astype(jnp.float32))) / 2.0
            if act_b is not None:
                miss = jnp.where(act_b, miss, 0.0)
            metrics["deadline_miss_frac"] = miss
        if cfg.membership is not None:
            metrics["active_nodes"] = jnp.asarray(
                float(sum(mask) if mask is not None
                      else self.ctx.total_consensus_nodes), jnp.float32)
        self._telemetry_metrics(metrics, acct, clipped, resync, resync_ok,
                                act_b, retired=retired)
        if cfg.track_consensus_error:
            metrics["consensus_err"] = _consensus_error(x_next, self.ctx)
        return x_next, new_state, metrics

    # ------------------------------------------------------------------
    def _adc_exchange_per_leaf(self, x_prev, x_half, state, step, key,
                               stride=1, noise=None, layout=None, mask=None):
        """Per-leaf reference wire path (the historical hot loop): per leaf
        a noise draw, a quantize launch, FOUR ring collectives (codes/
        scales x both directions) and a dequant-combine launch.  Shares
        the packed shadow state with :meth:`_adc_exchange`; given the same
        injected ``noise`` buffer the two paths are bit-for-bit
        interchangeable (tests/test_wire.py).  Kept for equivalence
        testing and the consensus_step_latency benchmark.
        """
        cfg, ctx = self.cfg, self.ctx
        assert mask is None, "per-leaf reference path has no membership"
        if layout is None:
            layout = self.state_layout(x_half)
        resync = self._resync_flag(step)
        step_k = self._step_k(step)
        key = _device_key(key, ctx)
        push = cfg.push_sum_enabled
        w_fwd, w_bwd = cfg.in_weights
        directed = w_fwd != w_bwd
        keep_up, keep_dn = self._keep_flags(step)
        resync_ok = self._resync_ok(resync, step)
        if push:
            # reference path: the weight scalar is its own (tiny) ppermute
            # pair instead of the packed payload trailer — same received
            # values bit-for-bit (the trailer is an fp32 bitcast roundtrip)
            ps_w = state["ps_w"]
            fresh_l = _ppermute_ring(ps_w, ctx, +stride)
            fresh_r = _ppermute_ring(ps_w, ctx, -stride)
            w_l, w_r = fresh_l, fresh_r
            if keep_up is not None:
                w_l = jnp.where(keep_up, fresh_l, state["ps_nbr"][0:1])
                w_r = jnp.where(keep_dn, fresh_r, state["ps_nbr"][1:2])
            if resync is not None:
                # bounded-retry control-plane refresh at epoch boundaries
                # (the fresh ppermute already ran on this path, so no
                # extra collective inside a cond); a failed handshake
                # keeps the stale weights, like the packed paths
                ok = resync if resync_ok is None else jnp.logical_and(
                    resync, resync_ok)
                w_l = jnp.where(ok, fresh_l, w_l)
                w_r = jnp.where(ok, fresh_r, w_r)
            ps_new = ps_w + (jnp.float32(w_fwd) * (w_l - ps_w)
                             + jnp.float32(w_bwd) * (w_r - ps_w))
        leaves, treedef = jax.tree_util.tree_flatten(x_half)
        prev_leaves = jax.tree_util.tree_flatten(x_prev)[0]
        leaf_keys = (jax.random.split(key, len(leaves))
                     if noise is None else None)

        def rowpad(a, rows):
            # per-leaf buffers padded to the historical TILE_N-aligned
            # blockify height (zero rows quantize to code 0, so padding is
            # inert); the packed layout itself is row-granular
            return jnp.pad(a, ((0, rows - a.shape[0]), (0, 0)))

        new_x, new_xt_rows, new_m_rows = [], [], []
        clipped_acc = jnp.zeros((), jnp.float32)
        residual_sq = jnp.zeros((), jnp.float32)
        for i, (leaf_half, leaf_prev) in enumerate(zip(leaves, prev_leaves)):
            slot = layout.slots[i]
            full = kops.padded_block_rows(slot.size)
            xp_b = kops.blockify(leaf_prev.astype(jnp.float32).reshape(-1))
            if push:
                xp_b = xp_b * ps_w[0]       # numerator domain (cf. packed)
            xtb = rowpad(layout.leaf_rows(state["x_tilde"], i), full)
            mb = rowpad(layout.leaf_rows(state["m_agg"], i), full)
            yb = xp_b - xtb
            residual_sq = residual_sq + jnp.sum(yb * yb)
            if noise is None:       # historical per-leaf noise stream
                with jax.named_scope("noise"):
                    noise_b = jax.random.uniform(leaf_keys[i], yb.shape,
                                                 jnp.float32)
            else:                   # injected shared stream (equivalence)
                noise_b = rowpad(layout.leaf_rows(noise, i), full)
            with jax.named_scope("encode"):
                codes, scales = kops.quantize_blocks(
                    yb, noise_b, fixed_step=step_k, use_pallas=cfg.use_pallas)
            if cfg.quant_mode == "fixed":
                clipped_acc = clipped_acc + jnp.sum(
                    (jnp.abs(codes.astype(jnp.float32)) >= 127)
                    .astype(jnp.float32))
            # per-leaf ring exchange (the 4 x n_leaves collective tax)
            c_l = _ppermute_ring(codes, ctx, +stride)
            s_l = _ppermute_ring(scales, ctx, +stride)
            c_r = _ppermute_ring(codes, ctx, -stride)
            s_r = _ppermute_ring(scales, ctx, -stride)
            if keep_up is not None:
                # dropped packet == zero codes AND zero scales: exactly
                # what decoding the packed path's zeroed payload yields
                c_l = jnp.where(keep_up, c_l, jnp.zeros_like(c_l))
                s_l = jnp.where(keep_up, s_l, jnp.zeros_like(s_l))
                c_r = jnp.where(keep_dn, c_r, jnp.zeros_like(c_r))
                s_r = jnp.where(keep_dn, s_r, jnp.zeros_like(s_r))
            if resync is not None:
                def _rebuild(xtb=xtb, mb=mb):
                    xt_l = _ppermute_ring(xtb, ctx, +stride)
                    xt_r = _ppermute_ring(xtb, ctx, -stride)
                    if directed:
                        built = (jnp.float32(w_fwd) * xt_l
                                 + jnp.float32(w_bwd) * xt_r)
                    else:
                        built = jnp.float32(cfg.side_weight) * (xt_l + xt_r)
                    if resync_ok is not None:
                        built = jnp.where(resync_ok, built, mb)
                    return built
                mb = jax.lax.cond(resync, _rebuild, lambda mb=mb: mb)
            with jax.named_scope("combine"):
                xt_new_b, m_new_b, comb_b = kops.dequant_combine(
                    codes, scales, c_l, s_l, c_r, s_r, xtb, mb,
                    cfg.self_weight, cfg.side_weight, jnp.float32(1.0),
                    use_pallas=cfg.use_pallas)
            if directed:
                # same antisymmetric out-of-kernel correction as the
                # packed path (see _adc_exchange)
                d_l = c_l.astype(jnp.float32) * s_l
                d_r = c_r.astype(jnp.float32) * s_r
                # barrier pins rounding (no fma contraction) so the
                # reference stays bit-identical to the packed transport
                t = jax.lax.optimization_barrier(
                    jnp.float32(w_fwd - cfg.side_weight) * (d_l - d_r))
                m_new_b = m_new_b + t
                comb_b = comb_b + t
            if push:
                comb_b = comb_b / ps_new[0]         # de-bias z = num / w
            grad_step = (leaf_half.astype(jnp.float32)
                         - leaf_prev.astype(jnp.float32))
            combined = kops.unblockify(comb_b, slot.size).reshape(slot.shape)
            new_x.append((combined + grad_step).astype(leaf_half.dtype))
            new_xt_rows.append(xt_new_b[: slot.n_rows])
            new_m_rows.append(m_new_b[: slot.n_rows])

        x_next = jax.tree_util.tree_unflatten(treedef, new_x)
        new_state = {"x_tilde": layout.from_leaf_rows(new_xt_rows),
                     "m_agg": layout.from_leaf_rows(new_m_rows)}
        if push:
            new_state["ps_w"] = ps_new
            new_state["ps_nbr"] = jnp.concatenate([w_l, w_r])
        overflow = clipped_acc / float(layout.n_rows * layout.block)
        residual = jnp.sqrt(residual_sq
                            / float(layout.n_rows * layout.block))
        metrics = {"overflow_frac": overflow, "residual_norm": residual,
                   **self._wire_metrics(layout)}
        acct = self.wire_accounting(layout.n_elements, layout=layout)
        if push:
            metrics["push_sum_weight"] = ps_new[0]
        if keep_up is not None:
            delivered = (keep_up.astype(jnp.float32)
                         + keep_dn.astype(jnp.float32))
            metrics["wire_bytes_delivered"] = acct.delivered_bytes(delivered)
            metrics["delivered_frac"] = delivered / 2.0
        self._telemetry_metrics(metrics, acct, clipped_acc, resync,
                                resync_ok, None)
        if cfg.track_consensus_error:
            metrics["consensus_err"] = _consensus_error(x_next, self.ctx)
        return x_next, new_state, metrics

    # ------------------------------------------------------------------
    def _cdgd_exchange_packed(self, x_prev, x_half, state, step, key,
                              stride=1, noise=None, layout=None):
        """Direct-compression DGD (Eq. (5), negative control), packed wire:
        one quantize launch over the packed x and one payload ppermute per
        ring direction.  The node's own x enters the mix uncompressed
        (matching :class:`repro.core.consensus.CompressedDGD`).  The wire
        is the int8 payload; ``cfg.wire_dtype`` applies only to the
        uncompressed ``dgd`` baseline."""
        cfg, ctx = self.cfg, self.ctx
        if layout is None:
            layout = self.state_layout(x_half)
        chunks = self._chunks_for(layout)
        key = _device_key(key, ctx)
        xp_p = layout.pack(x_prev)
        if noise is None:
            noise = jax.random.uniform(key, xp_p.shape, jnp.float32)

        def launch(c):
            start, rows = chunks.bounds[c]
            pay = kops.quantize_payload(
                xp_p, noise, fixed_step=jnp.float32(cfg.fixed_step0),
                use_pallas=cfg.use_pallas, row_offset=start, n_rows=rows)
            return (_ppermute_ring(pay, ctx, +stride),
                    _ppermute_ring(pay, ctx, -stride))

        def retire(c, inflight):
            p_l, p_r = inflight
            c_l, s_l = kops.unpack_payload(p_l, layout.block)
            c_r, s_r = kops.unpack_payload(p_r, layout.block)
            left = c_l.astype(jnp.float32) * s_l
            right = c_r.astype(jnp.float32) * s_r
            return (cfg.self_weight * chunks.slice_rows(xp_p, c)
                    + cfg.side_weight * (left + right))

        mixed = chunks.concat(
            _pipeline_schedule(chunks.n_chunks, launch, retire))
        mixed_leaves = layout.unpack(mixed, cast=False)
        x_next = jax.tree.map(
            lambda m, h, p: (m + (h.astype(jnp.float32)
                                  - p.astype(jnp.float32))).astype(h.dtype),
            mixed_leaves, x_half, x_prev)
        metrics = self._wire_metrics(layout)
        if cfg.track_consensus_error:
            metrics["consensus_err"] = _consensus_error(x_next, self.ctx)
        return x_next, state, metrics

    def _cdgd_exchange_per_leaf(self, x_prev, x_half, state, step, key,
                                stride=1, noise=None, layout=None):
        """Per-leaf reference of :meth:`_cdgd_exchange_packed` (4 ring
        collectives per leaf); bit-identical given the same injected
        noise buffer."""
        cfg, ctx = self.cfg, self.ctx
        if layout is None:
            layout = self.state_layout(x_half)
        key = _device_key(key, ctx)
        leaves, treedef = jax.tree_util.tree_flatten(x_half)
        prev_leaves = jax.tree_util.tree_flatten(x_prev)[0]
        leaf_keys = (jax.random.split(key, len(leaves))
                     if noise is None else None)
        out = []
        for i, (leaf_half, leaf_prev) in enumerate(zip(leaves, prev_leaves)):
            slot = layout.slots[i]
            xb = kops.blockify(leaf_prev.astype(jnp.float32).reshape(-1))
            if noise is None:
                noise_i = jax.random.uniform(leaf_keys[i], xb.shape,
                                             jnp.float32)
            else:
                noise_i = jnp.pad(layout.leaf_rows(noise, i),
                                  ((0, xb.shape[0] - slot.n_rows), (0, 0)))
            codes, scales = kops.quantize_blocks(
                xb, noise_i, fixed_step=jnp.float32(cfg.fixed_step0),
                use_pallas=cfg.use_pallas)
            left = _ppermute_ring(codes, ctx, +stride).astype(jnp.float32) * \
                _ppermute_ring(scales, ctx, +stride)
            right = _ppermute_ring(codes, ctx, -stride).astype(jnp.float32) * \
                _ppermute_ring(scales, ctx, -stride)
            mixed = (cfg.self_weight * xb + cfg.side_weight * (left + right))
            mixed = kops.unblockify(mixed, slot.size).reshape(slot.shape)
            grad_step = (leaf_half.astype(jnp.float32)
                         - leaf_prev.astype(jnp.float32))
            out.append((mixed + grad_step).astype(leaf_half.dtype))
        x_next = jax.tree_util.tree_unflatten(treedef, out)
        metrics = self._wire_metrics(layout)
        if cfg.track_consensus_error:
            metrics["consensus_err"] = _consensus_error(x_next, self.ctx)
        return x_next, state, metrics

    # ------------------------------------------------------------------
    def _dgd_exchange(self, x_prev, x_half, state, step, key, stride=1,
                      layout=None):
        """Uncompressed DGD: mix the raw fp32/wire_dtype parameters each
        step (per leaf — the wire_dtype cast is the whole wire format)."""
        cfg, ctx = self.cfg, self.ctx
        del step, key
        w_self, w_side = cfg.self_weight, cfg.side_weight
        if layout is None:
            layout = self.state_layout(x_half)
        leaves, treedef = jax.tree_util.tree_flatten(x_half)
        prev_leaves = jax.tree_util.tree_flatten(x_prev)[0]
        out = []
        for leaf_half, leaf_prev in zip(leaves, prev_leaves):
            send = leaf_prev.astype(cfg.wire_dtype)
            left = _ppermute_ring(send, ctx, +stride).astype(jnp.float32)
            right = _ppermute_ring(send, ctx, -stride).astype(jnp.float32)
            mixed = (w_self * leaf_prev.astype(jnp.float32)
                     + w_side * (left + right))
            grad_step = (leaf_half.astype(jnp.float32)
                         - leaf_prev.astype(jnp.float32))
            out.append((mixed + grad_step).astype(leaf_half.dtype))
        x_next = jax.tree_util.tree_unflatten(treedef, out)
        metrics = self._wire_metrics(layout)
        if cfg.track_consensus_error:
            metrics["consensus_err"] = _consensus_error(x_next, self.ctx)
        return x_next, state, metrics


def _node_group_sum(x, ctx: ParallelContext):
    """Sum over the consensus-node subgroup (same fsdp rank across nodes &
    pods) via a ppermute rotation ring — psum(axis_index_groups=...) is not
    implemented under shard_map in this jax version."""
    n = ctx.total_consensus_nodes
    acc = x
    rot = x
    for _ in range(n - 1):
        rot = _ppermute_ring(rot, ctx, 1)
        acc = acc + rot
    return acc


def _allreduce_mean_delta(x_prev, x_half, ctx: ParallelContext):
    """Classic sync data-parallelism: average the optimizer delta over the
    consensus-node set (ppermute-rotation all-reduce on the node ring)."""
    n = ctx.total_consensus_nodes
    if n <= 1:
        return x_half

    def avg(xp, xh):
        delta = (xh - xp).astype(jnp.float32)
        s = _node_group_sum(delta, ctx)
        return (xp.astype(jnp.float32) + s / n).astype(xh.dtype)

    return jax.tree.map(avg, x_prev, x_half)


def _consensus_error(params, ctx: ParallelContext):
    """|| x - mean_nodes(x) ||^2 summed over all shards (metrics only)."""
    n = ctx.total_consensus_nodes
    if n <= 1:
        return jnp.zeros((), jnp.float32)

    def err(x):
        x = x.astype(jnp.float32)
        mean = _node_group_sum(x, ctx) / n
        return jnp.sum((x - mean) ** 2)

    per_leaf = jax.tree.map(err, params)
    local = jax.tree.reduce(lambda a, b: a + b, per_leaf, jnp.zeros((), jnp.float32))
    # sum over every device (each holds a distinct shard), counting node
    # copies once: divide by tp (model ranks hold replicated *norm pieces*?
    # no: tp shards are distinct slices, fsdp shards distinct slices; the
    # psum above already spans nodes, so summing local over (data_groups x
    # model) counts each shard exactly once per node -> psum all and / n.
    total = local
    if ctx.data_size > 1:
        total = jax.lax.psum(total, "data")
    if ctx.pod_axis is not None and ctx.pods > 1:
        total = jax.lax.psum(total, "pod")
    if ctx.tp > 1:
        total = jax.lax.psum(total, "model")
    return total / n
