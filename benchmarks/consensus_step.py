"""consensus_step_latency: per-leaf vs packed vs pipelined wire paths,
plus the wire-codec sweep and the adaptive bit-budget controller demo.

Times one jit'd ADC-DGD consensus exchange (no model forward/backward — the
consensus step IS the system under test) on a >=4-device host-platform mesh
for the ``smollm_135m`` and ``qwen3_0_6b`` parameter trees, using each
device's *local* shard shapes from the production 16x16 (fsdp x tp) mesh
factored into 4 consensus nodes — exactly the per-device tree the trainer's
hot loop exchanges every step.

The trees are the **per-layer logical trees** (every transformer layer its
own set of leaves, i.e. ``ModelDefs.period`` repeated ``n_periods`` times
plus embed/final norm) — what any non-layer-scanned runtime exchanges, and
the leaf count that makes the per-leaf tax visible: O(100) leaves ->
4 x O(100) ring collectives per step on the per-leaf path vs exactly 2 on
the packed path.

Measured per arch and per wire path (``ConsensusConfig.wire_packing``):
  * steps/s under ``jax.jit`` (best-of-repeats wall clock; quantization
    noise is pre-generated and injected so the PRNG — identical in all
    paths — is excluded and the measurement isolates the wire path),
  * ring collectives per step (counted as ``ppermute`` eqns in the traced
    jaxpr — not hand-derived),
  * wire bytes per step (``ConsensusRuntime.wire_bytes_per_step``),
  * trace+compile seconds (the per-leaf path also pays an O(leaves)
    compile tax).

The pipelined (chunked double-buffered) path is swept over
``CHUNK_SWEEP`` chunk counts — chunking hides transfer latency behind
quantize/dequant compute when the exchange is transfer-bound, but pays
2 x chunks collectives and extra launch overhead, so the best chunk count
is hardware- and tree-dependent (EXPERIMENTS.md §Perf).  Chunk count 1 is
part of the sweep: it is structurally the monolithic packed path, so the
best swept configuration can never lose to packed by more than timing
noise.

The **codec sweep** (smollm-135m, packed path) measures each wire codec in
``CODEC_SWEEP`` — int8 / int4 / int2 / topk (DESIGN.md §Wire codecs) —
plus two **mixed per-leaf wire plans** (DESIGN.md §Wire plans):
``MIXED_PLAN`` (norms/embeddings cold at int4, projections hot at int8;
bytes- AND fidelity-gated) and ``MIXED_PLAN_AGGR`` (cold slots at int2;
bytes-gated only — its row documents the per-leaf sensitivity trade),
reporting steps/s, wire bytes/step, and the consensus error of a short
pure-gossip run (xh == x; per-device random init) so the bandwidth/fidelity
trade is a measured table (EXPERIMENTS.md §Wire codecs), and the
**controller demo** runs fixed-mode epochs with the AdaptiveBitController
in the loop, logging the codec chosen per epoch — the amplified grid
``Delta_0 / k^gamma`` shrinks across epochs, so the trace must walk the
bit-budget ladder.  The **equal-bytes choco_vs_adc section** routes the
reference ADC-DGD and CHOCO-SGD gossip wires through the SAME WirePlan
(``core.wireplan.WirePlanCompressor``) per plan, gating that their
cumulative bytes are exactly equal and both contract the gradient norm.

Writes ``BENCH_consensus_step.json`` at the repo root (the perf-trajectory
artifact tracked from PR 2 onward) plus a copy under
``benchmarks/artifacts/``.  CI smoke gates (exit non-zero):
  * packed slower than the per-leaf reference,
  * pipelined at its best swept chunk count slower than monolithic packed
    beyond the NOISE_TOL timing-noise tolerance (plus a deterministic
    structural check: chunks=1 must trace exactly 2 collectives),
  * packed trace+compile time above COMPILE_BUDGET_S (a trace-size blowup
    guard for the _adc_exchange rewrite),
  * any sub-byte/sparse codec NOT strictly below int8's wire bytes/step,
    int4 or topk below the 2x reduction the sub-byte formats promise,
  * the adaptive controller not switching codecs across the demo epochs,
  * the **packet-loss sweep** (directed-ring push-sum gossip under
    ``LOSS_SWEEP`` link-loss rates): any rate failing to contract the
    consensus error, rate 0.0 not bit-identical to the lossless path, the
    push-sum weight drifting off 1.0 on the homogeneous ring, or the
    delivered-bytes total not matching the ``faults.LossModel`` host
    oracle exactly (dropped payloads must be excluded from accounting),
  * the **hierarchy sweep** (two-level consensus, DESIGN.md §14): the
    inter-pod byte total failing to shrink by ~pod_size vs the flat
    compressed ring, the hierarchical gossip ending at worse consensus
    error than flat, or the hierarchical step tracing more than the
    2 ring ppermutes of the outer exchange.

It is a forced-host-device benchmark by design: it runs on four CPU
devices and never on an accelerator.  Run standalone (sets up its own
host devices):

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m benchmarks.consensus_step
"""
from __future__ import annotations

import json
import os
import sys
import time

N_DEVICES = 4

if "jax" not in sys.modules:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={N_DEVICES}"
        ).strip()

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P            # noqa: E402

from repro.configs import get_config                         # noqa: E402
from repro.core import telemetry, wire                       # noqa: E402
from repro.core.codec import AdaptiveBitController           # noqa: E402
from repro.core.distributed import (ConsensusConfig,         # noqa: E402
                                    ConsensusRuntime)
from repro.models import transformer as T                    # noqa: E402
from repro.models.params import ParamDef, local_block_shape  # noqa: E402
from repro.models.sharding import ParallelContext             # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCHS = ("smollm-135m", "qwen3-0.6b")
PROD_TP, PROD_FSDP, NODES = 16, 16, 4
STEPS_TIMED = 3
#: timed repeats per path: the reported seconds/step is the MEDIAN of the
#: repeats (PR 3's best-of-2 minimum let one lucky scheduling window pick
#: the winner on the noisy CI host and the best chunk count wandered
#: run-to-run); the per-repeat samples also feed the variance-aware
#: pipelined-vs-packed gate below
REPEATS = 5
#: untimed steps after compile, before the first timed repeat: the first
#: post-compile iterations run cold (allocator growth, instruction-cache
#: misses) and were previously charged to whichever path ran first
WARMUP_STEPS = 2
#: pipelined-path chunk counts swept per arch (1 == monolithic packed
#: structure, so the best swept config tracks packed within timing noise
#: even when chunking does not pay on this interconnect)
CHUNK_SWEEP = (1, 2, 4, 8)
#: trace+compile budget for the packed path: a trace-size *blowup* guard,
#: not a tight SLA — PR 2 measured ~9 s and the PR 3 pipelined rewrite
#: ~11 s on the CI host, whose compile times jitter tens of percent under
#: load; the budget only needs to catch order-of-magnitude regressions
#: (e.g. an accidentally unrolled scan)
COMPILE_BUDGET_S = 20.0
#: packed-path wire codecs swept on smollm-135m (DESIGN.md §Wire codecs)
CODEC_SWEEP = ("int8", "int4", "int2", "topk")
#: the mixed per-leaf plan column (DESIGN.md §Wire plans): cold slots
#: (norms + embeddings, the low-sensitivity rows) at int4, hot projection
#: rows pinned at int8.  CI gates: strictly fewer bytes/step than uniform
#: int8 AND pure-gossip fidelity within MIXED_FIDELITY_TOL of it.
MIXED_PLAN = "mixed:norm=int4,embed=int4,*=int8"
#: a second, aggressive plan recorded for the EXPERIMENTS.md table (norms
#: + embeddings at int2) — bytes-gated only; its int2 rows dominate the
#: gossip error, which is exactly the sensitivity story the table tells
MIXED_PLAN_AGGR = "mixed:norm=int2,embed=int2,*=int8"
MIXED_FIDELITY_TOL = 10.0
#: equal-bytes choco_vs_adc: reference ADC-DGD and CHOCO-SGD exchange
#: through the SAME WirePlan (core.wireplan.WirePlanCompressor), so their
#: bytes/step are equal by construction — the comparison PR 1 could only
#: make at equal nominal bits
CHOCO_EB_STEPS = 400
CHOCO_EB_LR = 0.05
CHOCO_EB_CONSENSUS_LR = 0.1
#: pure-gossip steps for the per-codec consensus-error column
GOSSIP_STEPS = 6
#: controller demo: epochs x steps/epoch of fixed-mode exchanges with the
#: AdaptiveBitController re-selecting the codec at every epoch boundary
CONTROLLER_EPOCHS = 4
CONTROLLER_EPOCH_STEPS = 5
CONTROLLER_STEP0 = 0.02
#: timing-noise floor for the pipelined-vs-packed gate: chunks=1 traces a
#: program identical to packed yet has measured up to ~45% faster/slower
#: on the shared CI host, so the timing gate's honest resolution is
#: catching ~2x genuine regressions — anything finer is delegated to the
#: deterministic chunks=1 structural check below.  The effective gate is
#: variance-aware: this floor is loosened further by the measured
#: per-repeat spread of the two paths being compared (_timing_gate).
NOISE_TOL = 0.5
#: packet-loss sweep (directed-ring push-sum gossip, smollm-135m): per
#: rate, a pure-gossip run must still contract consensus error, and the
#: delivered-bytes accounting must match the LossModel's host oracle
#: exactly; rate 0.0 must be bit-identical to the lossless (link_loss=
#: None) trace
LOSS_SWEEP = (0.0, 0.05, 0.2)
LOSS_GOSSIP_STEPS = 8
LOSS_SEED = 1
#: churn sweep (symmetric-ring packed gossip, smollm-135m): node 2 departs
#: for schedule epoch 1 and rejoins at epoch 2; after rejoin the run gets
#: CHURN_RECOVERY_EPOCHS epochs to contract back toward the static-
#: membership trajectory.  A burst-loss variant stacks a Gilbert-Elliott
#: channel on top of the churn; a single all-active mask must stay
#: bit-identical to membership=None (inert machinery, like loss 0.0)
CHURN_MASKS = ((True, True, True, True),
               (True, True, False, True),
               (True, True, True, True))
CHURN_PERIOD = 4
CHURN_RECOVERY_EPOCHS = 2
#: recovery thresholds (mirroring tests/test_membership.py's churn
#: scenario): end error under 0.2x the start AND within 5x the static-
#: membership end-point AND below the at-rejoin error
CHURN_RECOVERY_TOL = 0.2
CHURN_RECOVERY_FACTOR = 5.0
#: pure gossip mixes geometrically, so the static reference reaches the
#: fp32 rounding floor (~1e-12 here) inside the window; ratios between
#: tails below NOISE x the start error compare rounding noise, not
#: mixing, so the static end-point is floored before the FACTOR gate
CHURN_NOISE_FLOOR = 1e-7
CHURN_GOSSIP_STEPS = CHURN_PERIOD * (len(CHURN_MASKS) - 1 +
                                     CHURN_RECOVERY_EPOCHS)
CHURN_BURST = "gilbert:p=0.1,r=0.9"
#: overlap benchmark (wire_packing="async"): a synthetic-compute load (a
#: fori_loop matmul chain per device, the model fwd/bwd stand-in) is fused
#: into the exchange step but kept DATA-INDEPENDENT of it, so XLA may
#: schedule the ring collectives concurrently with the matmul chain.  The
#: iteration count is auto-calibrated so compute dominates: roughly
#: OVERLAP_TARGET_RATIO x the bare packed exchange.
OVERLAP_MM_DIM = 384
OVERLAP_TARGET_RATIO = 8.0
OVERLAP_CAL_ITERS = 8
OVERLAP_MIN_ITERS = 4
OVERLAP_MAX_ITERS = 512
#: ISSUE acceptance: under the compute-dominated load, the async path's
#: consensus overhead (t_step - t_compute) / t_step must stay below 15%
OVERLAP_OVERHEAD_BUDGET = 0.15
OVERLAP_PIPE_CHUNKS = 2
#: hierarchy sweep (two-level consensus, DESIGN.md §14): flat compressed
#: ring vs intra-pod fp32 all-reduce + compressed inter-pod ADC gossip,
#: same packed wire and the same pod-identical inits.  The inter-pod
#: byte total counts one logical payload per DISTINCT pod (pod members
#: trace replicated sends of the same representative payload), so it
#: must shrink by ~pod_size vs the flat ring where every node is its own
#: pod.  CI gates: the measured ratio >= HIER_BYTES_RATIO_TOL x
#: pod_size, the hierarchical gossip ends at consensus error no worse
#: than flat (matched steps — bytes are bought with a psum, not
#: fidelity), both runs contract, and the hierarchical step still traces
#: EXACTLY 2 ring ppermutes (the outer exchange; the inner level is a
#: psum, not extra ring hops).
HIER_PODS = 2
HIER_GOSSIP_STEPS = 6
HIER_BYTES_RATIO_TOL = 0.9


def _timing_gate(*paths) -> float:
    """Variance-aware lower bound for a speed-ratio gate: the NOISE_TOL
    floor loosened by the worst relative per-repeat spread among the
    compared paths (a host noisy enough to blur its own repeats cannot
    support a tighter verdict).  The arithmetic lives in
    core.telemetry.timing_gate so the obs regression reporter applies the
    identical policy across bench-series runs."""
    return telemetry.timing_gate(*paths, noise_tol=NOISE_TOL)


def count_eqns(jaxpr, prim_name: str) -> int:
    """Recursively count equations of one primitive in a (Closed)Jaxpr."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    n = 0
    for eqn in inner.eqns:
        if eqn.primitive.name == prim_name:
            n += 1
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for vi in vs:
                if hasattr(vi, "eqns") or hasattr(vi, "jaxpr"):
                    n += count_eqns(vi, prim_name)
    return n


def local_leaf_tree(arch: str, key) -> dict:
    """One device's per-layer parameter shard tree (production layout).

    Per-layer leaves (``defs.period`` repeated ``n_periods`` times) rather
    than the trainer's scan-stacked storage leaves: the logical tree any
    non-scanned runtime exchanges, and the leaf count the per-leaf wire
    path actually pays for."""
    cfg = get_config(arch)
    prod_ctx = ParallelContext(tp=PROD_TP, data_size=NODES * PROD_FSDP,
                               n_nodes=NODES)
    defs = T.build_defs(cfg, prod_ctx)
    def_tree = {
        "embed": defs.storage["embed"],
        "layers": tuple(defs.period) * cfg.n_periods,
        "final_norm": defs.storage["final_norm"],
    }
    if defs.prelude:
        def_tree["prelude"] = defs.prelude
    leaves, treedef = jax.tree_util.tree_flatten(
        def_tree, is_leaf=lambda x: isinstance(x, ParamDef))
    ks = jax.random.split(key, len(leaves))
    vals = [
        jax.random.normal(k, local_block_shape(d, PROD_TP, PROD_FSDP),
                          jnp.float32).astype(d.dtype)
        for k, d in zip(ks, leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, vals)


def build_step(rt: ConsensusRuntime, mesh, tree):
    """jit'd (x_prev, x_half, state, noise, k) -> (x_next, state').

    The bench trees carry a leading device dim of ``N_DEVICES`` (each
    consensus node holds its own copy of the local shard shapes).
    Quantization noise is injected (pre-generated once outside the timed
    loop): PRNG cost is identical in both wire paths and excluding it
    isolates exactly the per-leaf wire tax the packed path removes."""
    pspec = jax.tree.map(lambda _: P("data"), tree)
    cons_spec = {"x_tilde": P("data", None, None),
                 "m_agg": P("data", None, None)}
    noise_spec = P("data", None, None)

    def init(p):
        return jax.tree.map(lambda a: a[None], rt.init_state(p))

    init_f = jax.jit(jax.shard_map(init, mesh=mesh, in_specs=(pspec,),
                                   out_specs=cons_spec, check_vma=False))

    def step(xp, xh, st, noise, k):
        st = jax.tree.map(lambda a: a[0], st)
        x_next, st2, _ = rt.exchange(xp, xh, st, k, jax.random.PRNGKey(3),
                                     noise=noise[0])
        return x_next, jax.tree.map(lambda a: a[None], st2)

    step_f = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(pspec, pspec, cons_spec, noise_spec, P()),
        out_specs=(pspec, cons_spec), check_vma=False))
    return init_f, step_f


def time_path(rt, mesh, xp, xh, noise, label: str, built=None) -> dict:
    init_f, step_f = built if built is not None else build_step(rt, mesh, xp)
    st = jax.tree.map(lambda a: a.block_until_ready(), init_f(xp))
    k = jnp.asarray(2, jnp.int32)
    jaxpr = jax.make_jaxpr(step_f)(xp, xh, st, noise, k)
    collectives = count_eqns(jaxpr, "ppermute")
    # compile, then untimed warmup, then median-of-repeats timed loops
    # (median + warmup deflakes the chunk sweep on the noisy CI host —
    # the old best-of-2 minimum let one lucky scheduling window pick the
    # winning chunk count)
    t0 = time.perf_counter()
    x, s = step_f(xp, xh, st, noise, k)
    jax.tree.map(lambda a: a.block_until_ready(), (x, s))
    compile_s = time.perf_counter() - t0
    for _ in range(WARMUP_STEPS):
        x, s = step_f(x, xh, s, noise, k)
    jax.tree.map(lambda a: a.block_until_ready(), (x, s))
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        for _ in range(STEPS_TIMED):
            x, s = step_f(x, xh, s, noise, k)
        jax.tree.map(lambda a: a.block_until_ready(), (x, s))
        times.append((time.perf_counter() - t0) / STEPS_TIMED)
    sec = float(np.median(times))
    spread = float((np.max(times) - np.min(times)) / sec)
    print(f"  {label}: {1.0 / sec:8.2f} steps/s   {collectives} "
          f"ppermutes/step   (compile {compile_s:.0f}s, "
          f"spread {spread:.0%})", flush=True)
    return {"steps_per_s": 1.0 / sec, "seconds_per_step": sec,
            "collectives_per_step": collectives, "compile_s": compile_s,
            "timing_spread": spread,
            "timing_samples": [float(t) for t in times]}


def build_step_metrics(rt: ConsensusRuntime, mesh, tree):
    """Like :func:`build_step` but also surfaces the per-step residual RMS
    and clip fraction — the AdaptiveBitController's feedback signals."""
    pspec = jax.tree.map(lambda _: P("data"), tree)
    cons_spec = {"x_tilde": P("data", None, None),
                 "m_agg": P("data", None, None)}
    noise_spec = P("data", None, None)

    def init(p):
        return jax.tree.map(lambda a: a[None], rt.init_state(p))

    init_f = jax.jit(jax.shard_map(init, mesh=mesh, in_specs=(pspec,),
                                   out_specs=cons_spec, check_vma=False))

    def step(xp, xh, st, noise, k):
        st = jax.tree.map(lambda a: a[0], st)
        x_next, st2, m = rt.exchange(xp, xh, st, k, jax.random.PRNGKey(3),
                                     noise=noise[0])
        return (x_next, jax.tree.map(lambda a: a[None], st2),
                m["residual_norm"][None], m["overflow_frac"][None])

    step_f = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(pspec, pspec, cons_spec, noise_spec, P()),
        out_specs=(pspec, cons_spec, P("data"), P("data")), check_vma=False))
    return init_f, step_f


def _codec_noise(rt: ConsensusRuntime, layout: wire.WireLayout, seed=0):
    return jnp.asarray(np.random.default_rng(seed).random(
        (N_DEVICES, layout.n_rows, rt.noise_cols_for(layout)),
        np.float32))


def _consensus_err(x) -> float:
    """Normalized dispersion of the per-device copies (leading dim)."""
    total, count = 0.0, 0
    for leaf in jax.tree_util.tree_leaves(x):
        a = np.asarray(jax.device_get(leaf), np.float64)
        total += float(np.sum((a - a.mean(axis=0, keepdims=True)) ** 2))
        count += a[0].size
    return total / count


def codec_section(mesh, ctx) -> tuple[dict, bool]:
    """Wire-codec sweep + adaptive-controller demo (smollm-135m, packed).

    Per codec: steps/s (same harness as the wire-path columns), wire
    bytes/step, and the consensus error of a GOSSIP_STEPS pure-gossip run
    from per-device random inits (xh == x isolates the mixing fidelity —
    coarser codecs buy bandwidth with slower/looser consensus).  Then the
    controller demo: fixed-mode epochs with the amplified grid shrinking
    as Delta_0 / k, the controller re-selecting the codec from measured
    residual/overflow at every epoch boundary.
    """
    arch = "smollm-135m"
    ok = True
    key = jax.random.PRNGKey(hash(arch) % 2**31)
    local = local_leaf_tree(arch, key)
    layout = wire.WireLayout.for_tree(local)
    xp = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (N_DEVICES, *a.shape)), local)
    xh = jax.tree.map(
        lambda a: (a.astype(jnp.float32) + 1e-3).astype(a.dtype), xp)
    # per-device DISTINCT copies for the pure-gossip fidelity runs
    leaves, treedef = jax.tree_util.tree_flatten(local)
    ks = jax.random.split(jax.random.fold_in(key, 1), len(leaves))
    x0 = jax.tree_util.tree_unflatten(treedef, [
        (jax.random.normal(k2, (N_DEVICES, *a.shape), jnp.float32) * 0.05)
        .astype(a.dtype)
        for k2, a in zip(ks, leaves)])
    sweep = {}
    sweep_specs = {**{n: n for n in CODEC_SWEEP},
                   "mixed": MIXED_PLAN, "mixed_aggr": MIXED_PLAN_AGGR}
    print(f"codec sweep ({arch}, packed): {layout.n_elements:,} local "
          f"params, {layout.n_rows} rows", flush=True)
    for name, spec in sweep_specs.items():
        rt = ConsensusRuntime(
            ConsensusConfig(algorithm="adc_dgd", quant_mode="adaptive",
                            wire_codec=spec), ctx)
        # the runtime's buffer order, NOT the flat tree order: mixed
        # plans reorder slots by codec at layout-build time (DESIGN.md
        # §Wire plans), so anything written into x_tilde/m_agg must be
        # packed with the placed layout the exchange actually uses
        slayout = rt.state_layout(local)
        noise = _codec_noise(rt, slayout)
        built = build_step(rt, mesh, xp)
        r = time_path(rt, mesh, xp, xh, noise, f"{arch}/codec[{name}]",
                      built=built)
        r["wire_bytes_per_step"] = rt.wire_bytes_per_step(
            slayout.n_elements, layout=slayout)
        # pure-gossip fidelity: same compiled step, xh == x, distinct inits.
        # init_state's m_0 = (1 - W_ii) x0 bakes in the shared-init
        # contract (DESIGN.md §Changed assumptions); these nodes start
        # DISTINCT, so m_agg is rebuilt from the actual ring neighbors
        # (w_side * (x_left + x_right)) — the same correction the
        # epoch-boundary resync performs
        init_f, step_f = built
        st = init_f(x0)
        xt0 = np.stack([np.asarray(slayout.pack(
            jax.tree.map(lambda a, d=d: a[d], x0))) for d in range(N_DEVICES)])
        w_side = rt.cfg.side_weight
        m0 = w_side * (np.roll(xt0, 1, axis=0) + np.roll(xt0, -1, axis=0))
        st = {"x_tilde": st["x_tilde"], "m_agg": jnp.asarray(m0)}
        x = x0
        r["consensus_err_start"] = _consensus_err(x)
        for k2 in range(1, GOSSIP_STEPS + 1):
            x, st = step_f(x, x, st, noise, jnp.asarray(k2, jnp.int32))
        r["consensus_err_end"] = _consensus_err(x)
        print(f"    gossip err {r['consensus_err_start']:.3e} -> "
              f"{r['consensus_err_end']:.3e}   "
              f"{r['wire_bytes_per_step'] / 1e6:.2f} MB/step", flush=True)
        if spec != name:
            r["wire_plan"] = spec
        sweep[name] = r
    int8_bytes = sweep["int8"]["wire_bytes_per_step"]
    for name in ("int4", "int2", "topk"):
        if not sweep[name]["wire_bytes_per_step"] < int8_bytes:
            print(f"FAIL[codec]: {name} does not shrink wire bytes "
                  f"({sweep[name]['wire_bytes_per_step']} vs {int8_bytes})")
            ok = False
    for name in ("int4", "topk"):
        if int8_bytes / sweep[name]["wire_bytes_per_step"] < 2.0:
            print(f"FAIL[codec]: {name} below the promised 2x byte "
                  "reduction vs int8")
            ok = False
    for name in sweep_specs:
        if not sweep[name]["consensus_err_end"] \
                < sweep[name]["consensus_err_start"]:
            print(f"FAIL[codec]: {name} gossip did not contract "
                  "consensus error")
            ok = False
    # mixed-plan gates (DESIGN.md §Wire plans): strictly fewer bytes than
    # uniform int8 (both plans) AND the shipped plan's pure-gossip fidelity
    # within MIXED_FIDELITY_TOL of int8's (only the aggressive int2 plan
    # may trade fidelity beyond that — its row in the table is the
    # per-leaf sensitivity story, not the shipping default)
    for name in ("mixed", "mixed_aggr"):
        if not sweep[name]["wire_bytes_per_step"] < int8_bytes:
            print(f"FAIL[codec]: {name} plan does not ship strictly fewer "
                  f"bytes/step than uniform int8 "
                  f"({sweep[name]['wire_bytes_per_step']} vs {int8_bytes})")
            ok = False
    fid = (sweep["mixed"]["consensus_err_end"]
           / max(sweep["int8"]["consensus_err_end"], 1e-30))
    sweep["mixed"]["fidelity_vs_int8"] = fid
    if fid > MIXED_FIDELITY_TOL:
        print(f"FAIL[codec]: mixed plan gossip fidelity {fid:.1f}x worse "
              f"than int8 (tolerance {MIXED_FIDELITY_TOL:.0f}x)")
        ok = False

    # -- adaptive controller demo (fixed-mode epochs) --------------------
    ctl = AdaptiveBitController(fixed_step0=CONTROLLER_STEP0, gamma=1.0,
                                patience=1)
    trace = [ctl.initial(layout.n_rows)]
    steps_f, states, xs = {}, {}, {}
    print(f"controller demo: start {trace[0]}, Delta_k = "
          f"{CONTROLLER_STEP0}/k, {CONTROLLER_EPOCHS} epochs x "
          f"{CONTROLLER_EPOCH_STEPS} steps", flush=True)
    x = xp
    st = None
    noise_by = {}
    k = 0
    for epoch in range(CONTROLLER_EPOCHS):
        name = trace[-1]
        if name not in steps_f:
            rt = ConsensusRuntime(
                ConsensusConfig(algorithm="adc_dgd", quant_mode="fixed",
                                fixed_step0=CONTROLLER_STEP0,
                                wire_codec=name), ctx)
            steps_f[name] = (rt, *build_step_metrics(rt, mesh, x))
            noise_by[name] = _codec_noise(steps_f[name][0], layout)
        rt, init_f, step_f = steps_f[name]
        if st is None:
            st = init_f(x)
        res_l, ovf_l = [], []
        for _ in range(CONTROLLER_EPOCH_STEPS):
            k += 1
            xh_k = jax.tree.map(
                lambda a: (a.astype(jnp.float32) + 1e-3).astype(a.dtype), x)
            x, st, res, ovf = step_f(x, xh_k, st, noise_by[name],
                                     jnp.asarray(k, jnp.int32))
            res_l.append(float(np.mean(np.asarray(res))))
            ovf_l.append(float(np.mean(np.asarray(ovf))))
        chosen = ctl.select(k + 1, residual_rms=float(np.mean(res_l)),
                            overflow_frac=float(np.mean(ovf_l)),
                            n_rows=layout.n_rows)
        print(f"  epoch {epoch}: ran {name}, residual_rms="
              f"{np.mean(res_l):.3g} overflow={np.mean(ovf_l):.3g} "
              f"-> next codec {chosen}", flush=True)
        trace.append(chosen)
    controller = {"trace": trace, "epoch_steps": CONTROLLER_EPOCH_STEPS,
                  "fixed_step0": CONTROLLER_STEP0,
                  "switched": len(set(trace)) > 1}
    if not controller["switched"]:
        print(f"FAIL[codec]: controller never switched codecs: {trace}")
        ok = False
    return {"sweep": sweep, "controller": controller}, ok


def choco_equal_bytes_section() -> tuple[dict, bool]:
    """ADC-DGD vs CHOCO-SGD with BOTH gossip wires routed through the same
    WirePlan (core.wireplan.WirePlanCompressor): the error-feedback wire
    and the amplified-differential wire ship byte-identical heterogeneous
    payloads, so bytes/step are equal by construction — the head-to-head
    the PR 1 ``choco_vs_adc`` benchmark could only run at equal *nominal
    bits*.  Run per plan (uniform int8 + the mixed plan) on the paper's
    circle problem; gates: exactly-equal cumulative bytes within each
    pair, and both algorithms contract the gradient norm.
    """
    from repro.core import consensus, problems, topology, wireplan
    ok = True
    # a two-leaf layout so the mixed plan has real per-leaf structure
    tree = {"proj": jax.ShapeDtypeStruct((8 * 512,), jnp.float32),
            "norm1": jax.ShapeDtypeStruct((200,), jnp.float32)}
    layout = wire.WireLayout.for_tree(tree)
    prob = problems.paper_circle_problem(4, seed=0, dim=layout.n_elements)
    mix = topology.ring(4)
    ss = consensus.StepSize(CHOCO_EB_LR, 0.5)
    out = {"dim": layout.n_elements, "steps": CHOCO_EB_STEPS,
           "consensus_lr": CHOCO_EB_CONSENSUS_LR, "plans": {}}
    print(f"choco_vs_adc equal-bytes (dim {layout.n_elements}, ring4, "
          f"{CHOCO_EB_STEPS} steps):", flush=True)
    for label, spec in (("int8", "int8"), ("mixed", MIXED_PLAN)):
        plan = wireplan.parse_spec(spec).build(layout)
        res = {"wire_plan": spec,
               "payload_bytes": float(plan.payload_bytes)}
        for aname in ("adc_dgd", "choco"):
            alg = consensus.on_wire_plan(
                aname, mix, plan, ss,
                **({"gamma": 1.0} if aname == "adc_dgd"
                   else {"consensus_lr": CHOCO_EB_CONSENSUS_LR}))
            r = consensus.run(alg, prob, CHOCO_EB_STEPS, key=31)
            res[aname] = {
                "tail_gradnorm": float(np.mean(r["grad_norm"][-50:])),
                "tail_consensus": float(np.mean(r["consensus"][-50:])),
                "first_gradnorm": float(r["grad_norm"][0]),
                "total_bytes": float(r["bytes"][-1]),
            }
        eq = (res["adc_dgd"]["total_bytes"] == res["choco"]["total_bytes"])
        res["equal_bytes"] = eq
        print(f"  {label}: {res['payload_bytes'] / 1e3:.1f} KB/msg  "
              f"adc |g|={res['adc_dgd']['tail_gradnorm']:.2e} "
              f"choco |g|={res['choco']['tail_gradnorm']:.2e}  "
              f"equal_bytes={eq}", flush=True)
        if not eq:
            print(f"FAIL[choco_eb]: {label} adc/choco bytes differ "
                  f"({res['adc_dgd']['total_bytes']} vs "
                  f"{res['choco']['total_bytes']})")
            ok = False
        for aname in ("adc_dgd", "choco"):
            if not (res[aname]["tail_gradnorm"]
                    < res[aname]["first_gradnorm"]):
                print(f"FAIL[choco_eb]: {label}/{aname} did not contract "
                      "the gradient norm")
                ok = False
        out["plans"][label] = res
    return out, ok


def _build_loss_step(rt: ConsensusRuntime, mesh, tree):
    """:func:`build_step` variant for the push-sum transport: carries the
    ``ps_w``/``ps_nbr`` consensus-state entries and surfaces the per-device
    ``wire_bytes_delivered`` metric (zero when the loss machinery is off,
    so the compiled signature is rate-independent)."""
    pspec = jax.tree.map(lambda _: P("data"), tree)
    cons_spec = {"x_tilde": P("data", None, None),
                 "m_agg": P("data", None, None),
                 "ps_w": P("data", None),
                 "ps_nbr": P("data", None)}
    noise_spec = P("data", None, None)
    lossy = rt.cfg.faults_enabled

    def init(p):
        return jax.tree.map(lambda a: a[None], rt.init_state(p))

    init_f = jax.jit(jax.shard_map(init, mesh=mesh, in_specs=(pspec,),
                                   out_specs=cons_spec, check_vma=False))

    def step(xp, xh, st, noise, k):
        st = jax.tree.map(lambda a: a[0], st)
        x_next, st2, m = rt.exchange(xp, xh, st, k, jax.random.PRNGKey(3),
                                     noise=noise[0])
        delivered = (m["wire_bytes_delivered"] if lossy else jnp.zeros(()))
        return (x_next, jax.tree.map(lambda a: a[None], st2),
                delivered[None])

    step_f = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(pspec, pspec, cons_spec, noise_spec, P()),
        out_specs=(pspec, cons_spec, P("data")), check_vma=False))
    return init_f, step_f


def loss_sweep_section(mesh, ctx) -> tuple[dict, bool]:
    """Packet-loss sweep: directed-ring push-sum ADC gossip under link
    loss (smollm-135m, packed path).

    Per rate in ``LOSS_SWEEP`` (plus the lossless ``link_loss=None``
    reference), a ``LOSS_GOSSIP_STEPS`` pure-gossip run from distinct
    per-device inits.  CI gates:

      * every rate still contracts the consensus error (stale ``x_tilde``
        reuse degrades but must not break mixing),
      * rate 0.0 is bit-identical to the lossless trace (the loss
        machinery at zero rate is a no-op, not a perturbation),
      * the push-sum weight stays exactly 1.0 on the homogeneous ring,
      * the delivered-bytes total matches the :class:`~repro.core.faults.
        LossModel` host oracle EXACTLY (bytes accounting excludes dropped
        payloads), and is strictly below the shipped total at 20% loss.
    """
    from repro.core import faults
    arch = "smollm-135m"
    ok = True
    key = jax.random.PRNGKey(hash(arch) % 2**31)
    local = local_leaf_tree(arch, key)
    layout = wire.WireLayout.for_tree(local)
    leaves, treedef = jax.tree_util.tree_flatten(local)
    ks = jax.random.split(jax.random.fold_in(key, 2), len(leaves))
    x0 = jax.tree_util.tree_unflatten(treedef, [
        (jax.random.normal(k2, (N_DEVICES, *a.shape), jnp.float32) * 0.05)
        .astype(a.dtype)
        for k2, a in zip(ks, leaves)])
    xt0 = np.stack([np.asarray(layout.pack(
        jax.tree.map(lambda a, d=d: a[d], x0))) for d in range(N_DEVICES)])
    out = {"rates": [r for r in LOSS_SWEEP], "seed": LOSS_SEED,
           "gossip_steps": LOSS_GOSSIP_STEPS, "runs": {}}
    print(f"packet-loss sweep ({arch}, directed-ring push-sum, "
          f"{LOSS_GOSSIP_STEPS} gossip steps):", flush=True)
    x_ref = None
    for rate in (None,) + LOSS_SWEEP:
        name = "lossless" if rate is None else f"loss_{rate:g}"
        rt = ConsensusRuntime(
            ConsensusConfig(algorithm="adc_dgd", quant_mode="adaptive",
                            topology="directed-ring", link_loss=rate,
                            loss_seed=LOSS_SEED), ctx)
        noise = _codec_noise(rt, layout)
        init_f, step_f = _build_loss_step(rt, mesh, x0)
        st = init_f(x0)
        # distinct inits: rebuild m_agg from the actual directed in-weights
        # (the epoch-boundary resync correction, directed form)
        w_fwd, w_bwd = rt.cfg.in_weights
        m0 = (w_fwd * np.roll(xt0, 1, axis=0)
              + w_bwd * np.roll(xt0, -1, axis=0))
        st = dict(st, m_agg=jnp.asarray(m0))
        x = x0
        r = {"link_loss": 0.0 if rate is None else rate,
             "machinery": rate is not None,
             "consensus_err_start": _consensus_err(x)}
        delivered = 0.0
        for k2 in range(1, LOSS_GOSSIP_STEPS + 1):
            x, st, d = step_f(x, x, st, noise, jnp.asarray(k2, jnp.int32))
            delivered += float(np.sum(np.asarray(d)))
        r["consensus_err_end"] = _consensus_err(x)
        # one accounting for shipped AND the delivered oracle — the same
        # WireAccounting the runtime's traced metrics are derived from
        acct = telemetry.WireAccounting.for_plan(
            rt.wire_plan_for(layout), push_sum=True)
        shipped = LOSS_GOSSIP_STEPS * N_DEVICES * acct.shipped_payload
        r["shipped_bytes"] = float(shipped)
        ps_dev = float(np.max(np.abs(np.asarray(st["ps_w"]) - 1.0)))
        if ps_dev != 0.0:
            print(f"FAIL[loss]: {name} push-sum weight drifted off 1.0 "
                  f"by {ps_dev:g} on the homogeneous ring")
            ok = False
        if not r["consensus_err_end"] < r["consensus_err_start"]:
            print(f"FAIL[loss]: {name} gossip did not contract consensus "
                  f"error ({r['consensus_err_start']:.3e} -> "
                  f"{r['consensus_err_end']:.3e})")
            ok = False
        if rate is None:
            x_ref = x
        else:
            r["delivered_bytes"] = delivered
            mask = faults.LossModel(rate=rate, seed=LOSS_SEED) \
                .keep_mask_host(N_DEVICES, range(1, LOSS_GOSSIP_STEPS + 1))
            oracle = acct.delivered_bytes(float(mask.sum()))
            r["delivered_bytes_oracle"] = oracle
            if delivered != oracle:
                print(f"FAIL[loss]: {name} delivered-bytes accounting "
                      f"{delivered:g} != host oracle {oracle:g}")
                ok = False
        if rate == 0.0:
            diff = max(float(np.max(np.abs(
                np.asarray(a, np.float64) - np.asarray(b, np.float64))))
                for a, b in zip(jax.tree_util.tree_leaves(x),
                                jax.tree_util.tree_leaves(x_ref)))
            r["vs_lossless_max_diff"] = diff
            if diff != 0.0:
                print(f"FAIL[loss]: loss machinery at rate 0.0 is not "
                      f"bit-identical to the lossless path (diff {diff:g})")
                ok = False
        print(f"  {name}: err {r['consensus_err_start']:.3e} -> "
              f"{r['consensus_err_end']:.3e}"
              + (f"   delivered {delivered / 1e6:.2f}/"
                 f"{shipped / 1e6:.2f} MB" if rate is not None else ""),
              flush=True)
        out["runs"][name] = r
    lossy02 = out["runs"]["loss_0.2"]
    if not lossy02["delivered_bytes"] < lossy02["shipped_bytes"]:
        print("FAIL[loss]: 20% loss delivered bytes not below shipped "
              "(drops are not being excluded from accounting)")
        ok = False
    return out, ok


def _build_churn_step(rt: ConsensusRuntime, mesh, tree):
    """:func:`build_step` variant for the symmetric-ring packed transport
    under elastic membership: no push-sum state, and the per-device
    ``wire_bytes_delivered`` metric is surfaced only when a loss model is
    in the trace (zero otherwise, keeping the signature uniform)."""
    pspec = jax.tree.map(lambda _: P("data"), tree)
    cons_spec = {"x_tilde": P("data", None, None),
                 "m_agg": P("data", None, None)}
    noise_spec = P("data", None, None)
    lossy = rt.cfg.faults_enabled

    def init(p):
        return jax.tree.map(lambda a: a[None], rt.init_state(p))

    init_f = jax.jit(jax.shard_map(init, mesh=mesh, in_specs=(pspec,),
                                   out_specs=cons_spec, check_vma=False))

    def step(xp, xh, st, noise, k):
        st = jax.tree.map(lambda a: a[0], st)
        x_next, st2, m = rt.exchange(xp, xh, st, k, jax.random.PRNGKey(3),
                                     noise=noise[0])
        delivered = (m["wire_bytes_delivered"] if lossy else jnp.zeros(()))
        return (x_next, jax.tree.map(lambda a: a[None], st2),
                delivered[None])

    step_f = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(pspec, pspec, cons_spec, noise_spec, P()),
        out_specs=(pspec, cons_spec, P("data")), check_vma=False))
    return init_f, step_f


def churn_sweep_section(mesh, ctx) -> tuple[dict, bool]:
    """Elastic-membership sweep: symmetric-ring packed ADC gossip through
    the CHURN_MASKS depart/rejoin scenario (smollm-135m).

    Four runs from the same distinct per-device inits: a static-membership
    reference, an all-active single-mask run (membership machinery traced
    but inert), the churn scenario, and the churn scenario under
    Gilbert-Elliott burst loss.  CI gates:

      * the all-active mask is BIT-IDENTICAL to membership=None (the
        activity mask at full membership is a no-op, not a perturbation),
      * the churn run contracts after the rejoin and lands within
        CHURN_RECOVERY_FACTOR of the static end-point inside
        CHURN_RECOVERY_EPOCHS epochs (routing around the hole and the
        boundary resync must not wedge mixing),
      * the burst-loss churn run still contracts end-to-end (lossy-churn
        contraction: stale x_tilde reuse + a frozen node together must
        not break the gossip), and its delivered bytes stay strictly
        below the full-membership shipped total.
    """
    arch = "smollm-135m"
    ok = True
    key = jax.random.PRNGKey(hash(arch) % 2**31)
    local = local_leaf_tree(arch, key)
    layout = wire.WireLayout.for_tree(local)
    leaves, treedef = jax.tree_util.tree_flatten(local)
    ks = jax.random.split(jax.random.fold_in(key, 2), len(leaves))
    x0 = jax.tree_util.tree_unflatten(treedef, [
        (jax.random.normal(k2, (N_DEVICES, *a.shape), jnp.float32) * 0.05)
        .astype(a.dtype)
        for k2, a in zip(ks, leaves)])
    xt0 = np.stack([np.asarray(layout.pack(
        jax.tree.map(lambda a, d=d: a[d], x0))) for d in range(N_DEVICES)])
    rejoin_step = CHURN_PERIOD * (len(CHURN_MASKS) - 1)
    out = {"masks": [list(m) for m in CHURN_MASKS],
           "schedule_period": CHURN_PERIOD,
           "gossip_steps": CHURN_GOSSIP_STEPS,
           "burst_model": CHURN_BURST, "seed": LOSS_SEED, "runs": {}}
    print(f"churn sweep ({arch}, symmetric-ring packed, "
          f"{CHURN_GOSSIP_STEPS} gossip steps, hole at epoch 1):",
          flush=True)
    x_ref = None
    variants = {
        "static": {},
        "all_active": {"membership": (CHURN_MASKS[0],)},
        "churn": {"membership": CHURN_MASKS,
                  "schedule_period": CHURN_PERIOD},
        "churn_burst": {"membership": CHURN_MASKS,
                        "schedule_period": CHURN_PERIOD,
                        "link_loss_model": CHURN_BURST,
                        "loss_seed": LOSS_SEED},
    }
    for name, extra in variants.items():
        rt = ConsensusRuntime(
            ConsensusConfig(algorithm="adc_dgd", quant_mode="adaptive",
                            **extra), ctx)
        noise = _codec_noise(rt, layout)
        init_f, step_f = _build_churn_step(rt, mesh, x0)
        st = init_f(x0)
        # distinct inits: rebuild m_agg from the actual symmetric
        # in-weights (the epoch-boundary resync correction)
        w_up, w_dn = rt.cfg.in_weights
        m0 = (w_up * np.roll(xt0, 1, axis=0)
              + w_dn * np.roll(xt0, -1, axis=0))
        st = dict(st, m_agg=jnp.asarray(m0))
        x = x0
        errs = [_consensus_err(x)]
        delivered = 0.0
        for k2 in range(1, CHURN_GOSSIP_STEPS + 1):
            x, st, d = step_f(x, x, st, noise, jnp.asarray(k2, jnp.int32))
            delivered += float(np.sum(np.asarray(d)))
            errs.append(_consensus_err(x))
        r = {"consensus_err_start": errs[0],
             "consensus_err_at_rejoin": errs[rejoin_step],
             "consensus_err_end": errs[-1],
             "consensus_err_per_step": errs}
        if name == "static":
            x_ref = x
        if name == "all_active":
            diff = max(float(np.max(np.abs(
                np.asarray(a, np.float64) - np.asarray(b, np.float64))))
                for a, b in zip(jax.tree_util.tree_leaves(x),
                                jax.tree_util.tree_leaves(x_ref)))
            r["vs_static_max_diff"] = diff
            if diff != 0.0:
                print("FAIL[churn]: all-active membership mask is not "
                      "bit-identical to membership=None "
                      f"(diff {diff:g})")
                ok = False
        if name == "churn":
            static_end = max(
                out["runs"]["static"]["consensus_err_end"],
                CHURN_NOISE_FLOOR * r["consensus_err_start"])
            r["vs_static_end_ratio"] = r["consensus_err_end"] / static_end
            recovered = (
                r["consensus_err_end"]
                < CHURN_RECOVERY_TOL * r["consensus_err_start"]
                and r["consensus_err_end"]
                < CHURN_RECOVERY_FACTOR * static_end
                and r["consensus_err_end"] < r["consensus_err_at_rejoin"])
            r["recovered_within_epochs"] = CHURN_RECOVERY_EPOCHS
            if not recovered:
                print(f"FAIL[churn]: churn run did not recover within "
                      f"{CHURN_RECOVERY_EPOCHS} epochs of the rejoin "
                      f"(err {r['consensus_err_start']:.3e} -> rejoin "
                      f"{r['consensus_err_at_rejoin']:.3e} -> end "
                      f"{r['consensus_err_end']:.3e}, static end "
                      f"{static_end:.3e})")
                ok = False
        if name == "churn_burst":
            r["delivered_bytes"] = delivered
            acct = telemetry.WireAccounting.for_plan(
                rt.wire_plan_for(layout), push_sum=False)
            shipped = CHURN_GOSSIP_STEPS * N_DEVICES * acct.shipped_payload
            r["shipped_bytes_full_membership"] = float(shipped)
            if not r["consensus_err_end"] < r["consensus_err_start"]:
                print("FAIL[churn]: burst-loss churn run did not contract "
                      f"consensus error ({r['consensus_err_start']:.3e} "
                      f"-> {r['consensus_err_end']:.3e})")
                ok = False
            if not delivered < shipped:
                print("FAIL[churn]: burst-loss churn delivered bytes not "
                      "below the full-membership shipped total (drops/"
                      "inactive nodes are not being excluded)")
                ok = False
        print(f"  {name}: err {r['consensus_err_start']:.3e} -> "
              f"{r['consensus_err_end']:.3e}"
              + (f"   delivered {delivered / 1e6:.2f} MB"
                 if rt.cfg.faults_enabled else ""), flush=True)
        out["runs"][name] = r
    return out, ok


def _synth_compute(z, iters: int):
    """The fwd/bwd stand-in: a matmul chain with an RMS renormalization
    per iteration (keeps magnitudes bounded without letting XLA collapse
    the loop)."""
    def body(_, z):
        z = z @ z
        return z / (jnp.sqrt(jnp.mean(z * z)) + 1e-6)
    return jax.lax.fori_loop(0, iters, body, z)


def _overlap_tree(key) -> dict:
    """A small multi-leaf mixed-dtype tree (~0.2 M params): big enough for
    a real packed wire, small enough that the calibrated compute load —
    not the exchange — dominates the benchmark's wall clock."""
    ks = jax.random.split(key, 4)
    return {
        "w": jax.random.normal(ks[0], (64, 512), jnp.float32),
        "b": jax.random.normal(ks[1], (1024,), jnp.bfloat16),
        "deep": {"m": jax.random.normal(ks[2], (96, 512), jnp.float32)},
        "tail": jax.random.normal(ks[3], (3, 137), jnp.float32),
    }


def _median_steps(fn, args) -> dict:
    """compile + warmup + median-of-repeats for an arbitrary jit'd step
    (same protocol as :func:`time_path`, signature-agnostic)."""
    out = fn(*args)
    jax.tree.map(lambda a: a.block_until_ready(), out)
    for _ in range(WARMUP_STEPS):
        out = fn(*args)
    jax.tree.map(lambda a: a.block_until_ready(), out)
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        for _ in range(STEPS_TIMED):
            out = fn(*args)
        jax.tree.map(lambda a: a.block_until_ready(), out)
        times.append((time.perf_counter() - t0) / STEPS_TIMED)
    sec = float(np.median(times))
    return {"seconds_per_step": sec, "steps_per_s": 1.0 / sec,
            "timing_spread": float((np.max(times) - np.min(times)) / sec),
            "timing_samples": [float(t) for t in times]}


def _build_overlap_step(rt: ConsensusRuntime, mesh, tree, iters: int):
    """Fused compute + exchange step.  The synthetic load reads only the
    carried ``z`` buffer and the exchange reads only (x, xh, state), so
    the two are data-independent and the scheduler is free to overlap the
    ring collectives with the matmul chain — for the async transport the
    in-flight payload additionally depends on nothing produced this step."""
    pspec = jax.tree.map(lambda _: P("data"), tree)
    cons_spec = {"x_tilde": P("data", None, None),
                 "m_agg": P("data", None, None)}
    if rt.cfg.wire_packing == "async":
        for fk in wire.INFLIGHT_KEYS:
            cons_spec[fk] = P("data", None)
    noise_spec = P("data", None, None)
    z_spec = P("data", None, None)

    def init(p):
        return jax.tree.map(lambda a: a[None], rt.init_state(p))

    init_f = jax.jit(jax.shard_map(init, mesh=mesh, in_specs=(pspec,),
                                   out_specs=cons_spec, check_vma=False))

    def step(xp, xh, st, noise, z, k):
        st = jax.tree.map(lambda a: a[0], st)
        z2 = _synth_compute(z[0], iters)
        x_next, st2, _ = rt.exchange(xp, xh, st, k, jax.random.PRNGKey(3),
                                     noise=noise[0])
        return x_next, jax.tree.map(lambda a: a[None], st2), z2[None]

    step_f = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(pspec, pspec, cons_spec, noise_spec, z_spec, P()),
        out_specs=(pspec, cons_spec, z_spec), check_vma=False))
    return init_f, step_f


def overlap_section(mesh, ctx) -> tuple[dict, bool]:
    """Async-overlap benchmark (ISSUE 7 tentpole): consensus overhead
    fraction under a compute-dominated synthetic load.

    Columns: compute-only baseline, then eager packed / pipelined
    (OVERLAP_PIPE_CHUNKS) / async one-step-stale, each fused with the SAME
    synthetic load.  ``consensus_overhead_frac = (t_step - t_compute) /
    t_step`` is the exchange cost NOT hidden behind compute.  CI gates:

      * async consensus_overhead_frac < OVERLAP_OVERHEAD_BUDGET (15%),
      * async >= pipelined on steps/s within the variance-aware timing
        gate (the async transport must not lose to the chunked overlap
        it replaces),
      * the fused async program still traces EXACTLY 2 ring ppermutes
        (deterministic structural check — the overlap is scheduling, not
        extra collectives).
    """
    ok = True
    key = jax.random.PRNGKey(23)
    tree = _overlap_tree(key)
    local = jax.tree.map(lambda a: a, tree)
    layout = wire.WireLayout.for_tree(local)
    xp = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (N_DEVICES, *a.shape)), tree)
    xh = jax.tree.map(
        lambda a: (a.astype(jnp.float32) + 1e-3).astype(a.dtype), xp)
    z0 = jax.random.normal(jax.random.fold_in(key, 1),
                           (N_DEVICES, OVERLAP_MM_DIM, OVERLAP_MM_DIM),
                           jnp.float32) * 0.05

    # -- calibrate: bare packed exchange, then per-iteration compute cost
    rt_cal = ConsensusRuntime(
        ConsensusConfig(algorithm="adc_dgd", quant_mode="adaptive"), ctx)
    noise = _codec_noise(rt_cal, layout, seed=5)
    init_f, step_f = build_step(rt_cal, mesh, xp)
    st = init_f(xp)
    k2 = jnp.asarray(2, jnp.int32)
    t_exch = _median_steps(step_f, (xp, xh, st, noise, k2))
    z_spec = P("data", None, None)
    compute_f = {}
    for iters in {OVERLAP_CAL_ITERS}:
        compute_f[iters] = jax.jit(jax.shard_map(
            lambda z, it=iters: _synth_compute(z[0], it)[None], mesh=mesh,
            in_specs=(z_spec,), out_specs=z_spec, check_vma=False))
    t_cal = _median_steps(compute_f[OVERLAP_CAL_ITERS], (z0,))
    per_iter = t_cal["seconds_per_step"] / OVERLAP_CAL_ITERS
    iters = int(np.clip(round(
        OVERLAP_TARGET_RATIO * t_exch["seconds_per_step"] / per_iter),
        OVERLAP_MIN_ITERS, OVERLAP_MAX_ITERS))
    compute_f[iters] = jax.jit(jax.shard_map(
        lambda z: _synth_compute(z[0], iters)[None], mesh=mesh,
        in_specs=(z_spec,), out_specs=z_spec, check_vma=False))
    t_comp = _median_steps(compute_f[iters], (z0,))
    out = {"tree_params": layout.n_elements, "mm_dim": OVERLAP_MM_DIM,
           "synth_iters": iters, "target_ratio": OVERLAP_TARGET_RATIO,
           "overhead_budget": OVERLAP_OVERHEAD_BUDGET,
           "pipeline_chunks": OVERLAP_PIPE_CHUNKS,
           "exchange_only": t_exch, "compute_only": t_comp, "modes": {}}
    print(f"overlap bench: {layout.n_elements:,} params, bare exchange "
          f"{t_exch['seconds_per_step'] * 1e3:.1f} ms, compute load "
          f"{iters} x {OVERLAP_MM_DIM}^2 matmuls = "
          f"{t_comp['seconds_per_step'] * 1e3:.1f} ms/step", flush=True)

    modes = (
        ("packed", {"wire_packing": "packed"}),
        ("pipelined", {"wire_packing": "pipelined",
                       "pipeline_chunks": OVERLAP_PIPE_CHUNKS}),
        ("async", {"wire_packing": "async", "staleness": 1}),
    )
    for name, kw in modes:
        rt = ConsensusRuntime(
            ConsensusConfig(algorithm="adc_dgd", quant_mode="adaptive",
                            **kw), ctx)
        noise_m = _codec_noise(rt, layout, seed=5)
        init_m, step_m = _build_overlap_step(rt, mesh, xp, iters)
        st_m = init_m(xp)
        jaxpr = jax.make_jaxpr(step_m)(xp, xh, st_m, noise_m, z0, k2)
        r = _median_steps(step_m, (xp, xh, st_m, noise_m, z0, k2))
        r["collectives_per_step"] = count_eqns(jaxpr, "ppermute")
        r["consensus_overhead_frac"] = max(
            0.0, (r["seconds_per_step"] - t_comp["seconds_per_step"])
            / r["seconds_per_step"])
        print(f"  {name}: {r['steps_per_s']:8.2f} steps/s   overhead "
              f"{r['consensus_overhead_frac']:.1%}   "
              f"{r['collectives_per_step']} ppermutes/step   "
              f"(spread {r['timing_spread']:.0%})", flush=True)
        out["modes"][name] = r

    a, p = out["modes"]["async"], out["modes"]["pipelined"]
    if a["collectives_per_step"] != 2:
        print(f"FAIL[overlap]: fused async step traced "
              f"{a['collectives_per_step']} ppermutes (want 2)")
        ok = False
    if a["consensus_overhead_frac"] >= OVERLAP_OVERHEAD_BUDGET:
        print(f"FAIL[overlap]: async consensus overhead "
              f"{a['consensus_overhead_frac']:.1%} exceeds the "
              f"{OVERLAP_OVERHEAD_BUDGET:.0%} budget under the "
              "compute-dominated load")
        ok = False
    gate = _timing_gate(a, p)
    out["async_vs_pipelined"] = a["steps_per_s"] / p["steps_per_s"]
    out["async_gate"] = gate
    if out["async_vs_pipelined"] < gate:
        print(f"FAIL[overlap]: async {out['async_vs_pipelined']:.2f}x vs "
              f"pipelined, below the variance-aware {gate:.2f} gate")
        ok = False
    return out, ok


def hierarchy_sweep_section(mesh, ctx) -> tuple[dict, bool]:
    """Two-level hierarchical consensus vs the flat compressed ring
    (smollm-135m, packed path; DESIGN.md §14).

    Both modes run the same harness from the same POD-IDENTICAL inits
    (every pod member holds the same copy — the shared-x0 contract that
    makes the broadcast-back implicit; pods differ).  Per mode: steps/s,
    traced ppermutes, the per-level byte split, and a
    ``HIER_GOSSIP_STEPS`` pure-gossip consensus-error trajectory.  The
    inter-pod bytes column counts one logical compressed payload per
    DISTINCT pod per step; under hierarchy the intra-pod fp32 all-reduce
    is accounted separately (``inner_bytes_per_step``).  Gates: see the
    ``HIER_*`` constants above.
    """
    arch = "smollm-135m"
    ok = True
    m = N_DEVICES // HIER_PODS
    key = jax.random.PRNGKey(hash(arch) % 2**31)
    local = local_leaf_tree(arch, key)
    layout = wire.WireLayout.for_tree(local)
    xp = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (N_DEVICES, *a.shape)), local)
    xh = jax.tree.map(
        lambda a: (a.astype(jnp.float32) + 1e-3).astype(a.dtype), xp)
    # pod-identical distinct inits: pods differ, members within a pod are
    # bitwise equal — the contract under which pod members stay replicas
    # by induction and the inner broadcast-back is free
    leaves, treedef = jax.tree_util.tree_flatten(local)
    ks = jax.random.split(jax.random.fold_in(key, 3), len(leaves))
    x0 = jax.tree_util.tree_unflatten(treedef, [
        jnp.repeat(
            (jax.random.normal(k2, (HIER_PODS, *a.shape), jnp.float32)
             * 0.05).astype(a.dtype), m, axis=0)
        for k2, a in zip(ks, leaves)])
    xt0 = np.stack([np.asarray(layout.pack(
        jax.tree.map(lambda a, d=d: a[d], x0))) for d in range(N_DEVICES)])
    out = {"pods": HIER_PODS, "pod_size": m,
           "gossip_steps": HIER_GOSSIP_STEPS, "modes": {}}
    print(f"hierarchy sweep ({arch}, packed, {HIER_PODS} pods x {m} "
          f"nodes, {HIER_GOSSIP_STEPS} gossip steps):", flush=True)
    for name, extra, shift in (("flat", {}, 1),
                               ("hier", {"hierarchy": HIER_PODS}, m)):
        rt = ConsensusRuntime(
            ConsensusConfig(algorithm="adc_dgd", quant_mode="adaptive",
                            **extra), ctx)
        if shift == 1:
            noise = _codec_noise(rt, layout, seed=7)
        else:
            # the runtime's own PRNG is pod-granular under hierarchy;
            # injected noise must match or pod members would diverge
            pod_noise = np.random.default_rng(7).random(
                (HIER_PODS, layout.n_rows, rt.noise_cols_for(layout)),
                np.float32)
            noise = jnp.asarray(np.repeat(pod_noise, m, axis=0))
        built = build_step(rt, mesh, xp)
        r = time_path(rt, mesh, xp, xh, noise,
                      f"{arch}/hierarchy[{name}]", built=built)
        acct = rt.wire_accounting(layout.n_elements, layout=layout)
        pods = N_DEVICES // rt.pod_size
        r["inter_pod_bytes_per_step"] = pods * acct.shipped_payload
        r["inner_bytes_per_step"] = N_DEVICES * acct.inner_bytes
        # pure gossip from the pod-identical inits; m_agg rebuilt from
        # the actual (pod-)ring neighbors — the epoch-resync correction,
        # with the permutation stepping in units of pod_size
        init_f, step_f = built
        st = init_f(x0)
        w_side = rt.cfg.side_weight
        m0 = w_side * (np.roll(xt0, shift, axis=0)
                       + np.roll(xt0, -shift, axis=0))
        st = {"x_tilde": st["x_tilde"], "m_agg": jnp.asarray(m0)}
        x = x0
        r["consensus_err_start"] = _consensus_err(x)
        for k2 in range(1, HIER_GOSSIP_STEPS + 1):
            x, st = step_f(x, x, st, noise, jnp.asarray(k2, jnp.int32))
        r["consensus_err_end"] = _consensus_err(x)
        print(f"    gossip err {r['consensus_err_start']:.3e} -> "
              f"{r['consensus_err_end']:.3e}   inter-pod "
              f"{r['inter_pod_bytes_per_step'] / 1e6:.2f} MB/step   "
              f"intra-pod {r['inner_bytes_per_step'] / 1e6:.2f} MB/step",
              flush=True)
        out["modes"][name] = r
    f, h = out["modes"]["flat"], out["modes"]["hier"]
    ratio = (f["inter_pod_bytes_per_step"]
             / max(h["inter_pod_bytes_per_step"], 1e-30))
    out["inter_pod_ratio"] = ratio
    out["expected_ratio"] = float(m)
    print(f"  inter-pod bytes: flat {f['inter_pod_bytes_per_step'] / 1e6:.2f}"
          f" MB/step -> hier {h['inter_pod_bytes_per_step'] / 1e6:.2f} "
          f"MB/step ({ratio:.2f}x, pod_size {m})", flush=True)
    if ratio < HIER_BYTES_RATIO_TOL * m:
        print(f"FAIL[hier]: inter-pod bytes shrank only {ratio:.2f}x vs "
              f"flat (want >= {HIER_BYTES_RATIO_TOL:.1f} x pod_size "
              f"= {HIER_BYTES_RATIO_TOL * m:.2f}x)")
        ok = False
    if h["collectives_per_step"] != 2:
        print(f"FAIL[hier]: hierarchical step traced "
              f"{h['collectives_per_step']} ppermutes (want 2 — the inner "
              "level must be a psum, not extra ring hops)")
        ok = False
    for name in out["modes"]:
        r = out["modes"][name]
        if not r["consensus_err_end"] < r["consensus_err_start"]:
            print(f"FAIL[hier]: {name} gossip did not contract consensus "
                  f"error ({r['consensus_err_start']:.3e} -> "
                  f"{r['consensus_err_end']:.3e})")
            ok = False
    if h["consensus_err_end"] > f["consensus_err_end"]:
        print(f"FAIL[hier]: hierarchical gossip ended WORSE than flat "
              f"({h['consensus_err_end']:.3e} vs "
              f"{f['consensus_err_end']:.3e}) — the byte saving is not at "
              "matched consensus error")
        ok = False
    return out, ok


def _git_sha() -> str | None:
    import subprocess
    try:
        r = subprocess.run(["git", "rev-parse", "HEAD"], cwd=REPO,
                           capture_output=True, text=True, timeout=10)
        return r.stdout.strip() or None
    except Exception:
        return None


def _config_hash(payload: dict) -> str:
    """Digest of the benchmark *configuration* (constants, sweeps, plans —
    everything but the measurements), so a series reader can tell apart
    'same config re-measured' from 'the benchmark itself changed'."""
    import hashlib
    cfg = {k: v for k, v in payload.items()
           if k not in ("archs", "codecs", "choco_equal_bytes",
                        "loss_sweep", "churn_sweep", "overlap",
                        "hierarchy_sweep")}
    return hashlib.sha256(
        json.dumps(cfg, sort_keys=True, default=float).encode()).hexdigest()[:12]


def append_run(path: str, payload: dict, ok: bool) -> dict:
    """Append-mode artifact series: ``BENCH_consensus_step.json`` holds
    ``{"schema": "bench-series/v1", "runs": [...]}`` with every prior run
    retained; each run is stamped with the git sha and a config hash.  A
    pre-series flat payload found at ``path`` is preserved as a legacy
    first entry.  Cross-run comparisons should use each run's
    median-of-repeats timings with the variance-aware gate
    (:func:`_timing_gate`) — single-sample deltas on the shared CI host
    are noise."""
    series = {"schema": "bench-series/v1", "runs": []}
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = json.load(f)
        except (OSError, json.JSONDecodeError):
            prev = None
        if isinstance(prev, dict) and isinstance(prev.get("runs"), list):
            series["runs"] = prev["runs"]
        elif isinstance(prev, dict) and prev:
            series["runs"] = [{"legacy": True, "git_sha": None,
                               "config_hash": None, "gates_ok": None,
                               "payload": prev}]
    series["runs"].append({
        "git_sha": _git_sha(),
        "config_hash": _config_hash(payload),
        "gates_ok": ok,
        "payload": payload,
    })
    with open(path, "w") as f:
        json.dump(series, f, indent=1, default=float)
    return series


def main() -> int:
    if jax.default_backend() != "cpu" or jax.device_count() < N_DEVICES:
        print(f"FAIL: need >= {N_DEVICES} CPU devices, have "
              f"{jax.device_count()} {jax.default_backend()} device(s) (set "
              "JAX_PLATFORMS=cpu and "
              "XLA_FLAGS=--xla_force_host_platform_device_count=4)")
        return 1
    mesh = Mesh(np.array(jax.devices()[:N_DEVICES]), ("data",))
    ctx = ParallelContext(tp=1, data_size=N_DEVICES, n_nodes=N_DEVICES,
                          in_shard_map=True)
    out, ok = {}, True
    for arch in ARCHS:
        key = jax.random.PRNGKey(hash(arch) % 2**31)
        local = local_leaf_tree(arch, key)
        layout = wire.WireLayout.for_tree(local)
        # leading device dim: every node gets its own (identical-shape) shard
        xp = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (N_DEVICES, *a.shape)), local)
        xh = jax.tree.map(
            lambda a: (a.astype(jnp.float32) + 1e-3).astype(a.dtype), xp)
        print(f"{arch}: {layout.n_leaves} leaves, "
              f"{layout.n_elements:,} local params, {layout.n_rows} rows",
              flush=True)
        noise = jnp.asarray(
            np.random.default_rng(0).random(
                (N_DEVICES, layout.n_rows, layout.block), np.float32))
        res = {"leaves": layout.n_leaves, "local_params": layout.n_elements,
               "rows": layout.n_rows}
        for mode in ("per_leaf", "packed"):
            rt = ConsensusRuntime(
                ConsensusConfig(algorithm="adc_dgd", quant_mode="adaptive",
                                wire_packing=mode), ctx)
            res[mode] = time_path(rt, mesh, xp, xh, noise, f"{arch}/{mode}")
            res[mode]["wire_bytes_per_step"] = rt.wire_bytes_per_step(
                layout.n_elements, layout=layout)
        # chunked double-buffered pipeline: sweep the chunk count, keep the
        # best (the transfer-hiding vs launch-overhead tradeoff is swept,
        # not guessed — EXPERIMENTS.md §Perf)
        sweep, best = {}, None
        for chunks in CHUNK_SWEEP:
            rt = ConsensusRuntime(
                ConsensusConfig(algorithm="adc_dgd", quant_mode="adaptive",
                                wire_packing="pipelined",
                                pipeline_chunks=chunks), ctx)
            r = time_path(rt, mesh, xp, xh, noise,
                          f"{arch}/pipelined[{chunks}]")
            r["wire_bytes_per_step"] = rt.wire_bytes_per_step(
                layout.n_elements, layout=layout)
            r["pipeline_chunks"] = chunks
            sweep[str(chunks)] = r
            if best is None or r["steps_per_s"] > best["steps_per_s"]:
                best = r
        res["pipelined"] = dict(best, sweep=sweep,
                                best_chunks=best["pipeline_chunks"])
        res["speedup"] = (res["packed"]["steps_per_s"]
                         / res["per_leaf"]["steps_per_s"])
        res["pipelined_vs_packed"] = (best["steps_per_s"]
                                      / res["packed"]["steps_per_s"])
        # the unbiased chunking win: best vs the sweep's OWN chunks=1 point.
        # chunks=1 traces the identical program to packed, but the packed
        # column is timed earlier in a colder process, so best/packed
        # overstates the overlap gain by whatever warm-process drift
        # accumulated between the two measurements; best/sweep[1] compares
        # within the sweep and isolates what chunking itself buys.
        res["overlap_gain"] = (best["steps_per_s"]
                               / sweep["1"]["steps_per_s"])
        print(f"  speedup: {res['speedup']:.2f}x   pipelined(best "
              f"chunks={best['pipeline_chunks']}) vs packed: "
              f"{res['pipelined_vs_packed']:.2f}x   overlap gain vs "
              f"chunks=1: {res['overlap_gain']:.2f}x", flush=True)
        if res["speedup"] < 1.0:
            print(f"FAIL[{arch}]: packed slower than per-leaf reference")
            ok = False
        gate = _timing_gate(res["packed"], best)
        res["pipelined_gate"] = gate
        if res["pipelined_vs_packed"] < gate:
            print(f"FAIL[{arch}]: pipelined best chunk count slower than "
                  f"monolithic packed beyond the variance-aware {gate:.2f} "
                  "noise tolerance")
            ok = False
        if sweep["1"]["collectives_per_step"] != 2:
            # deterministic structural check alongside the noisy timing
            # gate: chunks=1 must trace exactly the monolithic packed wire
            print(f"FAIL[{arch}]: pipelined chunks=1 traced "
                  f"{sweep['1']['collectives_per_step']} collectives "
                  "(want 2 — structure diverged from packed)")
            ok = False
        if res["packed"]["compile_s"] > COMPILE_BUDGET_S:
            compile_s = res["packed"]["compile_s"]
            print(f"FAIL[{arch}]: packed compile {compile_s:.1f}s exceeds "
                  f"the {COMPILE_BUDGET_S:.0f}s budget "
                  "(trace-size regression)")
            ok = False
        out[arch.replace("-", "_").replace(".", "_")] = res
    codecs, codec_ok = codec_section(mesh, ctx)
    ok = ok and codec_ok
    choco_eb, choco_ok = choco_equal_bytes_section()
    ok = ok and choco_ok
    loss_sweep, loss_ok = loss_sweep_section(mesh, ctx)
    ok = ok and loss_ok
    churn_sweep, churn_ok = churn_sweep_section(mesh, ctx)
    ok = ok and churn_ok
    overlap, overlap_ok = overlap_section(mesh, ctx)
    ok = ok and overlap_ok
    hier_sweep, hier_ok = hierarchy_sweep_section(mesh, ctx)
    ok = ok and hier_ok
    payload = {"n_devices": N_DEVICES, "nodes": NODES,
               "prod_mesh": f"{PROD_FSDP}x{PROD_TP}",
               "steps_timed": STEPS_TIMED, "chunk_sweep": list(CHUNK_SWEEP),
               "compile_budget_s": COMPILE_BUDGET_S, "noise_tol": NOISE_TOL,
               "mixed_plan": MIXED_PLAN, "mixed_plan_aggr": MIXED_PLAN_AGGR,
               "mixed_fidelity_tol": MIXED_FIDELITY_TOL,
               "archs": out, "codecs": codecs,
               "choco_equal_bytes": choco_eb, "loss_sweep": loss_sweep,
               "churn_sweep": churn_sweep, "overlap": overlap,
               "hierarchy_sweep": hier_sweep}
    series = append_run(os.path.join(REPO, "BENCH_consensus_step.json"),
                        payload, ok)
    print(f"bench series: {len(series['runs'])} run(s) recorded "
          f"(sha {series['runs'][-1]['git_sha']}, config "
          f"{series['runs'][-1]['config_hash']})", flush=True)
    art = os.path.join(REPO, "benchmarks", "artifacts")
    os.makedirs(art, exist_ok=True)
    # the artifacts/ copy stays the flat LATEST-run payload (the series
    # lives at the repo root; this one is for quick single-run inspection)
    with open(os.path.join(art, "consensus_step_latency.json"), "w") as f:
        json.dump(payload, f, indent=1, default=float)
    if not ok:
        print("FAIL: consensus-step smoke gates violated (see FAIL lines)")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
