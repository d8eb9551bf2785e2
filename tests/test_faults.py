"""Link-loss fault injection (core.faults + the lossy consensus exchange).

Covered contracts:
  * LossModel drop masks are deterministic under a fixed seed (the host
    oracle reproduces itself, differs across seeds, keeps everything at
    rate 0) and the traced ``keep`` agrees with ``keep_mask_host`` exactly
  * the delivered fraction concentrates at ``1 - rate``
  * ``link_loss=0.0`` (machinery in the trace) is bit-identical to
    ``link_loss=None`` (no machinery at all)
  * under heavy loss the packed, per-leaf and pipelined transports stay
    bit-identical (ONE drop decision per direction per step covers every
    pipeline chunk), including the (1,2)-stride schedule's epoch-boundary
    resync, and the push-sum weight stays exactly 1.0
  * same ``loss_seed`` -> bit-identical trajectories; a different seed
    realizes a drop pattern that actually changes the trajectory
  * stale-``x_tilde`` reuse is unbiased: the seed-averaged lossy
    trajectory matches the lossless one within Monte-Carlo error
  * a multi-epoch directed-ring gossip under 30% loss still contracts the
    consensus error by an order of magnitude (the epoch-boundary resync
    repairs the lossy epoch's drift exactly)

Multi-device tests reuse the subprocess harness from tests/test_wire.py
(jax locks the device count at first init; the main pytest process must
keep seeing ONE device).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import faults
from test_wire import run_sub


# ---------------------------------------------------------------------------
# LossModel: host-side determinism + traced/oracle agreement
# ---------------------------------------------------------------------------

def test_loss_model_validates_rate():
    with pytest.raises(ValueError, match="rate"):
        faults.LossModel(rate=1.0)
    with pytest.raises(ValueError, match="rate"):
        faults.LossModel(rate=-0.1)
    # rate 0 is legal and distinct from "no model": machinery on, no drops
    assert faults.LossModel(rate=0.0).expected_delivered_frac() == 1.0


def test_keep_mask_deterministic_and_seeded():
    m1 = faults.LossModel(rate=0.3, seed=4).keep_mask_host(8, range(1, 33))
    m2 = faults.LossModel(rate=0.3, seed=4).keep_mask_host(8, range(1, 33))
    assert m1.shape == (32, 2, 8)
    np.testing.assert_array_equal(m1, m2)
    m3 = faults.LossModel(rate=0.3, seed=5).keep_mask_host(8, range(1, 33))
    assert np.any(m1 != m3)
    # the mask varies along every axis it folds (step, direction, node)
    assert np.any(m1[0] != m1[1])
    assert np.any(m1[:, 0] != m1[:, 1])
    assert np.any(m1[:, :, 0] != m1[:, :, 1])
    assert faults.LossModel(rate=0.0, seed=4).keep_mask_host(
        8, range(1, 9)).all()


def test_traced_keep_matches_host_oracle():
    """The traced drop decision and the host oracle are the SAME PRNG
    chain — what lets tests predict exactly which packets a compiled
    exchange drops."""
    lm = faults.LossModel(rate=0.45, seed=9)
    mask = lm.keep_mask_host(4, range(1, 7))
    keep_j = jax.jit(lm.keep)
    for si, s in enumerate(range(1, 7)):
        for d in (faults.FROM_UPSTREAM, faults.FROM_DOWNSTREAM):
            for v in range(4):
                assert bool(keep_j(jnp.asarray(s, jnp.int32), d, v)) \
                    == mask[si, d, v], (s, d, v)


def test_delivered_fraction_concentrates():
    lm = faults.LossModel(rate=0.2, seed=0)
    mask = lm.keep_mask_host(16, range(1, 201))     # 6400 Bernoulli draws
    assert abs(mask.mean() - lm.expected_delivered_frac()) < 0.02


# ---------------------------------------------------------------------------
# Multi-device: the lossy exchange (subprocess, 4 devices)
# ---------------------------------------------------------------------------

def test_loss_zero_bit_identical_to_lossless():
    """Acceptance: rate 0.0 keeps the loss machinery in the trace (the
    where-masks, the delivered-bytes metric) yet the exchange is
    bit-for-bit the link_loss=None path."""
    body = """
tree = make_tree(jax.random.PRNGKey(0))
kw = dict(algorithm="adc_dgd", quant_mode="fixed", fixed_step0=1e-2,
          topology="directed-ring", wire_packing="packed")
ref = trajectory(kw, tree, steps=5)
l0 = trajectory({**kw, "link_loss": 0.0}, tree, steps=5)
print("RESULT", json.dumps({"diff": max_diff(ref, l0)}))
"""
    r = run_sub(body)
    assert r["diff"] == 0.0


def test_transports_bit_identical_under_loss():
    """Acceptance: one drop decision per (step, direction, receiver)
    covers the whole flat payload, so packed == per-leaf == pipelined
    bit-for-bit under 35% loss — and through the (1,2)-stride schedule's
    epoch-boundary resync at 20% loss, with the push-sum weight pinned at
    exactly 1.0 on the homogeneous ring."""
    body = """
tree = make_tree(jax.random.PRNGKey(1))
out = {}
kw = dict(algorithm="adc_dgd", quant_mode="fixed", fixed_step0=1e-2,
          topology="directed-ring", link_loss=0.35, loss_seed=5)
ref = trajectory({**kw, "wire_packing": "packed"}, tree, steps=5)
out["per_leaf"] = max_diff(
    trajectory({**kw, "wire_packing": "per_leaf"}, tree, steps=5), ref)
out["pipelined4"] = max_diff(
    trajectory({**kw, "wire_packing": "pipelined", "pipeline_chunks": 4},
               tree, steps=5), ref)
skw = {**kw, "ring_strides": (1, 2), "schedule_period": 2, "link_loss": 0.2}
sref = trajectory({**skw, "wire_packing": "packed"}, tree, steps=6)
out["sched_per_leaf"] = max_diff(
    trajectory({**skw, "wire_packing": "per_leaf"}, tree, steps=6), sref)
out["ps_w_dev"] = float(np.max(np.abs(np.asarray(sref[1]["ps_w"]) - 1.0)))
print("RESULT", json.dumps(out))
"""
    r = run_sub(body)
    for k, v in r.items():
        assert v == 0.0, f"{k}: {v}"


def test_drop_seed_determinism_end_to_end():
    """Same loss_seed -> bit-identical trajectories; a different seed
    realizes different drops and the trajectory actually moves."""
    body = """
tree = make_tree(jax.random.PRNGKey(2))
kw = dict(algorithm="adc_dgd", quant_mode="fixed", fixed_step0=1e-2,
          topology="directed-ring", wire_packing="packed", link_loss=0.5)
a = trajectory({**kw, "loss_seed": 3}, tree, steps=4)
b = trajectory({**kw, "loss_seed": 3}, tree, steps=4)
c = trajectory({**kw, "loss_seed": 4}, tree, steps=4)
print("RESULT", json.dumps({"same_seed": max_diff(a, b),
                            "other_seed": max_diff(a, c)}))
"""
    r = run_sub(body)
    assert r["same_seed"] == 0.0
    assert r["other_seed"] > 0.0


def test_stale_reuse_is_exactly_the_missing_differential():
    """Packet-level semantics of stale-x_tilde reuse, pinned two ways.

    Deterministic: after a lossy step, a receiver with full delivery is
    bit-identical to the lossless run, and a receiver that missed a
    packet differs by EXACTLY the in-weighted differential that packet
    carried (the sender's shadow advance xt' - xt) — the drop corrupts
    nothing else.  The step is the second: the wire carries x^k - xt,
    which is zero at k = 1 (the shadows start at x^1), so the first
    step's drops lose nothing.  Monte-Carlo over 16 drop seeds: the mean absolute
    deviation matches the first-order prediction ``rate * (w_fwd |d_up|
    + w_bwd |d_dn|)`` — the stale-reuse error scales with the loss rate
    and the differential magnitude ~ Delta_k, with no constant-order
    corruption term."""
    body = """
from repro.core import faults
key = jax.random.PRNGKey(5)
tree = {"w": jax.random.normal(key, (4, 3, 37), jnp.float32),
        "m": jax.random.normal(jax.random.fold_in(key, 1), (4, 7, 11, 2),
                               jnp.float32)}
local = jax.tree.map(lambda a: a[0], tree)
layout = wire.WireLayout.for_tree(local)
kw = dict(algorithm="adc_dgd", quant_mode="fixed", fixed_step0=1e-2,
          topology="directed-ring", wire_packing="packed")
rt = ConsensusRuntime(ConsensusConfig(**kw), ctx)
w_fwd, w_bwd = rt.cfg.in_weights
RATE = 0.3

def packed(x):
    return np.stack([np.asarray(layout.pack(
        jax.tree.map(lambda a, d=d: a[d], x)), np.float64)
        for d in range(4)])

_, ref_st1 = trajectory(kw, tree, steps=1)
ref_x, ref_st = trajectory(kw, tree, steps=2)
dec = (np.asarray(ref_st["x_tilde"], np.float64)
       - np.asarray(ref_st1["x_tilde"], np.float64))
px_ref = packed(ref_x)
exact = {"full": [], "dropped": []}
seed_means = []
for seed in range(16):
    mask = faults.LossModel(rate=RATE, seed=seed).keep_mask_host(4, [2])[0]
    got_x, _ = trajectory({**kw, "link_loss": RATE, "loss_seed": seed},
                          tree, steps=2)
    px_got = packed(got_x)
    gaps = []
    for v in range(4):
        expected = (w_fwd * dec[(v - 1) % 4] * (0.0 if mask[0, v] else 1.0)
                    + w_bwd * dec[(v + 1) % 4] * (0.0 if mask[1, v] else 1.0))
        gap = px_ref[v] - px_got[v]
        gaps.append(float(np.abs(gap).mean()))
        rec = {"err": float(np.max(np.abs(gap - expected))),
               "mag": float(np.max(np.abs(expected))),
               "bitgap": float(np.max(np.abs(gap)))}
        (exact["full"] if mask[:, v].all() else exact["dropped"]).append(rec)
    seed_means.append(float(np.mean(gaps)))
pred = RATE * (w_fwd + w_bwd) * float(np.abs(dec).mean())
print("RESULT", json.dumps({
    "n_full": len(exact["full"]), "n_dropped": len(exact["dropped"]),
    "full_bitgap": max((r["bitgap"] for r in exact["full"]), default=-1.0),
    "dropped_err": max((r["err"] for r in exact["dropped"]), default=-1.0),
    "dropped_mag": min((r["mag"] for r in exact["dropped"]), default=-1.0),
    "mc_ratio": float(np.mean(seed_means) / pred)}))
"""
    r = run_sub(body)
    assert r["n_full"] >= 1 and r["n_dropped"] >= 1, r
    # full delivery -> the lossy trace is bit-identical for that receiver
    assert r["full_bitgap"] == 0.0, r
    # a drop's entire effect is the missing in-weighted differential
    assert r["dropped_mag"] > 1e-4, r         # the differential is substantial
    assert r["dropped_err"] < 1e-5, r         # ...and explains the gap
    # loss-rate scaling of the stale-reuse error (MC over 128 Bernoullis)
    assert 0.75 < r["mc_ratio"] < 1.25, r


def test_lossy_epoch_resync_recovers_consensus():
    """A directed-ring pure-gossip run under 30% loss across three
    schedule epochs: the epoch-boundary resync (reliable control plane)
    repairs the drift the lossy epochs accumulate in m_agg, so the
    consensus error still contracts by an order of magnitude and the
    push-sum weight never leaves 1.0."""
    body = """
key = jax.random.PRNGKey(9)
tree = make_tree(key)
local = jax.tree.map(lambda a: a[0], tree)
layout = wire.WireLayout.for_tree(local)
leaves, treedef = jax.tree_util.tree_flatten(tree)
ks = jax.random.split(key, len(leaves))
x0 = jax.tree_util.tree_unflatten(treedef, [
    (jax.random.normal(k2, a.shape, jnp.float32) * 0.05).astype(a.dtype)
    for k2, a in zip(ks, leaves)])
kw = dict(algorithm="adc_dgd", quant_mode="adaptive",
          topology="directed-ring", ring_strides=(1, 2),
          schedule_period=3, link_loss=0.3, loss_seed=2,
          wire_packing="packed")
rt = ConsensusRuntime(ConsensusConfig(**kw), ctx)
init_f, step_f = build(rt, x0)
st = init_f(x0)
# distinct inits: rebuild m_agg from the actual stride-1 in-neighbors
# with the directed in-weights (the resync correction, applied up front)
xt0 = np.stack([np.asarray(layout.pack(
    jax.tree.map(lambda a, d=d: a[d], x0))) for d in range(4)])
w_fwd, w_bwd = rt.cfg.in_weights
m0 = w_fwd * np.roll(xt0, 1, axis=0) + w_bwd * np.roll(xt0, -1, axis=0)
st = dict(st, m_agg=jnp.asarray(m0))

def cerr(x):
    t, c = 0.0, 0
    for leaf in jax.tree_util.tree_leaves(x):
        a = np.asarray(jax.device_get(leaf), np.float64)
        t += float(np.sum((a - a.mean(0, keepdims=True)) ** 2))
        c += a[0].size
    return t / c

x = x0
err0 = cerr(x)
for k in range(1, 10):
    x, st = step_f(x, x, st, jnp.asarray(k, jnp.int32))
print("RESULT", json.dumps({
    "err0": err0, "err1": cerr(x),
    "ps_w_dev": float(np.max(np.abs(np.asarray(st["ps_w"]) - 1.0)))}))
"""
    r = run_sub(body)
    assert r["err1"] < 0.1 * r["err0"], r
    assert r["ps_w_dev"] == 0.0
