"""Elastic membership (core.topology.MembershipSchedule + the runtime's
in-trace activity mask) and the fault processes that drive it.

Covered contracts:
  * ``MembershipSchedule`` spec parsing, epoch clamping and validation
    (>= 2 active nodes per epoch, equal mask lengths)
  * ``NodeFailureModel`` masks are seed-deterministic, start all-active
    and never drop the active count below ``min_active``
  * ``GilbertElliottLoss`` is seed-deterministic, traced ``keep`` ==
    ``keep_mask_host``, losses are genuinely bursty (mean bad-run length
    ~ 1/r) and the empirical delivered fraction matches
    ``expected_delivered_frac`` — the generalized accounting oracle
  * ``StragglerModel`` draws are independent of the ``LossModel`` stream
    at equal (rate, seed)
  * the bounded-retry resync handshake: traced ``resync_keep`` == host
    oracle, and more retries monotonically raise the success rate
  * elastic mixing algebra: Metropolis-Hastings reweighting over the
    survivor ring is symmetric doubly stochastic with identity rows for
    inactive nodes; the push-sum handoff matrix is column-stochastic and
    mass-conserving (hypothesis versions in test_property_based.py)
  * reference runtime: ``consensus.run_elastic`` under churn converges
    back to the static-membership trajectory; push-sum mass handoff keeps
    the ratio-consensus estimate finite and convergent

Multi-device (subprocess, 4 devices — harness from tests/test_wire.py):
  * a single all-active mask keeps the membership machinery in the trace
    yet is BIT-IDENTICAL to membership=None (packed AND async)
  * an inactive node still traces exactly 2 ppermutes/step, and the
    churn dispatch (mask switching) costs exactly what the stride
    schedule costs — no extra collectives
  * churn scenario: a node leaves for one schedule epoch and rejoins;
    post-resync the consensus error contracts back to the static
    trajectory's level on BOTH the packed and async transports
  * delivered-bytes accounting is exact against ``keep_mask_host`` for
    the Gilbert-Elliott model (the "any loss model" generalization), and
    ``deadline_miss_frac`` matches the ``StragglerModel`` host oracle
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import consensus, faults
from repro.core.compression import RandomizedRounding
from repro.core.problems import paper_circle_problem
from repro.core.topology import MembershipSchedule, ring
from test_wire import REPO, run_sub


# ---------------------------------------------------------------------------
# MembershipSchedule: spec parsing, clamping, mixing algebra
# ---------------------------------------------------------------------------

def test_membership_from_spec_and_clamping():
    m = MembershipSchedule.from_spec("2@1:3;0@4:6", 6)
    assert m.n_nodes == 6
    assert m.n_epochs == 7          # max(end) + 1: the recovery epoch exists
    assert m.mask_at(0) == (True,) * 6
    assert m.mask_at(1) == (True, True, False, True, True, True)
    assert not m.mask_at(2)[2] and m.mask_at(3)[2]
    assert not m.mask_at(4)[0] and m.mask_at(6)[0]
    # epochs past the schedule clamp to the last mask
    assert m.mask_at(99) == m.mask_at(6)
    assert not m.is_static
    assert MembershipSchedule.static(4).is_static


def test_membership_validation():
    with pytest.raises(ValueError):
        MembershipSchedule(((True, False, False, False),))  # < 2 active
    with pytest.raises(ValueError):
        MembershipSchedule(((True, True), (True, True, True)))  # ragged
    with pytest.raises(ValueError):
        MembershipSchedule.from_spec("9@1:2", 4)            # node oob


def test_elastic_mixing_is_doubly_stochastic_with_identity_rows():
    m = MembershipSchedule.from_spec("2@1:3;4@1:2", 6)
    for e in range(m.n_epochs):
        for rule in ("metropolis", "ring"):
            w = np.asarray(m.mixing_at(e, rule=rule).w)
            np.testing.assert_allclose(w.sum(0), 1.0, atol=1e-6)
            np.testing.assert_allclose(w.sum(1), 1.0, atol=1e-6)
            np.testing.assert_allclose(w, w.T, atol=1e-7)
            for j, on in enumerate(m.mask_at(e)):
                if not on:
                    row = np.zeros(6); row[j] = 1.0
                    np.testing.assert_array_equal(w[j], row)
                    np.testing.assert_array_equal(w[:, j], row)
    # MH over the compacted ring (every degree 2) is the uniform 1/3 rule
    w1 = np.asarray(m.mixing_at(1, rule="metropolis").w)
    active = [i for i, on in enumerate(m.mask_at(1)) if on]
    sub = w1[np.ix_(active, active)]
    assert np.allclose(sub[sub > 0], 1.0 / 3.0, atol=1e-6)


def test_handoff_matrix_conserves_mass():
    m = MembershipSchedule.from_spec("2@1:3", 6)
    h = np.asarray(m.handoff_at(1))
    np.testing.assert_allclose(h.sum(0), 1.0, atol=1e-7)  # column-stochastic
    x = np.random.default_rng(0).normal(size=(6, 3))
    np.testing.assert_allclose((h @ x).sum(0), x.sum(0), atol=1e-5)
    # departing node 2's mass lands on a survivor, its own row zeroes out
    assert h[2].sum() == 0.0 and h[:, 2].sum() == 1.0
    # rejoin epoch: node 2 warm-restarts from a neighbour active through
    # the outage
    src = m.rejoin_sources_at(3)
    assert set(src) == {2}
    assert m.mask_at(2)[src[2]] and m.mask_at(3)[src[2]]


# ---------------------------------------------------------------------------
# NodeFailureModel / GilbertElliottLoss / StragglerModel / resync retries
# ---------------------------------------------------------------------------

def test_node_failure_model_deterministic_and_floored():
    fm = faults.NodeFailureModel(fail_rate=0.6, recover_rate=0.4, seed=7)
    a = fm.active_mask_host(6, 20)
    np.testing.assert_array_equal(
        a, faults.NodeFailureModel(fail_rate=0.6, recover_rate=0.4,
                                   seed=7).active_mask_host(6, 20))
    assert a[0].all()                                  # epoch 0 all-active
    assert (a.sum(axis=1) >= 2).all()                  # min_active floor
    assert a.min() == 0                                # failures do happen
    b = faults.NodeFailureModel(fail_rate=0.6, recover_rate=0.4,
                                seed=8).active_mask_host(6, 20)
    assert np.any(a != b)
    sched = MembershipSchedule.from_failure_model(fm, 6, 20)
    np.testing.assert_array_equal(np.asarray(sched.masks), a)


def test_gilbert_elliott_deterministic_bursty_and_calibrated():
    m = faults.GilbertElliottLoss(p=0.1, r=0.5, seed=3, n_nodes=8,
                                  horizon=2048)
    tab = m._keep_table
    np.testing.assert_array_equal(
        tab, faults.GilbertElliottLoss(p=0.1, r=0.5, seed=3, n_nodes=8,
                                       horizon=2048)._keep_table)
    assert np.any(tab != faults.GilbertElliottLoss(
        p=0.1, r=0.5, seed=4, n_nodes=8, horizon=2048)._keep_table)
    # stationary delivered fraction (the generalized accounting oracle)
    assert abs(tab.mean() - m.expected_delivered_frac()) < 0.02
    # burstiness: mean loss-run length ~ 1/r (i.i.d. at the same rate
    # would give 1 / (1 - stationary_loss) ~ 1.2)
    runs = []
    for d in range(2):
        for v in range(8):
            col = ~tab[:, d, v]
            n = 0
            for bit in col:
                if bit:
                    n += 1
                elif n:
                    runs.append(n); n = 0
    mean_run = np.mean(runs)
    assert abs(mean_run - 1.0 / m.r) < 0.25, mean_run


def test_gilbert_traced_keep_matches_host_oracle():
    m = faults.GilbertElliottLoss(p=0.3, r=0.4, seed=1, n_nodes=4)
    mask = m.keep_mask_host(4, range(1, 7))
    keep_j = jax.jit(m.keep)
    for si, s in enumerate(range(1, 7)):
        for d in (faults.FROM_UPSTREAM, faults.FROM_DOWNSTREAM):
            for v in range(4):
                assert bool(keep_j(jnp.asarray(s, jnp.int32), d, v)) \
                    == mask[si, d, v], (s, d, v)


def test_straggler_stream_independent_of_loss_stream():
    lm = faults.LossModel(rate=0.4, seed=11)
    sm = faults.StragglerModel(rate=0.4, seed=11)
    a = lm.keep_mask_host(8, range(1, 65))
    b = sm.keep_mask_host(8, range(1, 65))
    assert np.any(a != b)                       # domain-separated streams
    np.testing.assert_array_equal(
        b, faults.StragglerModel(rate=0.4, seed=11).keep_mask_host(
            8, range(1, 65)))
    assert abs(b.mean() - 0.6) < 0.05


def test_resync_keep_traced_matches_host_and_retries_help():
    lm = faults.LossModel(rate=0.6, seed=2)
    host = lm.resync_keep_host(4, [4, 7, 10], retries=3)
    for si, s in enumerate((4, 7, 10)):
        for v in range(4):
            up, dn = jax.jit(lm.resync_keep, static_argnames="retries")(
                jnp.asarray(s, jnp.int32), v, retries=3)
            assert bool(up) == host[si, 0, v]
            assert bool(dn) == host[si, 1, v]
    # OR over attempts: success rate rises monotonically, ~ 1 - rate^a
    fracs = [lm.resync_keep_host(16, range(1, 201), retries=a).mean()
             for a in (1, 2, 4)]
    assert fracs[0] < fracs[1] < fracs[2]
    assert abs(fracs[0] - 0.4) < 0.05
    assert abs(fracs[2] - (1.0 - 0.6**4)) < 0.05


# ---------------------------------------------------------------------------
# Reference runtime: run_elastic
# ---------------------------------------------------------------------------

def _elastic_fixture(n=6, dim=8):
    prob = paper_circle_problem(n, seed=0, dim=dim)
    alg = consensus.ADCDGD(ring(n, 0.5), RandomizedRounding(0.05),
                           consensus.StepSize(0.05, 0.6), gamma=1.0)
    return prob, alg


def test_run_elastic_static_mask_reproduces_run():
    prob, alg = _elastic_fixture()
    r_el = consensus.run_elastic(alg, prob, 40, MembershipSchedule.static(6),
                                 schedule_period=4, rule="ring", key=3)
    r_ref = consensus.run(alg, prob, 40, key=3)
    np.testing.assert_allclose(r_el["x_final"], r_ref["x_final"], rtol=1e-6)
    np.testing.assert_allclose(r_el["consensus"], r_ref["consensus"],
                               rtol=1e-5)
    np.testing.assert_allclose(r_el["bytes"], r_ref["bytes"])


def test_run_elastic_churn_converges_to_static_trajectory():
    prob, alg = _elastic_fixture()
    mem = MembershipSchedule.from_spec("2@1:3", 6, n_epochs=10)
    r_ch = consensus.run_elastic(alg, prob, 120, mem, schedule_period=6,
                                 key=3)
    r_st = consensus.run(alg, prob, 120, key=3)
    assert np.asarray(r_ch["active_nodes"])[6] == 5.0
    assert np.asarray(r_ch["active_nodes"])[-1] == 6.0
    # post-rejoin the consensus error contracts back to the static level
    assert r_ch["consensus"][-1] < 0.3 * r_ch["consensus"][0]
    assert r_ch["consensus"][-1] < 5.0 * max(r_st["consensus"][-1], 1e-3)
    assert abs(r_ch["obj"][-1] - r_st["obj"][-1]) < 0.05 * abs(
        r_st["obj"][-1])
    # churn epochs bill fewer wire bytes than the static run
    assert r_ch["bytes"][-1] < r_st["bytes"][-1]


def test_run_elastic_push_sum_handoff_converges():
    prob, alg = _elastic_fixture()
    mem = MembershipSchedule.from_spec("2@1:3", 6, n_epochs=10)
    r = consensus.run_elastic(alg, prob, 120, mem, schedule_period=6,
                              push_sum=True, key=3)
    assert all(np.isfinite(v).all() for v in r.values())
    assert r["consensus"][-1] < 0.3 * r["consensus"][0]
    r_st = consensus.run(alg, prob, 120, key=3)
    assert abs(r["obj"][-1] - r_st["obj"][-1]) < 0.05 * abs(r_st["obj"][-1])
    # every node's final weight is positive (mass was handed off, then
    # re-seeded at rejoin), and the de-biased estimates agree
    assert (r["ps_w_final"] > 0).all()


# ---------------------------------------------------------------------------
# Multi-device: the elastic exchange (subprocess, 4 devices)
# ---------------------------------------------------------------------------

def test_all_active_membership_bit_identical_to_none():
    """Acceptance: a single all-active mask keeps the membership machinery
    in the trace yet the exchange is bit-for-bit membership=None — on the
    packed AND the async transport."""
    body = """
tree = make_tree(jax.random.PRNGKey(0))
out = {}
for mode in ("packed", "async"):
    kw = dict(algorithm="adc_dgd", quant_mode="fixed", fixed_step0=1e-2,
              wire_packing=mode)
    ref = trajectory(kw, tree, steps=5)
    ela = trajectory({**kw, "membership": ((True,) * 4,)}, tree, steps=5)
    out[mode] = max_diff(ref, ela)
print("RESULT", json.dumps(out))
"""
    r = run_sub(body)
    for mode, v in r.items():
        assert v == 0.0, f"{mode}: all-active membership perturbed by {v}"


def test_churn_exchange_still_two_ppermutes():
    """Acceptance: routing around an inactive node (compacted survivor
    ring) traces EXACTLY 2 ppermutes/step on packed and async; the churn
    mask dispatch costs exactly what the stride-schedule dispatch costs
    (same recursive ppermute count — the resync stays amortized)."""
    body = """
import sys
sys.path.insert(0, os.path.join(%r, "benchmarks"))
from consensus_step import count_eqns

def count_for(**kw):
    rt = ConsensusRuntime(ConsensusConfig(algorithm="adc_dgd", **kw), ctx)
    tree = make_tree(jax.random.PRNGKey(2))
    init_f, step_f = build(rt, tree)
    st = init_f(tree)
    jaxpr = jax.make_jaxpr(step_f)(tree, tree, st, jnp.asarray(2, jnp.int32))
    return count_eqns(jaxpr, "ppermute")

mask_out = (True, True, False, True)
allm = (True,) * 4
out = {
    "packed_hole": count_for(wire_packing="packed", membership=(mask_out,)),
    "async_hole": count_for(wire_packing="async", membership=(mask_out,)),
    "churn": count_for(wire_packing="packed",
                       membership=(allm, mask_out, allm),
                       schedule_period=2),
    "sched": count_for(wire_packing="packed", ring_strides=(1, 2),
                       schedule_period=2),
}
print("RESULT", json.dumps(out))
""" % REPO
    r = run_sub(body)
    assert r["packed_hole"] == 2, r
    assert r["async_hole"] == 2, r
    assert r["churn"] == r["sched"], r


def test_churn_scenario_recovers_consensus():
    """Acceptance: node 2 inactive for one schedule epoch, rejoins; the
    epoch-boundary resync rebuilds its m_agg and the consensus error
    contracts back to the static-membership trajectory's level on BOTH
    the packed and the async transport."""
    body = """
from repro.core import wire as W

def consensus_err(x):
    tot = 0.0
    for leaf in jax.tree_util.tree_leaves(x):
        a = np.asarray(leaf, np.float64)
        tot += float(((a - a.mean(0)) ** 2).sum())
    return tot ** 0.5

def gossip(cfg_kw, tree, steps):
    rt = ConsensusRuntime(ConsensusConfig(**cfg_kw), ctx)
    init_f, step_f = build(rt, tree)
    st = init_f(tree)
    x, errs = tree, []
    for k in range(1, steps + 1):
        x, st = step_f(x, x, st, jnp.asarray(k, jnp.int32))
        errs.append(consensus_err(x))
    return errs

ks = jax.random.split(jax.random.PRNGKey(5), 4)
tree = {"w": jax.random.normal(ks[0], (4, 3, 37), jnp.float32) * 0.05,
        "b": jax.random.normal(ks[1], (4, 513), jnp.float32) * 0.05}
allm = (True,) * 4
mem = (allm, (True, True, False, True), allm)
out = {}
for mode in ("packed", "async"):
    kw = dict(algorithm="adc_dgd", quant_mode="adaptive",
              wire_packing=mode, schedule_period=4)
    static = gossip(kw, tree, 16)
    churn = gossip({**kw, "membership": mem}, tree, 16)
    out[mode] = {"start": churn[0], "end": churn[-1],
                 "static_end": static[-1]}
print("RESULT", json.dumps(out))
"""
    r = run_sub(body)
    for mode, v in r.items():
        assert v["end"] < 0.2 * v["start"], (mode, v)
        assert v["end"] < 5.0 * max(v["static_end"], 1e-9), (mode, v)


def test_delivered_bytes_exact_for_gilbert_and_straggler_oracle():
    """Acceptance (small-fix satellite): delivered-bytes accounting is
    EXACT against ``keep_mask_host`` for the Gilbert-Elliott burst model,
    and the async ``deadline_miss_frac`` metric replays the
    ``StragglerModel`` host oracle exactly."""
    body = """
from repro.core import faults, wire as W

def build_metrics(rt, tree, keys):
    pspec = jax.tree.map(lambda a: P("data"), tree)
    cons_spec = {"x_tilde": P("data", None, None),
                 "m_agg": P("data", None, None)}
    if rt.cfg.wire_packing == "async":
        for fk in wire.INFLIGHT_KEYS:
            cons_spec[fk] = P("data", None)
    init = lambda p: jax.tree.map(lambda a: a[None], rt.init_state(p))
    init_f = jax.jit(jax.shard_map(
        init, mesh=mesh, in_specs=(pspec,), out_specs=cons_spec, check_vma=False))
    def step(xp, xh, s, k):
        s = jax.tree.map(lambda a: a[0], s)
        xn, s2, m = rt.exchange(xp, xh, s, k, jax.random.PRNGKey(7))
        got = jnp.stack([m[k2] for k2 in keys])
        return xn, jax.tree.map(lambda a: a[None], s2), got[None]
    step_f = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(pspec, pspec, cons_spec, P()),
        out_specs=(pspec, cons_spec, P("data")), check_vma=False))
    return init_f, step_f

tree = make_tree(jax.random.PRNGKey(0))
steps = 6
out = {}

# Gilbert burst loss on the packed path: delivered bytes vs host oracle
rt = ConsensusRuntime(ConsensusConfig(
    algorithm="adc_dgd", link_loss_model="gilbert:p=0.4,r=0.5",
    loss_seed=5), ctx)
init_f, step_f = build_metrics(rt, tree, ("wire_bytes_delivered",))
st, x, delivered = init_f(tree), tree, 0.0
for k in range(1, steps + 1):
    x, st, m = step_f(x, x, st, jnp.asarray(k, jnp.int32))
    delivered += float(np.sum(np.asarray(m)))
layout = wire.WireLayout.for_tree(jax.tree.map(lambda a: a[0], tree))
per_payload = float(rt.wire_plan_for(layout).wire_bytes(push_sum=False))
mask = rt.loss.keep_mask_host(4, range(1, steps + 1))
out["gilbert_delivered"] = delivered
out["gilbert_oracle"] = float(mask.sum()) * per_payload
out["gilbert_lossy"] = bool(mask.sum() < mask.size)

# Straggler deadlines on the async path: deadline_miss_frac vs oracle
rt2 = ConsensusRuntime(ConsensusConfig(
    algorithm="adc_dgd", wire_packing="async", straggle_rate=0.4,
    straggle_seed=9), ctx)
init_f2, step_f2 = build_metrics(rt2, tree, ("deadline_miss_frac",))
st2, x2, miss = init_f2(tree), tree, []
for k in range(1, steps + 1):
    x2, st2, m = step_f2(x2, x2, st2, jnp.asarray(k, jnp.int32))
    miss.append(np.asarray(m).reshape(4))       # per receiving node
got = np.stack(miss)                            # (steps, n_nodes)
# the deadline is drawn at the LAUNCH step (k - 1): row k of the metric
# replays the oracle's row for step k - 1
meet = rt2.straggler.keep_mask_host(4, range(0, steps))  # (steps, 2, 4)
oracle = 1.0 - meet.mean(axis=1)                # (steps, n_nodes)
out["straggler_match"] = bool((got == oracle).all())
out["straggler_miss_frac"] = float(got.mean())
print("RESULT", json.dumps(out))
"""
    r = run_sub(body)
    assert r["gilbert_lossy"], "gilbert config dropped nothing — bad fixture"
    assert r["gilbert_delivered"] == r["gilbert_oracle"], r
    assert r["straggler_match"], r
    assert 0.0 < r["straggler_miss_frac"] < 1.0, r
