"""Consensus telemetry subsystem (core.telemetry + launch.obs).

Covered contracts:
  * ``WireAccounting`` — the ONE wire-byte arithmetic: shipped ==
    delivered + dropped by construction for every constructor
    (plan-backed, per-leaf, uncompressed) and every delivered count,
    and ``ConsensusRuntime.wire_bytes_per_step`` is exactly its
    ``shipped_per_step``
  * ``timing_gate`` — the variance-aware speed-gate floor shared by the
    benchmark gates (PR 6's ``_timing_gate``) and the obs regression
    reporter: noise_tol at zero spread, relaxed by 1/(1 + 3 s)
  * telemetry/v1 validation — good meta/step/event records pass,
    malformed ones are rejected with a reason (pure stdlib)
  * ``Telemetry`` sink — JSONL roundtrip validates clean; typed
    registry rejects unregistered metrics, non-finite values and
    negative counters; ``register`` extends the schema via per-record
    ``types``
  * JSON-able describe()/event helpers: WireLayout, WirePlan, loss
    models, ``MembershipSchedule.epoch_events``,
    ``AdaptiveBitController.candidate_table``

Multi-device (subprocess, 4 devices — harness from tests/test_wire.py):
  * cross-check (satellite): traced ``wire_bytes_shipped`` ==
    ``wire_bytes_delivered`` + dropped-oracle EXACTLY, with delivered
    matching the host keep-table oracle, for Bernoulli AND
    Gilbert-Elliott loss on packed, pipelined and async transports
  * per-node health metrics under churn: ``active_nodes``,
    ``delivered_frac`` and the byte counters replay the keep-table and
    membership oracles across a MembershipSchedule epoch boundary, and
    every per-node metric is ZERO while the node is inactive; async +
    straggler churn additionally replays ``deadline_miss_frac``
"""
import json

import jax
import numpy as np
import pytest

from repro.core import faults, telemetry, wire
from repro.core.codec import AdaptiveBitController
from repro.core.distributed import ConsensusConfig, ConsensusRuntime
from repro.core.topology import MembershipSchedule
from repro.models.sharding import ParallelContext
from test_wire import REPO, run_sub


# ---------------------------------------------------------------------------
# WireAccounting: the unified byte arithmetic
# ---------------------------------------------------------------------------

def test_wire_accounting_invariant():
    """shipped_payload == delivered + dropped for every delivered count,
    traced-or-host, on every constructor."""
    accts = [
        telemetry.WireAccounting(payload_bytes=1000),
        telemetry.WireAccounting(payload_bytes=1000, trailer_bytes=4),
        telemetry.WireAccounting(payload_bytes=777, trailer_bytes=4,
                                 resync_bytes_amortized=123.5),
        telemetry.WireAccounting.uncompressed(n_params=4096, itemsize=4),
    ]
    for a in accts:
        assert a.bytes_per_direction == a.payload_bytes + a.trailer_bytes
        assert a.shipped_payload == 2 * a.bytes_per_direction
        assert a.shipped_per_step == (a.shipped_payload
                                      + a.resync_bytes_amortized)
        for d in (0, 1, 2, 0.5, 1.75):
            assert a.delivered_bytes(d) + a.dropped_bytes(d) == \
                a.shipped_payload


def _runtime(ctx=None, **kw):
    ctx = ctx or ParallelContext(tp=1, data_size=4, n_nodes=4,
                                 in_shard_map=True)
    return ConsensusRuntime(ConsensusConfig(algorithm="adc_dgd", **kw), ctx)


def _local_tree():
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    return {"w": jax.random.normal(ks[0], (3, 37)),
            "b": jax.random.normal(ks[1], (513,)),
            "deep": {"m": jax.random.normal(ks[2], (7, 11, 2))}}


def test_wire_accounting_is_the_runtime_source():
    """ConsensusRuntime.wire_bytes_per_step is EXACTLY the accounting's
    shipped_per_step, for packed (plan-backed, incl. mixed), per-leaf
    (padded rows) and schedule-varying (amortized resync) configs; the
    plan constructor reproduces the runtime's payload arithmetic."""
    layout = wire.WireLayout.for_tree(_local_tree())
    n = layout.n_elements
    for kw in (dict(),
               dict(wire_codec="mixed:deep=int4,*=int8"),
               dict(wire_packing="per_leaf"),
               dict(ring_strides=(1, 2), schedule_period=2)):
        rt = _runtime(**kw)
        acct = rt.wire_accounting(n, layout=layout)
        assert acct is not None
        assert rt.wire_bytes_per_step(n, layout=layout) == \
            acct.shipped_per_step
    # plan-backed constructor == the runtime's packed accounting
    rt = _runtime(wire_codec="mixed:deep=int4,*=int8")
    plan = rt.wire_plan_for(layout)
    a1 = telemetry.WireAccounting.for_plan(plan)
    a2 = rt.wire_accounting(n, layout=layout)
    assert a1.payload_bytes == a2.payload_bytes == plan.payload_bytes
    # per-leaf ships MORE rows (TILE_N-padded per leaf) than packed
    a_pl = telemetry.WireAccounting.for_per_leaf(layout)
    assert a_pl.payload_bytes == \
        _runtime(wire_packing="per_leaf").wire_accounting(
            n, layout=layout).payload_bytes
    assert a_pl.payload_bytes > a1.payload_bytes
    # push-sum rides as a 4-byte trailer per direction
    a_ps = telemetry.WireAccounting.for_plan(plan, push_sum=True)
    assert a_ps.shipped_payload == a1.shipped_payload + 8


def test_timing_gate_values():
    assert telemetry.timing_gate({"timing_spread": 0.0}) == 0.5
    assert telemetry.timing_gate(
        {"timing_spread": 0.0}, noise_tol=0.9) == 0.9
    # spread s relaxes the floor by 1/(1 + 3 s); the WORST path governs
    got = telemetry.timing_gate({"timing_spread": 0.1},
                                {"timing_spread": 0.5}, noise_tol=0.6)
    assert got == pytest.approx(0.6 / 2.5)
    # missing/None spread counts as zero
    assert telemetry.timing_gate({}, {"timing_spread": None}) == 0.5


# ---------------------------------------------------------------------------
# telemetry/v1 records + the host sink
# ---------------------------------------------------------------------------

def test_validate_record():
    S = telemetry.SCHEMA
    ok = [
        {"schema": S, "kind": "meta", "run_id": "r1", "config": {},
         "git_sha": None},
        {"schema": S, "kind": "step", "step": 3,
         "metrics": {"loss": 1.25, "wire_bytes_delivered": 0.0}},
        {"schema": S, "kind": "step", "step": 0,
         "metrics": {"my_gauge": -1.0}, "types": {"my_gauge": "gauge"}},
        {"schema": S, "kind": "event", "event": "resync", "step": 4,
         "data": {"ok": True}},
        {"schema": S, "kind": "event", "event": "run_end", "step": None,
         "data": {}},
    ]
    for rec in ok:
        assert telemetry.validate_record(rec) is None, rec
    bad = [
        ("not an object", []),
        ("schema", {"schema": "telemetry/v0", "kind": "meta",
                    "run_id": "r", "config": {}}),
        ("kind", {"schema": S, "kind": "span"}),
        ("run_id", {"schema": S, "kind": "meta", "run_id": "",
                    "config": {}}),
        ("step.step", {"schema": S, "kind": "step", "step": -1,
                       "metrics": {"loss": 1.0}}),
        ("registered", {"schema": S, "kind": "step", "step": 1,
                        "metrics": {"mystery": 1.0}}),
        ("finite", {"schema": S, "kind": "step", "step": 1,
                    "metrics": {"loss": float("nan")}}),
        ("counter", {"schema": S, "kind": "step", "step": 1,
                     "metrics": {"wire_bytes_delivered": -2.0}}),
        ("number", {"schema": S, "kind": "step", "step": 1,
                    "metrics": {"loss": True}}),
        ("event.event", {"schema": S, "kind": "event", "event": "boom",
                         "data": {}}),
        ("event.data", {"schema": S, "kind": "event", "event": "resync",
                        "data": None}),
    ]
    for tag, rec in bad:
        assert telemetry.validate_record(rec) is not None, tag


def test_telemetry_sink_roundtrip(tmp_path):
    tel = telemetry.Telemetry("t1", out_dir=str(tmp_path),
                              config={"steps": 3}, git_sha="deadbeef")
    tel.register("my_count", "counter")
    tel.record_step(1, {"loss": 0.5, "wire_bytes_shipped": 100.0,
                        "my_count": 2})
    tel.event("codec_decision", step=1, old="int8", new="int4")
    tel.event("run_end", wall_s=0.1)
    with pytest.raises(ValueError):
        tel.record_step(2, {"mystery_metric": 1.0})    # unregistered
    with pytest.raises(ValueError):
        tel.record_step(2, {"my_count": -1.0})         # negative counter
    with pytest.raises(ValueError):
        tel.record_step(2, {"loss": float("inf")})     # non-finite
    with pytest.raises(ValueError):
        tel.event("not_an_event")
    with pytest.raises(ValueError):
        tel.register("x", "histogram")
    tel.close()
    assert telemetry.validate_file(tel.path) == []
    recs = [json.loads(line) for line in open(tel.path)]
    assert [r["kind"] for r in recs] == ["meta", "step", "event", "event"]
    assert recs[0]["run_id"] == "t1" and recs[0]["git_sha"] == "deadbeef"
    assert recs[1]["metrics"]["my_count"] == 2.0
    assert recs[1]["types"] == {"my_count": "counter"}
    assert recs[2]["data"] == {"old": "int8", "new": "int4"}
    tel.close()  # idempotent


# ---------------------------------------------------------------------------
# JSON-able event payload helpers
# ---------------------------------------------------------------------------

def test_epoch_events():
    m = MembershipSchedule.from_spec("1@1:2", 4)
    ev = m.epoch_events()
    assert ev == [
        {"epoch": 1, "joined": [], "departed": [1], "active": 3},
        {"epoch": 2, "joined": [1], "departed": [], "active": 4},
    ]
    assert MembershipSchedule.static(4).epoch_events() == []
    json.dumps(ev)


def test_candidate_table():
    c = AdaptiveBitController(byte_budget=None, current="int8")
    tab = c.candidate_table(n_rows=16)
    assert {r["name"] for r in tab} == set(c.ladder)
    assert all(r["fits_budget"] for r in tab)      # no budget: all fit
    assert [r["name"] for r in tab if r["current"]] == ["int8"]
    # a tight budget prices some rungs out but keeps the cheapest
    tight = AdaptiveBitController(byte_budget=1.0).candidate_table(16)
    assert sum(r["fits_budget"] for r in tight) == 1
    json.dumps(tab)


def test_describe_helpers_are_json_able():
    layout = wire.WireLayout.for_tree(_local_tree())
    d = layout.describe()
    assert d["n_leaves"] == 3 and d["n_elements"] == layout.n_elements
    rt = _runtime(wire_codec="mixed:deep=int4,*=int8")
    p = rt.wire_plan_for(layout).describe()
    assert p["payload_bytes"] == rt.wire_plan_for(layout).payload_bytes
    assert not p["is_uniform"] and len(p["runs"]) >= 2
    assert sum(r["n_rows"] for r in p["runs"]) == layout.n_rows
    lm = faults.LossModel(rate=0.2, seed=3).describe()
    assert lm["expected_delivered_frac"] == pytest.approx(0.8)
    ge = faults.GilbertElliottLoss(p=0.4, r=0.5, seed=1,
                                   n_nodes=4).describe()
    assert ge["mean_burst_steps"] == pytest.approx(2.0)
    json.dumps([d, p, lm, ge])


# ---------------------------------------------------------------------------
# Multi-device cross-checks (subprocess, 4 devices)
# ---------------------------------------------------------------------------

_METRICS_BUILD = """
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

def run_metrics(cfg_kw, tree, keys, steps):
    rt = ConsensusRuntime(ConsensusConfig(**cfg_kw), ctx)
    init_f, step_f = build_metrics(rt, tree, keys)
    st, x, rows = init_f(tree), tree, []
    for k in range(1, steps + 1):
        x, st, m = step_f(x, x, st, jnp.asarray(k, jnp.int32))
        rows.append(np.asarray(m))        # (n_nodes, len(keys))
    return rt, np.stack(rows)             # (steps, n_nodes, len(keys))
"""


def test_shipped_equals_delivered_plus_dropped_all_transports():
    """Satellite cross-check: with ``telemetry=True`` the traced byte
    counters satisfy shipped == delivered + dropped EXACTLY — per
    node-step AND against the host keep-table oracles — for Bernoulli
    and Gilbert-Elliott loss on packed, pipelined and async."""
    body = """
from repro.core import telemetry as tele
""" + _METRICS_BUILD + """
tree = make_tree(jax.random.PRNGKey(0))
layout = wire.WireLayout.for_tree(jax.tree.map(lambda a: a[0], tree))
steps = 6
keys = ("wire_bytes_shipped", "wire_bytes_delivered")
out = {}
for loss_tag, loss_kw in (
        ("bern", dict(link_loss=0.35, loss_seed=5)),
        ("gilbert", dict(link_loss_model="gilbert:p=0.4,r=0.5",
                         loss_seed=5))):
    for mode, mode_kw in (("packed", {}),
                          ("pipelined", dict(pipeline_chunks=4)),
                          ("async", {})):
        kw = dict(algorithm="adc_dgd", wire_packing=mode, telemetry=True,
                  **loss_kw, **mode_kw)
        rt, m = run_metrics(kw, tree, keys, steps)
        acct = rt.wire_accounting(layout.n_elements, layout=layout)
        shipped, delivered = m[:, :, 0], m[:, :, 1]
        # async retires the payload LAUNCHED at step k-1; the eager
        # transports draw at step k
        first = 0 if mode == "async" else 1
        mask = rt.loss.keep_mask_host(4, range(first, first + steps))
        o = {}
        o["shipped_const"] = bool(
            (shipped == acct.shipped_payload).all())
        o["delivered_matches_oracle"] = bool(np.allclose(
            delivered.sum(),
            float(mask.sum()) * acct.bytes_per_direction))
        dropped_oracle = (float(mask.size - mask.sum())
                          * acct.bytes_per_direction)
        o["conservation"] = bool(np.allclose(
            shipped.sum(), delivered.sum() + dropped_oracle))
        # per node-step too: dropped = shipped - delivered is exactly
        # acct.dropped_bytes of the per-step delivered direction count
        d_dirs = delivered / acct.bytes_per_direction
        o["per_step"] = bool(np.allclose(
            shipped - delivered, acct.dropped_bytes(d_dirs)))
        o["lossy"] = bool(mask.sum() < mask.size)
        out[f"{loss_tag}_{mode}"] = o
print("RESULT", json.dumps(out))
"""
    r = run_sub(body)
    assert len(r) == 6
    for tag, o in r.items():
        assert o["lossy"], f"{tag}: fixture dropped nothing"
        for check, val in o.items():
            assert val, f"{tag}: {check} failed"


def test_churn_health_metrics_across_epoch_boundary():
    """Satellite: per-node health metrics under churn replay the
    membership + keep-table oracles across a MembershipSchedule epoch
    boundary; every per-node metric is ZERO while the node is inactive;
    async + straggler churn replays ``deadline_miss_frac`` too."""
    body = """
""" + _METRICS_BUILD + """
tree = make_tree(jax.random.PRNGKey(0))
layout = wire.WireLayout.for_tree(jax.tree.map(lambda a: a[0], tree))
masks = ((True,) * 4, (True, False, True, True), (True,) * 4)
period, steps = 2, 6
epoch_of = lambda k: min((k - 1) // period, len(masks) - 1)
out = {}

# eager packed transport under Bernoulli loss + churn
keys = ("wire_bytes_shipped", "wire_bytes_delivered", "delivered_frac",
        "active_nodes", "resync_fired", "resync_ok")
rt, m = run_metrics(dict(
    algorithm="adc_dgd", membership=masks, schedule_period=period,
    link_loss=0.3, loss_seed=3, telemetry=True), tree, keys, steps)
acct = rt.wire_accounting(layout.n_elements, layout=layout)
keep = rt.loss.keep_mask_host(4, range(1, steps + 1))  # (steps, 2, 4)
o = {"active_nodes": True, "zeroed": True, "delivered": True,
     "frac": True}
for k in range(1, steps + 1):
    mk = masks[epoch_of(k)]
    o["active_nodes"] &= bool((m[k - 1, :, 3] == float(sum(mk))).all())
    for v in range(4):
        shipped, delivered, frac = m[k - 1, v, 0], m[k - 1, v, 1], \
            m[k - 1, v, 2]
        if not mk[v]:
            o["zeroed"] &= (shipped == 0.0 and delivered == 0.0
                            and frac == 0.0 and m[k - 1, v, 4] == 0.0)
        else:
            d = float(keep[k - 1, :, v].sum())
            o["delivered"] &= bool(np.allclose(
                delivered, acct.delivered_bytes(d)))
            o["delivered"] &= shipped == acct.shipped_payload
            o["frac"] &= bool(np.allclose(frac, d / 2.0))
# epoch-boundary resyncs: steps 3 and 5 fire on every ACTIVE node
fired = m[:, :, 4]
o["resync_steps"] = bool(
    (fired.sum(1) == np.array([0, 0, 3, 0, 4, 0])).all())
o["resync_ok_le_fired"] = bool((m[:, :, 5] <= fired).all())
out["packed"] = {k2: bool(v) for k2, v in o.items()}

# async transport: straggler deadlines under the same churn window
keys2 = ("delivered_frac", "deadline_miss_frac", "active_nodes")
rt2, m2 = run_metrics(dict(
    algorithm="adc_dgd", wire_packing="async", membership=masks,
    schedule_period=period, straggle_rate=0.3, straggle_seed=2,
    telemetry=True), tree, keys2, steps)
meet = rt2.straggler.keep_mask_host(4, range(0, steps))  # launch step k-1
o2 = {"zeroed": True, "miss": True, "frac": True}
for k in range(1, steps + 1):
    mk = masks[epoch_of(k)]
    for v in range(4):
        frac, miss = m2[k - 1, v, 0], m2[k - 1, v, 1]
        if not mk[v]:
            o2["zeroed"] &= (frac == 0.0 and miss == 0.0)
        else:
            mu = meet[k - 1, :, v].astype(np.float64)
            o2["miss"] &= bool(np.allclose(miss, 1.0 - mu.mean()))
            o2["frac"] &= bool(np.allclose(frac, mu.mean()))
o2["missed_some"] = bool(m2[:, :, 1].sum() > 0)
out["async"] = {k2: bool(v) for k2, v in o2.items()}
print("RESULT", json.dumps(out))
"""
    r = run_sub(body)
    for transport, checks in r.items():
        for check, val in checks.items():
            assert val, f"{transport}: {check} failed"
