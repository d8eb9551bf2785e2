"""Distributed runtime tests (multi-device CPU via subprocess).

Each test spawns a fresh python with XLA_FLAGS=--xla_force_host_platform_
device_count=8 (jax locks device count at first init; the main pytest
process must keep seeing ONE device for the smoke tests).

Covered invariants:
  * distributed (fsdp x tp) gradients == single-device oracle
  * ADC-DGD / DGD / allreduce all train; ADC tracks allreduce closely
  * on a batch shared by every node ADC-DGD follows allreduce (one step)
  * consensus error of allreduce == 0, ADC-DGD stays bounded
  * Pallas kernels (interpret) inside the distributed exchange == jnp path
  * model-replicated leaves stay bit-identical across model ranks
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, timeout: int = 1500) -> dict:
    """Run `body` in a subprocess with 8 host devices; it must print a final
    line 'RESULT <json>'."""
    prelude = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.launch.mesh import make_cpu_mesh
        from repro.launch import train as LT
        from repro.data import SyntheticLMDataset
        from repro.models import transformer as T
        from repro.models.sharding import local_context
        from repro.models.params import ParamDef
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", prelude + textwrap.dedent(body)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env, cwd=REPO)
    if proc.returncode != 0:
        raise AssertionError(f"subprocess failed:\n{proc.stderr[-4000:]}")
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise AssertionError(f"no RESULT line in output:\n{proc.stdout[-2000:]}")


GRAD_ORACLE_BODY = """
import dataclasses
cfg = reduced(get_config("{arch}"))
if cfg.n_experts:
    # router aux loss is a per-node objective (mean over the NODE batch,
    # nonlinear in the batch split) — zero it so CE decomposes exactly.
    cfg = dataclasses.replace(cfg, router_aux_weight=0.0)
mesh = make_cpu_mesh(data={data}, model={model})
ds_kw = {{}}
if cfg.frontend == "audio_frames":
    ds_kw = dict(enc_frames=cfg.encoder_frames, d_model=cfg.d_model)
ds = SyntheticLMDataset(cfg.vocab_size, 64, {batch}, n_shards={data}, **ds_kw)
setup = LT.build_train_setup(cfg, mesh, consensus_nodes={nodes},
                             algorithm="none", lr=1e-2, global_batch={batch})
state = LT.init_train_state(setup, jax.random.PRNGKey(0))
pb = jax.device_get(state["params"])
bn = ds.global_batch_arrays(0)
state, m = setup.train_step(state, jax.device_put(bn, setup.batch_sharding))
pa = jax.device_get(state["params"])

ctx_l = local_context()
defs_l = T.build_defs(cfg, ctx_l)
fd = jax.tree_util.tree_flatten(defs_l.storage,
        is_leaf=lambda x: isinstance(x, ParamDef))[0]
fs, td = jax.tree_util.tree_flatten(pb)
def logical(d, a):
    sl = tuple(slice(0, d.shape[i]) if i == d.fsdp_dim else slice(None)
               for i in range(a.ndim))
    return jnp.asarray(a[sl])
params_l = jax.tree_util.tree_unflatten(td, [logical(d, a) for d, a in zip(fd, fs)])
# full-batch oracle loss (the distributed metric is the all-node mean);
# node-0 batch slice oracle for gradients (the update we read is node 0's:
# with algorithm "none" each node steps on its OWN microbatches only).
bfull = {{k: jnp.asarray(v) for k, v in bn.items()}}
(loss_l, _), _ = jax.value_and_grad(T.train_loss, has_aux=True)(
    params_l, defs_l, bfull, ctx_l)
b_node = {batch} // {nodes}
bn0 = {{k: jnp.asarray(v[:b_node]) for k, v in bn.items()}}
(_, _), gl = jax.value_and_grad(T.train_loss, has_aux=True)(
    params_l, defs_l, bn0, ctx_l)
fa = jax.tree_util.tree_flatten(pa)[0]
fg = jax.tree_util.tree_flatten(gl)[0]
errs = []
for d, b4, af, g in zip(fd, fs, fa, fg):
    sl = tuple(slice(0, d.shape[i]) if i == d.fsdp_dim else slice(None)
               for i in range(b4.ndim))
    upd = af[sl] - b4[sl]
    exp = -1e-2 * np.asarray(g)
    errs.append(float(np.max(np.abs(upd - exp)) /
                (np.max(np.abs(exp)) + 1e-12)))
print("RESULT", json.dumps({{"max_rel_err": max(errs),
                             "loss_dist": float(m["loss"]),
                             "loss_oracle": float(loss_l)}}))
"""


@pytest.mark.parametrize("arch,data,model,nodes,batch", [
    ("smollm-135m", 4, 2, 1, 8),        # head-sharded, fsdp=4
    ("smollm-135m", 1, 8, 1, 2),        # seq-sharded attention (tp=8 > heads)
    ("deepseek-moe-16b", 2, 4, 1, 4),   # MoE expert-parallel + prelude
    ("mamba2-1.3b", 4, 2, 2, 8),        # SSM, 2 consensus nodes (alg none)
    ("whisper-small", 2, 4, 1, 4),      # enc-dec, seq-sharded
])
def test_distributed_grads_match_oracle(arch, data, model, nodes, batch):
    r = run_sub(GRAD_ORACLE_BODY.format(arch=arch, data=data, model=model,
                                        nodes=nodes, batch=batch))
    assert abs(r["loss_dist"] - r["loss_oracle"]) < 2e-4
    assert r["max_rel_err"] < 5e-3


def test_adc_matches_allreduce_and_dgd():
    """The paper's headline claim, live on the LLM trainer: ADC-DGD's loss
    curve tracks uncompressed DGD and allreduce closely."""
    body = """
cfg = reduced(get_config("smollm-135m"))
mesh = make_cpu_mesh(data=4, model=2)
ds = SyntheticLMDataset(cfg.vocab_size, 64, 8, n_shards=4)
out = {}
for alg, kw in [("adc_dgd", dict(quant_mode="adaptive")),
                ("dgd", {}), ("allreduce", {})]:
    setup = LT.build_train_setup(cfg, mesh, consensus_nodes=2, algorithm=alg,
                                 lr=1.0, global_batch=8,
                                 track_consensus_error=(alg != "allreduce"),
                                 **kw)
    state = LT.init_train_state(setup, jax.random.PRNGKey(0))
    losses, cerr = [], []
    for step in range(40):
        b = jax.device_put(ds.global_batch_arrays(step), setup.batch_sharding)
        state, m = setup.train_step(state, b)
        losses.append(float(m["loss"]))
        if "consensus_err" in m:
            cerr.append(float(m["consensus_err"]))
    out[alg] = {"losses": losses, "cerr": cerr}
print("RESULT", __import__("json").dumps(out))
"""
    r = run_sub(body, timeout=2400)
    import numpy as np
    for alg in ("adc_dgd", "dgd", "allreduce"):
        ls = r[alg]["losses"]
        # learning: mean of the last 5 clearly below the first 5 (the data
        # stream is fresh-random per step, so single-point compares are noisy)
        assert np.mean(ls[-5:]) < np.mean(ls[:5]) - 0.05, alg
    # ADC-DGD tracks the uncompressed baselines within a tight margin
    diff_adc = abs(np.mean(r["adc_dgd"]["losses"][-5:])
                   - np.mean(r["allreduce"]["losses"][-5:]))
    assert diff_adc < 0.2
    # consensus error stays bounded for adc
    assert max(r["adc_dgd"]["cerr"]) < 10.0


def test_adc_applies_local_step_once():
    """On one batch given to every node, every replica sees the same
    gradients, so ADC-DGD must follow allreduce up to quantization noise:
    its wire carries x^k - x_tilde, and the local step is added once,
    after the combine.  (Carrying x^{k+1/2} - x_tilde instead applies the
    step twice: then ADC-DGD is 0.3 nats ahead at step 1 and matches
    allreduce at twice the learning rate.)"""
    body = """
cfg = reduced(get_config("smollm-135m"))
mesh = make_cpu_mesh(data=4, model=1)
one = SyntheticLMDataset(cfg.vocab_size, 64, 8, seed=0).global_batch_arrays(0)
out = {}
for alg in ("adc_dgd", "allreduce"):
    setup = LT.build_train_setup(cfg, mesh, consensus_nodes=4, algorithm=alg,
                                 optimizer="adam", lr=1e-3, global_batch=32,
                                 seq_len=64, seed=0)
    state = LT.init_train_state(setup, 0)
    b = jax.device_put({k: np.tile(v, (4, 1)) for k, v in one.items()},
                       setup.batch_sharding)
    losses = []
    for step in range(5):
        state, m = setup.train_step(state, b)
        losses.append(float(m["loss"]))
    out[alg] = losses
print("RESULT", json.dumps(out))
"""
    r = run_sub(body)
    import numpy as np
    adc, ar = np.asarray(r["adc_dgd"]), np.asarray(r["allreduce"])
    assert ar[-1] < ar[0] - 1.0, ar             # it trains
    assert np.max(np.abs(adc - ar)) < 5e-3, (adc, ar)


def test_pallas_kernels_in_distributed_exchange():
    """use_pallas=True (interpret) must match the jnp reference path exactly
    (same PRNG noise -> identical codes -> identical trajectories)."""
    body = """
cfg = reduced(get_config("smollm-135m"))
mesh = make_cpu_mesh(data=2, model=1)
ds = SyntheticLMDataset(cfg.vocab_size, 32, 4, n_shards=2)
finals = {}
for use_pallas in (False, True):
    setup = LT.build_train_setup(cfg, mesh, consensus_nodes=2,
                                 algorithm="adc_dgd", quant_mode="adaptive",
                                 lr=2e-2, global_batch=4,
                                 use_pallas=use_pallas)
    state = LT.init_train_state(setup, jax.random.PRNGKey(0))
    for step in range(3):
        b = jax.device_put(ds.global_batch_arrays(step), setup.batch_sharding)
        state, m = setup.train_step(state, b)
    leaf = jax.device_get(jax.tree_util.tree_leaves(state["params"])[0])
    finals[use_pallas] = leaf
import numpy as np
diff = float(np.max(np.abs(finals[True] - finals[False])))
print("RESULT", __import__("json").dumps({"max_diff": diff}))
"""
    r = run_sub(body, timeout=2400)
    assert r["max_diff"] < 1e-6


def test_replicated_leaves_stay_identical_across_model_ranks():
    """Norm weights (tp-replicated) must remain bit-identical on every model
    rank after ADC-DGD steps (shared quantization noise across tp)."""
    body = """
cfg = reduced(get_config("smollm-135m"))
mesh = make_cpu_mesh(data=2, model=4)
ds = SyntheticLMDataset(cfg.vocab_size, 32, 4, n_shards=2)
setup = LT.build_train_setup(cfg, mesh, consensus_nodes=2,
                             algorithm="adc_dgd", quant_mode="adaptive",
                             lr=2e-2, global_batch=4)
state = LT.init_train_state(setup, jax.random.PRNGKey(0))
for step in range(3):
    b = jax.device_put(ds.global_batch_arrays(step), setup.batch_sharding)
    state, m = setup.train_step(state, b)
# fetch the final_norm leaf from every device and compare across model ranks
leaf = state["params"]["final_norm"]
import numpy as np
shards = [np.asarray(s.data) for s in leaf.addressable_shards]
devs = [s.device for s in leaf.addressable_shards]
ok = all(np.array_equal(shards[0], sh) or sh.shape != shards[0].shape
         for sh in shards)
# shards along data differ (different nodes), along model must be equal;
# compare pairs with identical data coordinate:
coords = {}
for s in leaf.addressable_shards:
    idx = s.index
    coords.setdefault(str(idx), []).append(np.asarray(s.data))
same = all(all(np.array_equal(v[0], vi) for vi in v) for v in coords.values())
print("RESULT", __import__("json").dumps({"identical": bool(same)}))
"""
    r = run_sub(body, timeout=2400)
    assert r["identical"]


def test_timevarying_ring_stride_schedule_trains():
    """DESIGN.md §Topology schedules: ring_strides=(1,2) re-wires the node
    ring every schedule_period steps (lax.switch over static ppermute
    wirings); ADC-DGD must keep training and stay consensus-bounded."""
    body = """
cfg = reduced(get_config("smollm-135m"))
mesh = make_cpu_mesh(data=4, model=2)
ds = SyntheticLMDataset(cfg.vocab_size, 32, 8, n_shards=4)
setup = LT.build_train_setup(cfg, mesh, consensus_nodes=4, algorithm="adc_dgd",
                             quant_mode="adaptive", lr=2e-2, global_batch=8,
                             ring_strides=(1, 2), schedule_period=2,
                             track_consensus_error=True)
state = LT.init_train_state(setup, jax.random.PRNGKey(0))
losses = []
for step in range(12):
    b = jax.device_put(ds.global_batch_arrays(step), setup.batch_sharding)
    state, m = setup.train_step(state, b)
    losses.append(float(m["loss"]))
print("RESULT", __import__("json").dumps(
    {"losses": losses, "cerr": float(m["consensus_err"])}))
"""
    r = run_sub(body, timeout=2400)
    import numpy as np
    assert np.mean(r["losses"][-3:]) < np.mean(r["losses"][:3])
    assert r["cerr"] < 10.0


def test_multipod_mesh_trains():
    """3-axis (pod, data, model) mesh: consensus ring spans pods."""
    body = """
cfg = reduced(get_config("smollm-135m"))
mesh = make_cpu_mesh(data=2, model=2, pod=2)
ds = SyntheticLMDataset(cfg.vocab_size, 32, 4, n_shards=4)
setup = LT.build_train_setup(cfg, mesh, consensus_nodes=2, algorithm="adc_dgd",
                             quant_mode="adaptive", lr=2e-2, global_batch=4,
                             track_consensus_error=True)
state = LT.init_train_state(setup, jax.random.PRNGKey(0))
losses = []
for step in range(8):
    b = jax.device_put(ds.global_batch_arrays(step), setup.batch_sharding)
    state, m = setup.train_step(state, b)
    losses.append(float(m["loss"]))
print("RESULT", __import__("json").dumps(
    {"losses": losses, "cerr": float(m["consensus_err"])}))
"""
    r = run_sub(body, timeout=2400)
    assert r["losses"][-1] < r["losses"][0] + 0.05
    assert r["cerr"] < 10.0


@pytest.mark.parametrize("model", [1, 2])
def test_train_step_collectives_pinned(model):
    """The packed ADC step's collectives on model=1 and model=2 meshes:
    exactly 2 ring ppermutes over ``data``; on model > 1 also exactly one
    pmax over ``model``, of the model-replicated leaves only (it retypes
    them invariant over ``model``, launch.train._invariant_over_model)."""
    body = """
from repro.models.params import local_block_shape
cfg = reduced(get_config("smollm-135m"))
mesh = make_cpu_mesh(data=2, model=%d)
setup = LT.build_train_setup(cfg, mesh, consensus_nodes=2,
                             algorithm="adc_dgd", global_batch=4)
ctx = setup.ctx
n_rep = sum(int(np.prod(local_block_shape(d, ctx.tp, ctx.fsdp)))
            for d in jax.tree.leaves(setup.defs.storage,
                                     is_leaf=lambda x: isinstance(x, ParamDef))
            if d.tp_dim is None)
batch = {k: jax.ShapeDtypeStruct((4, 32), jnp.int32)
         for k in ("tokens", "labels")}
jaxpr = jax.make_jaxpr(setup.train_step)(setup.state_shape, batch)

def eqns(j):
    for e in getattr(j, "jaxpr", j).eqns:
        yield e
        for p in e.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    yield from eqns(sub)

ring = [e for e in eqns(jaxpr) if e.primitive.name == "ppermute"
        and e.params["axis_name"] == ("data",)]
pmax = [e for e in eqns(jaxpr) if e.primitive.name == "pmax"
        and tuple(e.invars[0].aval.shape) == (n_rep,)]
print("RESULT", json.dumps({"ring": len(ring), "pmax": len(pmax)}))
""" % model
    r = run_sub(body)
    assert r["ring"] == 2, r
    assert r["pmax"] == (1 if model > 1 else 0), r
