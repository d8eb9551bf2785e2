"""Two-level hierarchical consensus (core.hierarchy + the runtime threading).

Covered invariants (DESIGN.md §14):
  * HierarchySpec parsing/validation: int / "pods=P" / passthrough specs,
    the divisibility contract, the pod psum-group layout, and the fp32
    ring-all-reduce inner byte model
  * topology.hierarchical_mixing: W_outer (x) (1/m) 11^T is doubly
    stochastic and its spectral beta EQUALS the outer ring's (the pod ring
    alone governs the consensus rate)
  * consensus.run_hierarchical degeneracies: pods == n is bit-identical to
    the flat run (same algorithm object, same key, same cumulative bytes);
    pods == 1 is the exact single-chain GD recurrence on the pod-mean
    objective (ADCDGD.init's first gradient step + the scan)
  * run_hierarchical pods=2 converges and reports the per-level byte split
  * the DISTRIBUTED runtime (subprocess, 4 host devices): pod members stay
    bitwise replicas on the packed AND async transports; pods == n is
    bit-identical to the flat ring path; pods == 1 is bit-identical to
    algorithm="allreduce"; the jaxpr pin — the hierarchical step traces
    EXACTLY 2 ring ppermutes (the outer exchange) with the inner psum
    present
  * ConsensusConfig/ConsensusRuntime guards: hierarchy rejects non-adc
    algorithms, directed/push-sum outer rings, the per-leaf wire path, and
    pod counts that do not tile the node set

Multi-device tests spawn a fresh python with XLA_FLAGS (jax locks the
device count at first init), mirroring tests/test_wire.py.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import consensus, problems, topology
from repro.core.compression import IdentityCompressor, RandomizedRounding
from repro.core.hierarchy import HierarchySpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# HierarchySpec algebra
# ---------------------------------------------------------------------------

def test_spec_parsing_and_validation():
    assert HierarchySpec.from_spec(2).pods == 2
    assert HierarchySpec.from_spec("pods=4").pods == 4
    s = HierarchySpec(pods=3)
    assert HierarchySpec.from_spec(s) is s
    with pytest.raises(ValueError, match=">= 1"):
        HierarchySpec(pods=0)
    with pytest.raises(ValueError, match="unrecognized hierarchy spec"):
        HierarchySpec.from_spec("rings=2")
    with pytest.raises(ValueError, match="unrecognized hierarchy spec"):
        HierarchySpec.from_spec("pods=two")


def test_spec_pod_size_divisibility():
    assert HierarchySpec(pods=2).pod_size(8) == 4
    assert HierarchySpec(pods=8).pod_size(8) == 1
    with pytest.raises(ValueError, match="does not divide"):
        HierarchySpec(pods=3).pod_size(8)


def test_pod_psum_groups_same_fsdp_rank_only():
    """Each inner psum group holds one pod's members at ONE fsdp rank —
    devices at different fsdp ranks hold different shards and must never
    be averaged together."""
    groups = HierarchySpec(pods=2).pod_psum_groups(4, fsdp=2)
    # 2 pods x 2 fsdp ranks; device index = node * fsdp + f
    assert groups == ((0, 2), (1, 3), (4, 6), (5, 7))
    flat = [d for g in groups for d in g]
    assert sorted(flat) == list(range(8))
    # singleton pods: every group is one device (no inner level)
    groups1 = HierarchySpec(pods=4).pod_psum_groups(4, fsdp=1)
    assert all(len(g) == 1 for g in groups1)


def test_inner_bytes_model():
    # fp32 ring all-reduce: 2 (m-1)/m * 4 * n_elements per member per step
    assert HierarchySpec(pods=4).inner_bytes_per_step(1000, 4) == 0.0
    assert HierarchySpec(pods=2).inner_bytes_per_step(1000, 4) == \
        2.0 * (1 / 2) * 4.0 * 1000
    assert HierarchySpec(pods=1).inner_bytes_per_step(1000, 4) == \
        2.0 * (3 / 4) * 4.0 * 1000


# ---------------------------------------------------------------------------
# Kronecker mixing
# ---------------------------------------------------------------------------

def test_hierarchical_mixing_structure_and_beta():
    outer = topology.ring(4, 0.5)
    m = 3
    hier = topology.hierarchical_mixing(outer, m)
    w = np.asarray(hier.w)
    assert w.shape == (12, 12)
    np.testing.assert_allclose(w.sum(axis=0), 1.0, atol=1e-12)
    np.testing.assert_allclose(w.sum(axis=1), 1.0, atol=1e-12)
    # Kronecker structure: block (p, q) is W_outer[p, q] / m everywhere
    wo = np.asarray(outer.w)
    np.testing.assert_allclose(
        w, np.kron(wo, np.full((m, m), 1.0 / m)), atol=1e-12)
    # the spectrum is eig(W_outer) plus zeros -> beta is the POD ring's
    assert topology.spectral_beta(w) == pytest.approx(
        topology.spectral_beta(wo), abs=1e-9)


def test_hierarchical_mixing_degenerate_pod_size_one():
    outer = topology.ring(4, 0.5)
    np.testing.assert_array_equal(
        np.asarray(topology.hierarchical_mixing(outer, 1).w),
        np.asarray(outer.w))


# ---------------------------------------------------------------------------
# Reference rule: consensus.run_hierarchical
# ---------------------------------------------------------------------------

def _quad_problem(n=4, dim=6, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.uniform(0.5, 2.0, size=(n, dim))
    b = rng.normal(size=(n, dim))
    return problems.quadratic_problem(a, b)


def test_run_hierarchical_pods_n_is_flat_run():
    """Singleton pods: run_hierarchical IS the flat compressed-ring run —
    same trajectory, same metrics, same cumulative bytes (no inner level)."""
    prob = _quad_problem()
    kw = dict(compressor=RandomizedRounding(delta=0.05), stepsize=consensus.StepSize(0.05, 0.5),
              gamma=1.0, key=3)
    hier = consensus.run_hierarchical(prob, prob.n_nodes, 30, **kw)
    flat = consensus.run(
        consensus.ADCDGD(mixing=topology.ring(prob.n_nodes, 0.5),
                         compressor=RandomizedRounding(delta=0.05),
                         stepsize=consensus.StepSize(0.05, 0.5), gamma=1.0),
        prob, 30, key=3)
    for name in ("grad_norm", "consensus", "obj", "bytes"):
        np.testing.assert_array_equal(hier[name], flat[name], err_msg=name)
    np.testing.assert_array_equal(hier["x_final"], flat["x_final"])
    assert hier["pods"] == prob.n_nodes and hier["pod_size"] == 1
    assert not np.any(hier["bytes_inner"])


def test_run_hierarchical_pods_1_is_exact_mean_gd():
    """One pod spanning every node: the compressed outer wire vanishes and
    the rule collapses to exact GD on the pod-mean objective — replicated
    here as the literal recurrence (ADCDGD.init takes the k=1 step BEFORE
    the scan, so n_steps steps = n_steps + 1 gradient evaluations)."""
    import jax.numpy as jnp
    prob = _quad_problem()
    n_steps = 25
    ss = consensus.StepSize(0.05, 0.5)
    out = consensus.run_hierarchical(prob, 1, n_steps, stepsize=ss, key=9)
    pp = consensus.pod_problem(prob, 1)
    x = jnp.zeros((1, prob.dim))
    x = x - ss(1.0) * pp.grad_fn(x)
    for k in range(1, n_steps + 1):
        x = x - ss(float(k)) * pp.grad_fn(x)
    ref = np.broadcast_to(np.asarray(x), (prob.n_nodes, prob.dim))
    np.testing.assert_array_equal(out["x_final"], ref)
    # consensus is exact at every step; zero compressed outer bytes
    assert float(np.max(out["consensus"])) == 0.0
    assert not np.any(out["bytes_outer"])
    assert np.all(np.diff(out["bytes_inner"]) > 0)


def test_run_hierarchical_pods_2_converges_with_byte_split():
    prob = _quad_problem(n=4)
    out = consensus.run_hierarchical(
        prob, 2, 300, compressor=RandomizedRounding(delta=0.05),
        stepsize=consensus.StepSize(0.1, 0.5), gamma=1.0, key=5)
    assert out["pods"] == 2 and out["pod_size"] == 2
    # converges on the pod-mean problem
    assert float(np.mean(out["grad_norm"][-10:])) \
        < 0.05 * float(out["grad_norm"][0])
    # pod members are exact replicas in the expanded final iterate
    xf = out["x_final"]
    assert xf.shape == (4, prob.dim)
    np.testing.assert_array_equal(xf[0::2], xf[1::2])
    # per-level byte split: total == outer + inner; inner follows the
    # fp32 all-reduce model, billed for every node every step
    np.testing.assert_array_equal(out["bytes"],
                                  out["bytes_outer"] + out["bytes_inner"])
    spec = HierarchySpec(pods=2)
    per_step = spec.inner_bytes_per_step(prob.dim, 4) * 4
    assert out["bytes_inner"][0] == pytest.approx(per_step)


def test_pod_problem_grad_is_pod_mean():
    import jax.numpy as jnp
    prob = _quad_problem(n=4, dim=5)
    pp = consensus.pod_problem(prob, 2)
    assert pp.n_nodes == 2 and pp.dim == 5
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 5)))
    g = np.asarray(pp.grad_fn(x))
    full = np.asarray(prob.grad_fn(jnp.repeat(x, 2, axis=0)))
    np.testing.assert_allclose(g, full.reshape(2, 2, 5).mean(axis=1),
                               atol=1e-12)
    # global metrics rescale by 1/m so grad-norm traces stay comparable
    xb = jnp.asarray(np.random.default_rng(2).normal(size=(5,)))
    assert float(pp.global_obj(xb)) == pytest.approx(
        float(prob.global_obj(xb)) / 2)


# ---------------------------------------------------------------------------
# Config / runtime guards (host process, no devices needed)
# ---------------------------------------------------------------------------

def test_config_guards():
    from repro.core.distributed import ConsensusConfig
    cfg = ConsensusConfig(algorithm="adc_dgd", hierarchy="pods=2")
    assert isinstance(cfg.hierarchy, HierarchySpec)
    assert cfg.hierarchy.pods == 2
    with pytest.raises(ValueError, match="does not support it"):
        ConsensusConfig(algorithm="allreduce", hierarchy=2)
    with pytest.raises(ValueError, match="symmetric outer"):
        ConsensusConfig(algorithm="adc_dgd", hierarchy=2,
                        topology="directed-ring")
    with pytest.raises(ValueError, match="per-leaf reference"):
        ConsensusConfig(algorithm="adc_dgd", hierarchy=2,
                        wire_packing="per_leaf")
    with pytest.raises(ValueError, match="unrecognized hierarchy spec"):
        ConsensusConfig(algorithm="adc_dgd", hierarchy="rings=2")


def test_runtime_guard_divisibility():
    from repro.core.distributed import ConsensusConfig, ConsensusRuntime
    from repro.models.sharding import ParallelContext
    ctx = ParallelContext(tp=1, data_size=4, n_nodes=4, in_shard_map=True)
    with pytest.raises(ValueError, match="does not divide"):
        ConsensusRuntime(
            ConsensusConfig(algorithm="adc_dgd", hierarchy=3), ctx)


# ---------------------------------------------------------------------------
# Distributed runtime: pod identity, degeneracies, jaxpr pin (subprocess)
# ---------------------------------------------------------------------------

def run_sub(body: str, timeout: int = 1500) -> dict:
    prelude = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core import wire
        from repro.core.distributed import ConsensusConfig, ConsensusRuntime
        from repro.models.sharding import ParallelContext

        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        ctx = ParallelContext(tp=1, data_size=4, n_nodes=4, in_shard_map=True)

        def make_tree(key):
            # shared-x0 contract (DESIGN.md §14): every node starts from
            # the same parameters, so pod members are replicas from step 0
            ks = jax.random.split(key, 3)
            def rep(a):
                return jnp.broadcast_to(a[None], (4,) + a.shape).astype(a.dtype)
            return {
                "w": rep(jax.random.normal(ks[0], (3, 37), jnp.float32)),
                "b": rep(jax.random.normal(ks[1], (513,), jnp.bfloat16)),
                "deep": {"m": rep(jax.random.normal(ks[2], (7, 11, 2),
                                                    jnp.float32))},
            }

        def build(rt, tree):
            pspec = jax.tree.map(lambda a: P("data"), tree)
            cons_spec = {"x_tilde": P("data", None, None),
                         "m_agg": P("data", None, None)}
            if rt.cfg.wire_packing == "async":
                for fk in wire.INFLIGHT_KEYS:
                    cons_spec[fk] = P("data", None)
            init = lambda p: jax.tree.map(lambda a: a[None], rt.init_state(p))
            init_f = jax.jit(jax.shard_map(
                init, mesh=mesh, in_specs=(pspec,), out_specs=cons_spec,
                check_vma=False))
            def step(xp, xh, s, k):
                s = jax.tree.map(lambda a: a[0], s)
                xn, s2, m = rt.exchange(xp, xh, s, k, jax.random.PRNGKey(7))
                return xn, jax.tree.map(lambda a: a[None], s2)
            step_f = jax.jit(jax.shard_map(
                step, mesh=mesh, in_specs=(pspec, pspec, cons_spec, P()),
                out_specs=(pspec, cons_spec), check_vma=False))
            return init_f, step_f

        def trajectory(cfg_kw, tree, steps=5):
            rt = ConsensusRuntime(ConsensusConfig(**cfg_kw), ctx)
            init_f, step_f = build(rt, tree)
            if cfg_kw.get("algorithm", "adc_dgd") == "adc_dgd":
                st = init_f(tree)
            else:
                pspec = jax.tree.map(lambda a: P("data"), tree)
                def step(xp, xh, s, k):
                    xn, s2, m = rt.exchange(xp, xh, s, k,
                                            jax.random.PRNGKey(7))
                    return xn, s2
                step_f = jax.jit(jax.shard_map(
                    step, mesh=mesh, in_specs=(pspec, pspec, P(), P()),
                    out_specs=(pspec, P()), check_vma=False))
                st = 0.0
            x = tree
            for k in range(1, steps + 1):
                # node-dependent perturbation: pods genuinely average
                xh = jax.tree.map(
                    lambda a: (a.astype(jnp.float32) + 0.01 * k
                               + 0.005 * jnp.arange(a.shape[0],
                                                    dtype=jnp.float32)
                               .reshape((-1,) + (1,) * (a.ndim - 1))
                               ).astype(a.dtype), x)
                x, st = step_f(x, xh, st, jnp.asarray(k, jnp.int32))
            return jax.device_get((x, st))

        def pod_gap(x, m):
            # max |member - member| within each pod (bitwise-replica check)
            return max(float(np.max(np.abs(
                np.asarray(v, np.float64).reshape((-1, m)
                    + np.asarray(v).shape[1:])[:, :1]
                - np.asarray(v, np.float64).reshape((-1, m)
                    + np.asarray(v).shape[1:]))))
                for v in jax.tree_util.tree_leaves(x))

        def max_diff(a, b):
            la = jax.tree_util.tree_leaves(a)
            lb = jax.tree_util.tree_leaves(b)
            assert len(la) == len(lb)
            return max(float(np.max(np.abs(
                np.asarray(x, np.float64) - np.asarray(y, np.float64))))
                if np.asarray(x).size else 0.0
                for x, y in zip(la, lb))

        def count_eqns(jaxpr, prim_name):
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
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", prelude + textwrap.dedent(body)],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)
    if proc.returncode != 0:
        raise AssertionError(f"subprocess failed:\n{proc.stderr[-4000:]}")
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise AssertionError(f"no RESULT line in output:\n{proc.stdout[-2000:]}")


def test_runtime_hierarchy_packed_identities():
    """Packed transport: pods=2 keeps pod members bitwise identical; the
    degenerate configs collapse exactly — pods=4 (singleton pods) is the
    flat ring bit-for-bit, pods=1 is algorithm="allreduce" bit-for-bit;
    and the jaxpr pin: the hierarchical step traces EXACTLY 2 ring
    ppermutes (outer exchange only) with the inner psum present."""
    out = run_sub("""
        tree = make_tree(jax.random.PRNGKey(0))
        res = {}
        x2, _ = trajectory(dict(algorithm="adc_dgd", fixed_step0=1e-2,
                                hierarchy="pods=2"), tree)
        res["pods2_pod_gap"] = pod_gap(x2, 2)

        flat = trajectory(dict(algorithm="adc_dgd", fixed_step0=1e-2), tree)
        h4 = trajectory(dict(algorithm="adc_dgd", fixed_step0=1e-2,
                             hierarchy="pods=4"), tree)
        res["pods4_vs_flat"] = max_diff(h4, flat)

        ar = trajectory(dict(algorithm="allreduce"), tree)
        h1 = trajectory(dict(algorithm="adc_dgd", fixed_step0=1e-2,
                             hierarchy="pods=1"), tree)
        res["pods1_vs_allreduce"] = max_diff(h1[0], ar[0])

        rt = ConsensusRuntime(ConsensusConfig(algorithm="adc_dgd",
                                              hierarchy="pods=2"), ctx)
        init_f, step_f = build(rt, tree)
        st = init_f(tree)
        jaxpr = jax.make_jaxpr(step_f)(tree, tree, st,
                                       jnp.asarray(2, jnp.int32))
        res["ppermute"] = count_eqns(jaxpr, "ppermute")
        res["psum"] = count_eqns(jaxpr, "psum")
        print("RESULT", json.dumps(res))
    """)
    assert out["pods2_pod_gap"] == 0.0
    assert out["pods4_vs_flat"] == 0.0
    assert out["pods1_vs_allreduce"] == 0.0
    assert out["ppermute"] == 2
    assert out["psum"] >= 1


def test_runtime_hierarchy_async_identities():
    """Async one-step-stale transport under hierarchy: pod members stay
    bitwise identical (the in-flight payload is pod-replicated too) and
    pods=n remains bit-identical to the flat async path."""
    out = run_sub("""
        tree = make_tree(jax.random.PRNGKey(1))
        res = {}
        x2, _ = trajectory(dict(algorithm="adc_dgd", fixed_step0=1e-2,
                                wire_packing="async",
                                hierarchy="pods=2"), tree)
        res["pods2_pod_gap"] = pod_gap(x2, 2)
        flat = trajectory(dict(algorithm="adc_dgd", fixed_step0=1e-2,
                               wire_packing="async"), tree)
        h4 = trajectory(dict(algorithm="adc_dgd", fixed_step0=1e-2,
                             wire_packing="async",
                             hierarchy="pods=4"), tree)
        res["pods4_vs_flat"] = max_diff(h4, flat)
        print("RESULT", json.dumps(res))
    """)
    assert out["pods2_pod_gap"] == 0.0
    assert out["pods4_vs_flat"] == 0.0
