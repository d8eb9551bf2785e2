"""Flat wire-packing subsystem (core.wire + the packed consensus exchange).

Covered invariants:
  * WireLayout pack -> unpack == identity for every config's parameter tree
    (reduced sizes) and for synthetic odd-shaped mixed-dtype trees
  * the packed buffer is bit-for-bit the concatenation of the per-leaf
    blockified buffers (the foundation of packed/per-leaf equivalence)
  * packed `_adc_exchange` == per-leaf reference bit-for-bit over a
    multi-leaf, oddly-shaped, mixed-dtype tree, on all compressor modes,
    including the stride-schedule m_agg resync step (subprocess, 4 devices)
  * the packed exchange issues EXACTLY 2 ring ppermute collectives per step
    regardless of leaf count (counted in the traced jaxpr)
  * packed compressed-DGD == per-leaf reference bit-for-bit
  * ChunkedLayout split algebra: tile-aligned contiguous cover, ragged
    tails, chunk-count clamping
  * pipelined (chunked double-buffered) exchange == monolithic packed
    bit-for-bit for chunk counts {1, 2, 4, 7-with-ragged-tail}, including
    the epoch-boundary m_agg resync and fixed-mode overflow accounting
  * the pipelined exchange issues EXACTLY 2 x pipeline_chunks ppermutes
    per step with wire bytes unchanged vs packed (jaxpr + metrics)
  * the push-sum transport (directed-ring topology) keeps the collective
    count UNCHANGED — the fp32 weight rides the flat payload as a 4-byte
    trailer, never as its own ppermute pair — on packed AND pipelined
    chunk counts {1, 2, 4, 7}, with or without the loss machinery; the
    per-leaf reference ships the weight as its own pair (4n + 2)
  * directed-ring push-sum: packed == per-leaf == pipelined bit-for-bit,
    including the (1,2)-stride schedule's epoch-boundary resync
  * the async one-step-stale exchange (wire_packing="async"): staleness=0
    is bit-for-bit the eager packed path; staleness=1 still traces EXACTLY
    2 ppermutes per step; the epoch-boundary resync drains the in-flight
    payload BEFORE rebuilding m_agg; smoke matrix over int8 / mixed plan
    with parameterized top-k / directed-ring push-sum

Multi-device tests spawn a fresh python with XLA_FLAGS (jax locks the device
count at first init; the main pytest process must keep seeing ONE device).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import wire
from repro.kernels import ops as kops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ODD_TREE_SPECS = {
    "w": ((3, 37), jnp.float32),
    "b": ((513,), jnp.bfloat16),
    "scalar": ((), jnp.float32),
    "deep": {"m": ((7, 11, 2), jnp.float32), "n": ((1, 129), jnp.bfloat16)},
}


def _make_tree(specs, key):
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))
    ks = jax.random.split(key, len(leaves))
    vals = [jax.random.normal(k, shape, jnp.float32).astype(dt)
            for k, (shape, dt) in zip(ks, leaves)]
    return jax.tree_util.tree_unflatten(treedef, vals)


# ---------------------------------------------------------------------------
# WireLayout: layout algebra + round trips
# ---------------------------------------------------------------------------

def test_layout_roundtrip_odd_tree():
    tree = _make_tree(ODD_TREE_SPECS, jax.random.PRNGKey(0))
    layout = wire.WireLayout.for_tree(tree)
    assert layout.n_leaves == 5
    assert layout.n_rows % 32 == 0        # lane/tile aligned overall
    packed = layout.pack(tree)
    assert packed.shape == (layout.n_rows, kops.BLOCK)
    assert packed.dtype == jnp.float32
    back = layout.unpack(packed)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(tree),
            jax.tree_util.tree_leaves_with_path(back)):
        assert a.dtype == b.dtype, pa
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), err_msg=str(pa))


def test_pack_matches_per_leaf_blockify_rows():
    """The bit-identity foundation: every leaf's row range in the packed
    buffer equals the leading rows of its standalone ``kops.blockify``
    (quantization blocks never span leaves), and the only extra content is
    zero padding (row-granular per leaf + the TILE_N tail)."""
    tree = _make_tree(ODD_TREE_SPECS, jax.random.PRNGKey(1))
    layout = wire.WireLayout.for_tree(tree)
    packed = layout.pack(tree)
    for i, leaf in enumerate(jax.tree_util.tree_leaves(tree)):
        slot = layout.slots[i]
        blockified = kops.blockify(leaf.astype(jnp.float32).reshape(-1))
        np.testing.assert_array_equal(
            np.asarray(layout.leaf_rows(packed, i)),
            np.asarray(blockified[: slot.n_rows]))
        # the rows blockify adds beyond the layout's are pure zero padding
        assert not np.any(np.asarray(blockified[slot.n_rows:]))
    # TILE_N alignment lives in the buffer tail, not inside leaves
    assert layout.n_rows % kops.TILE_N == 0
    assert layout.n_rows - layout.n_data_rows < kops.TILE_N
    assert not np.any(np.asarray(packed[layout.n_data_rows:]))


def test_layout_rejects_mismatched_tree():
    tree = _make_tree(ODD_TREE_SPECS, jax.random.PRNGKey(2))
    layout = wire.WireLayout.for_tree(tree)
    bad = dict(tree)
    bad["w"] = jnp.zeros((4, 37))
    with pytest.raises(ValueError, match="leaf shape"):
        layout.pack(bad)
    with pytest.raises(ValueError, match="packed shape"):
        layout.unpack(jnp.zeros((layout.n_rows + 32, kops.BLOCK)))


@pytest.mark.parametrize("arch", [
    "smollm-135m", "qwen3-0.6b", "yi-9b", "gemma2-9b", "mamba2-1.3b",
    "deepseek-moe-16b", "granite-moe-3b-a800m", "jamba-v0.1-52b",
    "chameleon-34b", "whisper-small",
])
def test_layout_roundtrip_every_config_tree(arch):
    """pack -> unpack == identity on every config's (reduced) storage tree."""
    from repro.configs import get_config, reduced
    from repro.models import transformer as T
    from repro.models.params import ParamDef, materialize_logical
    from repro.models.sharding import local_context
    cfg = reduced(get_config(arch))
    defs = T.build_defs(cfg, local_context())
    params = materialize_logical(defs.storage, jax.random.PRNGKey(3))
    layout = wire.WireLayout.for_tree(params)
    assert layout.n_leaves == len(jax.tree_util.tree_leaves(params))
    back = layout.unpack(layout.pack(params))
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(back)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_wire_bytes_and_collectives_accounting():
    """collectives_per_step / wire_bytes_per_step: packed is leaf-count
    independent, per-leaf pays 4/leaf; payload bytes identical."""
    from repro.core.distributed import ConsensusConfig, ConsensusRuntime
    from repro.models.sharding import ParallelContext
    ctx = ParallelContext(tp=1, data_size=4, n_nodes=4)
    tree = _make_tree(ODD_TREE_SPECS, jax.random.PRNGKey(4))
    layout = wire.WireLayout.for_tree(tree)
    packed = ConsensusRuntime(ConsensusConfig(algorithm="adc_dgd"), ctx)
    per_leaf = ConsensusRuntime(
        ConsensusConfig(algorithm="adc_dgd", wire_packing="per_leaf"), ctx)
    assert packed.collectives_per_step(layout.n_leaves) == 2.0
    assert packed.collectives_per_step(1000) == 2.0
    assert per_leaf.collectives_per_step(layout.n_leaves) == 4.0 * 5
    b = packed.wire_bytes_per_step(layout.n_elements, layout=layout)
    assert b == 2 * layout.n_rows * kops.payload_width()
    # the per-leaf path ships TILE_N-padded per-leaf buffers -> more bytes
    b_pl = per_leaf.wire_bytes_per_step(layout.n_elements, layout=layout)
    rows_pl = sum(kops.padded_block_rows(s.size) for s in layout.slots)
    assert b_pl == 2 * rows_pl * kops.payload_width()
    assert b_pl > b
    # multi-stride schedules amortize the fp32 resync exchange
    sched = ConsensusRuntime(ConsensusConfig(
        algorithm="adc_dgd", ring_strides=(1, 2), schedule_period=4), ctx)
    assert sched.collectives_per_step(layout.n_leaves) == 2.0 + 2.0 / 4
    assert sched.wire_bytes_per_step(layout.n_elements, layout=layout) > b


def test_config_rejects_bad_wire_packing():
    from repro.core.distributed import ConsensusConfig
    with pytest.raises(ValueError, match="wire_packing"):
        ConsensusConfig(wire_packing="flat")
    with pytest.raises(ValueError, match="pipeline_chunks"):
        ConsensusConfig(wire_packing="pipelined", pipeline_chunks=0)


# ---------------------------------------------------------------------------
# ChunkedLayout: split algebra + chunk-view kernel equivalence
# ---------------------------------------------------------------------------

def test_chunked_layout_split_algebra():
    """Chunks are contiguous, tile-aligned, cover the buffer exactly;
    ragged splits put the extra tiles in the leading chunks; requested
    counts beyond the tile count clamp."""
    tree = {"big": jnp.zeros((10 * kops.TILE_N * kops.BLOCK - 5,))}
    layout = wire.WireLayout.for_tree(tree)
    n_tiles = layout.n_rows // kops.TILE_N
    assert n_tiles == 10
    for k in (1, 2, 4, 7, 10):
        cl = wire.ChunkedLayout.split(layout, k)
        assert cl.n_chunks == k
        row = 0
        for start, rows in cl.bounds:
            assert start == row and rows % kops.TILE_N == 0 and rows > 0
            row += rows
        assert row == layout.n_rows
    # ragged: 10 tiles over 7 chunks -> three 2-tile chunks then four 1-tile
    cl = wire.ChunkedLayout.split(layout, 7)
    assert [r // kops.TILE_N for _, r in cl.bounds] == [2, 2, 2, 1, 1, 1, 1]
    # clamp: more chunks than tiles
    assert wire.ChunkedLayout.split(layout, 64).n_chunks == n_tiles
    with pytest.raises(ValueError, match="pipeline_chunks"):
        wire.ChunkedLayout.split(layout, 0)
    # concat round-trips slice_rows
    buf = jnp.arange(layout.n_rows * layout.block, dtype=jnp.float32
                     ).reshape(layout.n_rows, layout.block)
    cl = wire.ChunkedLayout.split(layout, 7)
    back = cl.concat([cl.slice_rows(buf, c) for c in range(cl.n_chunks)])
    np.testing.assert_array_equal(np.asarray(back), np.asarray(buf))


@pytest.mark.parametrize("use_pallas", [False, True])
def test_chunk_view_kernels_match_monolithic(use_pallas):
    """quantize_payload / dequant_combine_payload chunk views (static
    row_offset/n_rows over full-height operands) == the same rows of the
    whole-buffer launch, bit-for-bit, on both kernel paths."""
    rng = np.random.default_rng(11)
    n, b = 10 * kops.TILE_N, kops.BLOCK
    y = jnp.asarray(rng.standard_normal((n, b)), jnp.float32)
    noise = jnp.asarray(rng.random((n, b)), jnp.float32)
    xt = jnp.asarray(rng.standard_normal((n, b)), jnp.float32)
    m = jnp.asarray(rng.standard_normal((n, b)), jnp.float32)

    class _L:
        n_rows, block = n, b

    for step in (None, jnp.float32(1e-2)):
        full = kops.quantize_payload(y, noise, fixed_step=step,
                                     use_pallas=use_pallas)
        dq_full = kops.dequant_combine_payload(
            full, full, full, xt, m, 0.5, 0.25, jnp.float32(1.0),
            use_pallas=use_pallas)
        for k in (2, 7):
            cl = wire.ChunkedLayout.split(_L, k)
            parts = [kops.quantize_payload(y, noise, fixed_step=step,
                                           use_pallas=use_pallas,
                                           row_offset=s, n_rows=r)
                     for s, r in cl.bounds]
            np.testing.assert_array_equal(
                np.asarray(jnp.concatenate(parts)), np.asarray(full))
            dq_parts = [
                kops.dequant_combine_payload(
                    # in-flight payloads arrive chunk-height off the wire;
                    # the persistent shadows stay full-height (in-kernel view)
                    cl.slice_rows(full, c), cl.slice_rows(full, c),
                    cl.slice_rows(full, c), xt, m, 0.5, 0.25,
                    jnp.float32(1.0), use_pallas=use_pallas,
                    row_offset=s, n_rows=r)
                for c, (s, r) in enumerate(cl.bounds)]
            for i in range(3):
                np.testing.assert_array_equal(
                    np.asarray(jnp.concatenate([p[i] for p in dq_parts])),
                    np.asarray(dq_full[i]))


# ---------------------------------------------------------------------------
# Multi-device: packed exchange vs per-leaf reference (subprocess)
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

        def make_tree(key, n_extra=0, big=0):
            ks = jax.random.split(key, 6 + n_extra)
            tree = {
                "w": jax.random.normal(ks[0], (4, 3, 37), jnp.float32),
                "b": jax.random.normal(ks[1], (4, 513), jnp.bfloat16),
                "scalar": jax.random.normal(ks[2], (4, 1), jnp.float32),
                "deep": {"m": jax.random.normal(ks[3], (4, 7, 11, 2),
                                                jnp.float32)},
            }
            if big:
                # one leaf large enough that the packed buffer spans many
                # TILE_N tiles (so multi-chunk pipelines have real splits)
                tree["big"] = jax.random.normal(ks[4], (4, big), jnp.float32)
            for i in range(n_extra):
                tree[f"x{i}"] = jax.random.normal(ks[6 + i], (4, 64 + i),
                                                  jnp.float32)
            return tree

        from repro.core.distributed import _device_key

        def shared_noise(rt, xh, k):
            # one uniform buffer from the device-folded key, injected into
            # BOTH wire paths so the transformation is compared bit-for-bit
            # (column count is plan-specific: top-k consumes a second
            # BLOCK-wide region for its selection race)
            layout = wire.WireLayout.for_tree(xh)
            dk = _device_key(jax.random.fold_in(jax.random.PRNGKey(7), k),
                             rt.ctx)
            return jax.random.uniform(
                dk, (layout.n_rows, rt.noise_cols_for(layout)),
                jnp.float32)

        def build(rt, tree):
            pspec = jax.tree.map(lambda a: P("data"), tree)
            cons_spec = {"x_tilde": P("data", None, None),
                         "m_agg": P("data", None, None)}
            if rt.cfg.push_sum_enabled:
                cons_spec["ps_w"] = P("data", None)
                cons_spec["ps_nbr"] = P("data", None)
            if rt.cfg.wire_packing == "async":
                for fk in wire.INFLIGHT_KEYS:
                    cons_spec[fk] = P("data", None)
            init = lambda p: jax.tree.map(lambda a: a[None], rt.init_state(p))
            init_f = jax.jit(jax.shard_map(
                init, mesh=mesh, in_specs=(pspec,), out_specs=cons_spec,
                check_vma=False))
            def step(xp, xh, s, k):
                s = jax.tree.map(lambda a: a[0], s)
                xn, s2, m = rt.exchange(xp, xh, s, k, jax.random.PRNGKey(7),
                                        noise=shared_noise(rt, xh, k))
                return xn, jax.tree.map(lambda a: a[None], s2)
            step_f = jax.jit(jax.shard_map(
                step, mesh=mesh,
                in_specs=(pspec, pspec, cons_spec, P()),
                out_specs=(pspec, cons_spec), check_vma=False))
            return init_f, step_f

        def trajectory(cfg_kw, tree, steps=5):
            rt = ConsensusRuntime(ConsensusConfig(**cfg_kw), ctx)
            init_f, step_f = build(rt, tree)
            st = init_f(tree) if cfg_kw["algorithm"] == "adc_dgd" else {}
            if cfg_kw["algorithm"] != "adc_dgd":
                pspec = jax.tree.map(lambda a: P("data"), tree)
                def step(xp, xh, s, k):
                    xn, s2, m = rt.exchange(xp, xh, s, k,
                                            jax.random.PRNGKey(7),
                                            noise=shared_noise(rt, xh, k))
                    return xn, s2
                step_f = jax.jit(jax.shard_map(
                    step, mesh=mesh, in_specs=(pspec, pspec, P(), P()),
                    out_specs=(pspec, P()), check_vma=False))
                st = 0.0
            x = tree
            for k in range(1, steps + 1):
                xh = jax.tree.map(
                    lambda a: (a.astype(jnp.float32)
                               + 0.01 * k).astype(a.dtype), x)
                x, st = step_f(x, xh, st, jnp.asarray(k, jnp.int32))
            return jax.device_get((x, st))

        def max_diff(a, b):
            la = jax.tree_util.tree_leaves(a)
            lb = jax.tree_util.tree_leaves(b)
            assert len(la) == len(lb)
            return max(float(np.max(np.abs(
                np.asarray(x, np.float64) - np.asarray(y, np.float64))))
                if np.asarray(x).size else 0.0
                for x, y in zip(la, lb))
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


def test_packed_equals_per_leaf_all_modes():
    """Bit-for-bit packed == per-leaf over a multi-leaf, oddly-shaped,
    mixed-dtype tree: adaptive & fixed quantization, static ring AND the
    (1,2)-stride schedule including its epoch-boundary m_agg resync."""
    body = """
tree = make_tree(jax.random.PRNGKey(0))
out = {}
for qm in ("adaptive", "fixed"):
    for strides, period, tag in (((1,), 1, "static"), ((1, 2), 2, "sched")):
        kw = dict(algorithm="adc_dgd", quant_mode=qm, fixed_step0=1e-2,
                  ring_strides=strides, schedule_period=period)
        a = trajectory({**kw, "wire_packing": "packed"}, tree, steps=5)
        b = trajectory({**kw, "wire_packing": "per_leaf"}, tree, steps=5)
        out[f"{qm}_{tag}"] = max_diff(a, b)
print("RESULT", json.dumps(out))
"""
    r = run_sub(body)
    for k, v in r.items():
        assert v == 0.0, f"{k}: packed vs per-leaf max diff {v}"


def test_compressed_dgd_packed_equals_per_leaf():
    body = """
tree = make_tree(jax.random.PRNGKey(1))
kw = dict(algorithm="compressed_dgd", fixed_step0=1e-2)
a = trajectory({**kw, "wire_packing": "packed"}, tree, steps=4)
b = trajectory({**kw, "wire_packing": "per_leaf"}, tree, steps=4)
print("RESULT", json.dumps({"max_diff": max_diff(a[0], b[0])}))
"""
    r = run_sub(body)
    assert r["max_diff"] == 0.0


def test_packed_exchange_issues_exactly_two_ppermutes():
    """Acceptance: the packed path traces EXACTLY 2 ring ppermute eqns per
    step regardless of leaf count; the per-leaf reference traces
    4 x n_leaves."""
    body = """
import sys
sys.path.insert(0, os.path.join(%r, "benchmarks"))
from consensus_step import count_eqns

def count_for(mode, n_extra):
    tree = make_tree(jax.random.PRNGKey(2), n_extra=n_extra)
    rt = ConsensusRuntime(ConsensusConfig(algorithm="adc_dgd",
                                          wire_packing=mode), ctx)
    init_f, step_f = build(rt, tree)
    st = init_f(tree)
    xh = jax.tree.map(lambda a: a, tree)
    jaxpr = jax.make_jaxpr(step_f)(tree, xh, st, jnp.asarray(2, jnp.int32))
    return count_eqns(jaxpr, "ppermute"), len(jax.tree_util.tree_leaves(tree))

out = {}
for n_extra in (0, 7):
    for mode in ("packed", "per_leaf"):
        n_pp, n_leaves = count_for(mode, n_extra)
        out[f"{mode}_{n_leaves}"] = n_pp
print("RESULT", json.dumps(out))
""" % REPO
    r = run_sub(body)
    leaf_counts = sorted(int(k.split("_")[1]) for k in r if "packed" in k)
    assert len(set(leaf_counts)) == 2          # genuinely different trees
    for k, v in r.items():
        mode, n_leaves = k.rsplit("_", 1)
        if mode == "packed":
            assert v == 2, f"{k}: {v} ppermutes (want 2, leaf-independent)"
        else:
            assert v == 4 * int(n_leaves), f"{k}: {v} ppermutes"


def test_pipelined_equals_packed_all_chunk_counts():
    """Acceptance: the chunked double-buffered exchange is bit-for-bit the
    monolithic packed path for every chunk count in {1, 2, 4,
    7-with-ragged-tail} — params AND shadows — on adaptive & fixed
    quantization, including the (1,2)-stride schedule's epoch-boundary
    m_agg resync, with the fixed-mode overflow accounting identical too
    (clip counts are integers, so chunk-summed accounting is exact)."""
    body = """
def build_m(rt, tree):
    # like build(), but also surfaces the per-device overflow_frac metric
    pspec = jax.tree.map(lambda a: P("data"), tree)
    cons_spec = {"x_tilde": P("data", None, None),
                 "m_agg": P("data", None, None)}
    init = lambda p: jax.tree.map(lambda a: a[None], rt.init_state(p))
    init_f = jax.jit(jax.shard_map(
        init, mesh=mesh, in_specs=(pspec,), out_specs=cons_spec, check_vma=False))
    def step(xp, xh, s, k):
        s = jax.tree.map(lambda a: a[0], s)
        xn, s2, m = rt.exchange(xp, xh, s, k, jax.random.PRNGKey(7),
                                noise=shared_noise(rt, xh, k))
        return (xn, jax.tree.map(lambda a: a[None], s2),
                m["overflow_frac"][None])
    step_f = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(pspec, pspec, cons_spec, P()),
        out_specs=(pspec, cons_spec, P("data")), check_vma=False))
    return init_f, step_f

def trajectory_m(cfg_kw, tree, steps=5):
    rt = ConsensusRuntime(ConsensusConfig(**cfg_kw), ctx)
    init_f, step_f = build_m(rt, tree)
    st = init_f(tree)
    x, overflows = tree, []
    for k in range(1, steps + 1):
        xh = jax.tree.map(
            lambda a: (a.astype(jnp.float32) + 0.01 * k).astype(a.dtype), x)
        x, st, ov = step_f(x, xh, st, jnp.asarray(k, jnp.int32))
        overflows.append(ov)
    return jax.device_get((x, st, overflows))

# big leaf -> 10+ tiles so 7 chunks is a genuinely ragged split
tree = make_tree(jax.random.PRNGKey(0), big=150000)
layout = wire.WireLayout.for_tree(jax.tree.map(lambda a: a[0], tree))
out = {"n_tiles": layout.n_rows // 32}
for qm in ("adaptive", "fixed"):
    for strides, period, tag in (((1,), 1, "static"), ((1, 2), 2, "sched")):
        kw = dict(algorithm="adc_dgd", quant_mode=qm, fixed_step0=1e-2,
                  ring_strides=strides, schedule_period=period)
        ref = trajectory_m({**kw, "wire_packing": "packed"}, tree)
        for chunks in (1, 2, 4, 7):
            got = trajectory_m({**kw, "wire_packing": "pipelined",
                                "pipeline_chunks": chunks}, tree)
            out[f"{qm}_{tag}_c{chunks}"] = max_diff(got, ref)
print("RESULT", json.dumps(out))
"""
    r = run_sub(body)
    n_tiles = r.pop("n_tiles")
    assert n_tiles >= 8, f"tree too small for ragged 7-chunk split: {n_tiles}"
    assert len(r) == 2 * 2 * 4
    for k, v in r.items():
        assert v == 0.0, f"{k}: pipelined vs packed max diff {v}"


@pytest.mark.parametrize("codec_name", ["int4", "topk"])
def test_codec_pipelined_equals_packed_all_chunk_counts(codec_name):
    """Acceptance (DESIGN.md §Wire codecs): the sub-byte and sparse codecs
    run end-to-end through the packed AND pipelined exchanges, bit-identical
    across chunk counts {1, 2, 4, 7-with-ragged-tail} for adaptive and
    fixed quantization — parameters and packed shadows alike — and their
    reported wire bytes/step are >= 2x below int8's."""
    body = """
codec_name = %r
tree = make_tree(jax.random.PRNGKey(4), big=150000)
local = jax.tree.map(lambda a: a[0], tree)
layout = wire.WireLayout.for_tree(local)
out = {"n_tiles": layout.n_rows // 32}
int8_rt = ConsensusRuntime(ConsensusConfig(algorithm="adc_dgd"), ctx)
out["bytes_int8"] = int8_rt.wire_bytes_per_step(layout.n_elements,
                                                layout=layout)
for qm in ("adaptive", "fixed"):
    kw = dict(algorithm="adc_dgd", quant_mode=qm, fixed_step0=1e-2,
              wire_codec=codec_name)
    ref = trajectory({**kw, "wire_packing": "packed"}, tree, steps=4)
    rt = ConsensusRuntime(ConsensusConfig(**kw), ctx)
    out[f"bytes_{qm}"] = rt.wire_bytes_per_step(layout.n_elements,
                                                layout=layout)
    for chunks in (1, 2, 4, 7):
        got = trajectory({**kw, "wire_packing": "pipelined",
                          "pipeline_chunks": chunks}, tree, steps=4)
        out[f"{qm}_c{chunks}"] = max_diff(got, ref)
print("RESULT", json.dumps(out))
""" % codec_name
    r = run_sub(body)
    n_tiles = r.pop("n_tiles")
    assert n_tiles >= 8, f"tree too small for ragged 7-chunk split: {n_tiles}"
    bytes_int8 = r.pop("bytes_int8")
    for qm in ("adaptive", "fixed"):
        assert bytes_int8 / r.pop(f"bytes_{qm}") >= 2.0
    assert len(r) == 2 * 4
    for k, v in r.items():
        assert v == 0.0, f"{codec_name}/{k}: pipelined vs packed diff {v}"


def test_mixed_plan_packed_and_pipelined_bit_identical():
    """Acceptance (DESIGN.md §Wire plans): a mixed per-leaf plan (norms ->
    int2, one leaf -> int4, projections -> int8) runs end-to-end through
    BOTH the packed and pipelined transports, bit-identically across chunk
    counts {1, 2, 4, 7} for adaptive and fixed quantization; the packed
    transport still traces EXACTLY 2 ring ppermutes (one flat
    heterogeneous payload per direction); pipeline chunk counts never drop
    below the plan's codec-run count (chunks never straddle a codec
    change); and the plan ships strictly fewer wire bytes/step than
    uniform int8."""
    body = """
import sys
sys.path.insert(0, os.path.join(%r, "benchmarks"))
from consensus_step import count_eqns

MIX = "mixed:scalar=int2,deep=int2,['b']=int4,*=int8"
tree = make_tree(jax.random.PRNGKey(5), big=150000)
local = jax.tree.map(lambda a: a[0], tree)
layout = wire.WireLayout.for_tree(local)
out = {"n_tiles": layout.n_rows // 32}
int8_rt = ConsensusRuntime(ConsensusConfig(algorithm="adc_dgd"), ctx)
out["bytes_int8"] = int8_rt.wire_bytes_per_step(layout.n_elements,
                                                layout=layout)
rt = ConsensusRuntime(ConsensusConfig(algorithm="adc_dgd",
                                      wire_codec=MIX), ctx)
out["bytes_mixed"] = rt.wire_bytes_per_step(layout.n_elements, layout=layout)
out["n_runs"] = rt.wire_plan_for(layout).n_runs
init_f, step_f = build(rt, tree)
st = init_f(tree)
jaxpr = jax.make_jaxpr(step_f)(tree, tree, st, jnp.asarray(2, jnp.int32))
out["pp_packed"] = count_eqns(jaxpr, "ppermute")
for qm in ("adaptive", "fixed"):
    kw = dict(algorithm="adc_dgd", quant_mode=qm, fixed_step0=1e-2,
              wire_codec=MIX)
    ref = trajectory({**kw, "wire_packing": "packed"}, tree, steps=4)
    for chunks in (1, 2, 4, 7):
        prt = ConsensusRuntime(
            ConsensusConfig(**kw, wire_packing="pipelined",
                            pipeline_chunks=chunks), ctx)
        out[f"eff_{qm}_{chunks}"] = prt.pipeline_chunks_for(layout)
        got = trajectory({**kw, "wire_packing": "pipelined",
                          "pipeline_chunks": chunks}, tree, steps=4)
        out[f"{qm}_c{chunks}"] = max_diff(got, ref)
print("RESULT", json.dumps(out))
""" % REPO
    r = run_sub(body)
    assert r.pop("n_tiles") >= 8
    n_runs = r.pop("n_runs")
    assert n_runs >= 3                      # a genuinely heterogeneous plan
    assert r.pop("pp_packed") == 2          # one flat payload per direction
    assert r.pop("bytes_mixed") < r.pop("bytes_int8")
    for qm in ("adaptive", "fixed"):
        for chunks in (1, 2, 4, 7):
            # snapped chunk counts: each codec run needs >= 1 chunk, and
            # this tree's int8 run has tiles to spare for the budget
            assert r.pop(f"eff_{qm}_{chunks}") == max(chunks, n_runs)
    assert len(r) == 2 * 4
    for k, v in r.items():
        assert v == 0.0, f"mixed-plan {k}: pipelined vs packed diff {v}"


def test_pipelined_collectives_scale_with_chunks():
    """Acceptance: the pipelined exchange traces EXACTLY 2 x pipeline_chunks
    ring ppermutes per step (counted in the jaxpr), its reported
    collectives_per_step metric agrees, the requested chunk count clamps to
    the buffer's tile count, and wire bytes are unchanged vs packed."""
    body = """
import sys
sys.path.insert(0, os.path.join(%r, "benchmarks"))
from consensus_step import count_eqns

tree = make_tree(jax.random.PRNGKey(2), big=150000)
local = jax.tree.map(lambda a: a[0], tree)
layout = wire.WireLayout.for_tree(local)
out = {"n_tiles": layout.n_rows // 32}
packed_rt = ConsensusRuntime(ConsensusConfig(algorithm="adc_dgd"), ctx)
bytes_packed = packed_rt.wire_bytes_per_step(layout.n_elements, layout=layout)
for chunks in (1, 2, 4, 7, 999):
    rt = ConsensusRuntime(
        ConsensusConfig(algorithm="adc_dgd", wire_packing="pipelined",
                        pipeline_chunks=chunks), ctx)
    init_f, step_f = build(rt, tree)
    st = init_f(tree)
    jaxpr = jax.make_jaxpr(step_f)(tree, tree, st, jnp.asarray(2, jnp.int32))
    out[f"pp_{chunks}"] = count_eqns(jaxpr, "ppermute")
    out[f"eff_{chunks}"] = rt.pipeline_chunks_for(layout)
    out[f"acct_{chunks}"] = rt.collectives_per_step(
        layout.n_leaves, n_chunks=rt.pipeline_chunks_for(layout))
    out[f"bytes_{chunks}"] = rt.wire_bytes_per_step(layout.n_elements,
                                                    layout=layout)
out["bytes_packed"] = bytes_packed
print("RESULT", json.dumps(out))
""" % REPO
    r = run_sub(body)
    n_tiles = r.pop("n_tiles")
    bytes_packed = r.pop("bytes_packed")
    for chunks in (1, 2, 4, 7, 999):
        eff = min(chunks, n_tiles)
        assert r[f"eff_{chunks}"] == eff
        assert r[f"pp_{chunks}"] == 2 * eff, \
            f"chunks={chunks}: {r[f'pp_{chunks}']} ppermutes (want {2 * eff})"
        assert r[f"acct_{chunks}"] == 2.0 * eff
        # chunking pays collectives, never bytes
        assert r[f"bytes_{chunks}"] == bytes_packed


def test_push_sum_keeps_exactly_two_ppermutes():
    """Acceptance: the push-sum weight rides the flat payload (a 4-byte
    fp32 trailer on the last transfer unit), so the directed-ring packed
    exchange still traces EXACTLY 2 ring ppermutes — and the pipelined
    exchange exactly 2 x chunks — never an extra collective for the
    weight.  The loss machinery adds no collectives either.  The per-leaf
    reference ships the weight as its own ppermute pair (4 x leaves + 2).
    The byte accounting shows exactly the 2 x 4-byte trailer."""
    body = """
import sys
sys.path.insert(0, os.path.join(%r, "benchmarks"))
from consensus_step import count_eqns
from repro.core import wireplan

tree = make_tree(jax.random.PRNGKey(6), big=150000)
local = jax.tree.map(lambda a: a[0], tree)
layout = wire.WireLayout.for_tree(local)
out = {"n_tiles": layout.n_rows // 32,
       "n_leaves": len(jax.tree_util.tree_leaves(tree)),
       "trailer": wireplan.PUSH_SUM_TRAILER_BYTES}

def pp_for(kw):
    rt = ConsensusRuntime(ConsensusConfig(algorithm="adc_dgd",
                                          topology="directed-ring",
                                          **kw), ctx)
    init_f, step_f = build(rt, tree)
    st = init_f(tree)
    jaxpr = jax.make_jaxpr(step_f)(tree, tree, st, jnp.asarray(2, jnp.int32))
    return count_eqns(jaxpr, "ppermute")

out["packed"] = pp_for({"wire_packing": "packed"})
out["packed_lossy"] = pp_for({"wire_packing": "packed", "link_loss": 0.1})
out["per_leaf"] = pp_for({"wire_packing": "per_leaf"})
for chunks in (1, 2, 4, 7):
    out[f"pipe_{chunks}"] = pp_for({"wire_packing": "pipelined",
                                    "pipeline_chunks": chunks})
sym = ConsensusRuntime(ConsensusConfig(algorithm="adc_dgd"), ctx)
push = ConsensusRuntime(ConsensusConfig(algorithm="adc_dgd",
                                        topology="directed-ring"), ctx)
out["bytes_sym"] = sym.wire_bytes_per_step(layout.n_elements, layout=layout)
out["bytes_push"] = push.wire_bytes_per_step(layout.n_elements, layout=layout)
print("RESULT", json.dumps(out))
""" % REPO
    r = run_sub(body)
    assert r["n_tiles"] >= 8
    assert r["packed"] == 2, \
        f"push-sum packed traced {r['packed']} ppermutes (want 2)"
    assert r["packed_lossy"] == 2, \
        f"loss machinery added collectives: {r['packed_lossy']}"
    assert r["per_leaf"] == 4 * r["n_leaves"] + 2
    for chunks in (1, 2, 4, 7):
        assert r[f"pipe_{chunks}"] == 2 * chunks, \
            f"push-sum pipelined[{chunks}]: {r[f'pipe_{chunks}']} ppermutes"
    # the weight costs exactly one fp32 trailer per direction, nothing more
    assert r["bytes_push"] == r["bytes_sym"] + 2 * r["trailer"]


def test_push_sum_packed_equals_per_leaf_and_pipelined():
    """Acceptance: directed-ring push-sum ADC is bit-for-bit identical
    between the packed transport and the per-leaf reference (the trailer
    bitcast round-trips exactly and both mix the same scalar), on the
    static ring AND the (1,2)-stride schedule including its
    epoch-boundary resync of both m_agg and the neighbor weights.

    Pipelined chunks are held to fp32-ulp agreement instead of exact
    equality: the directed correction's dense decode_payload side branch
    gives the payload buffers a second consumer, and XLA fuses (and so
    fma-contracts) the decode-combine differently for the whole-buffer
    vs chunked programs.  Ablation evidence: replacing the side decode
    with zeros makes every chunk count exactly 0.0, and symmetric
    (non-directed) push-sum pipelining is exactly 0.0 — the ulps come
    from instruction scheduling, not from the transport semantics.
    optimization_barrier at the t-product, the decode inputs, the
    resync rebuild, and the unit payloads was tried and does not pin it.
    """
    body = """
tree = make_tree(jax.random.PRNGKey(7), big=150000)
out = {}
for strides, period, tag in (((1,), 1, "static"), ((1, 2), 2, "sched")):
    kw = dict(algorithm="adc_dgd", quant_mode="fixed", fixed_step0=1e-2,
              topology="directed-ring", ring_strides=strides,
              schedule_period=period)
    ref = trajectory({**kw, "wire_packing": "packed"}, tree, steps=5)
    out[f"{tag}_per_leaf"] = max_diff(
        trajectory({**kw, "wire_packing": "per_leaf"}, tree, steps=5), ref)
    for chunks in (2, 7):
        out[f"{tag}_c{chunks}"] = max_diff(
            trajectory({**kw, "wire_packing": "pipelined",
                        "pipeline_chunks": chunks}, tree, steps=5), ref)
    # the weight state itself must stay exactly 1.0 on the homogeneous ring
    out[f"{tag}_ps_w_dev"] = float(np.max(np.abs(
        np.asarray(ref[1]["ps_w"]) - 1.0)))
print("RESULT", json.dumps(out))
"""
    r = run_sub(body)
    for k, v in r.items():
        if k.endswith("_per_leaf") or k.endswith("_ps_w_dev"):
            assert v == 0.0, f"push-sum {k}: max diff {v}"
        else:
            # pipelined: fusion-dependent fma rounding only (see docstring)
            assert v < 1e-6, f"push-sum {k}: max diff {v}"


def test_padding_rows_stay_zero_through_steps():
    """The layout invariant the packed shadows rely on: padding rows of
    x_tilde / m_agg remain exactly zero across exchange steps."""
    body = """
tree = make_tree(jax.random.PRNGKey(3))
local = jax.tree.map(lambda a: a[0], tree)
layout = wire.WireLayout.for_tree(local)
mask = np.zeros((layout.n_rows * layout.block,), bool)
for slot in layout.slots:
    start = slot.row_start * layout.block
    mask[start + slot.size: (slot.row_start + slot.n_rows) * layout.block] = True
x, st = trajectory(dict(algorithm="adc_dgd", quant_mode="adaptive",
                        wire_packing="packed"), tree, steps=5)
flat_xt = np.asarray(st["x_tilde"]).reshape(4, -1)
flat_m = np.asarray(st["m_agg"]).reshape(4, -1)
pad_max = max(float(np.max(np.abs(flat_xt[:, mask]))) if mask.any() else 0.0,
              float(np.max(np.abs(flat_m[:, mask]))) if mask.any() else 0.0)
print("RESULT", json.dumps({"pad_max": pad_max,
                            "n_pad": int(mask.sum())}))
"""
    r = run_sub(body)
    assert r["n_pad"] > 0
    assert r["pad_max"] == 0.0


# ---------------------------------------------------------------------------
# Async one-step-stale exchange (wire_packing="async")
# ---------------------------------------------------------------------------

def test_async_staleness0_bit_identical_to_packed():
    """Acceptance: wire_packing="async" with staleness=0 is the eager
    packed exchange bit-for-bit — params and both shadow sequences — on
    adaptive & fixed quantization, static ring AND the (1,2)-stride
    schedule.  (The async state carries extra in-flight buffers, so the
    comparison is on params + x_tilde + m_agg, the algorithmic state.)"""
    body = """
tree = make_tree(jax.random.PRNGKey(11))
out = {}
for qm in ("adaptive", "fixed"):
    for strides, period, tag in (((1,), 1, "static"), ((1, 2), 2, "sched")):
        kw = dict(algorithm="adc_dgd", quant_mode=qm, fixed_step0=1e-2,
                  ring_strides=strides, schedule_period=period)
        a = trajectory({**kw, "wire_packing": "packed"}, tree, steps=5)
        b = trajectory({**kw, "wire_packing": "async", "staleness": 0},
                       tree, steps=5)
        out[f"{qm}_{tag}_params"] = max_diff(a[0], b[0])
        out[f"{qm}_{tag}_xt"] = max_diff(a[1]["x_tilde"], b[1]["x_tilde"])
        out[f"{qm}_{tag}_m"] = max_diff(a[1]["m_agg"], b[1]["m_agg"])
print("RESULT", json.dumps(out))
"""
    r = run_sub(body)
    for k, v in r.items():
        assert v == 0.0, f"async staleness=0 vs packed {k}: max diff {v}"


def test_async_exchange_issues_exactly_two_ppermutes():
    """Acceptance: the one-step-stale exchange launches the step-k payload
    and retires the step-(k-1) payload with EXACTLY 2 ring ppermutes per
    step on the static ring — same wire shape as eager packed, so XLA's
    async collective scheduler can overlap both against compute.  Leaf
    count must not change the count."""
    body = """
import sys
sys.path.insert(0, os.path.join(%r, "benchmarks"))
from consensus_step import count_eqns

out = {}
for n_extra in (0, 7):
    tree = make_tree(jax.random.PRNGKey(12), n_extra=n_extra)
    rt = ConsensusRuntime(ConsensusConfig(algorithm="adc_dgd",
                                          wire_packing="async",
                                          staleness=1), ctx)
    init_f, step_f = build(rt, tree)
    st = init_f(tree)
    jaxpr = jax.make_jaxpr(step_f)(tree, tree, st, jnp.asarray(2, jnp.int32))
    n_leaves = len(jax.tree_util.tree_leaves(tree))
    out[str(n_leaves)] = count_eqns(jaxpr, "ppermute")
print("RESULT", json.dumps(out))
""" % REPO
    r = run_sub(body)
    assert len(r) == 2            # genuinely different leaf counts
    for n_leaves, v in r.items():
        assert v == 2, f"async ({n_leaves} leaves): {v} ppermutes (want 2)"


def test_async_resync_drains_inflight_before_rebuild():
    """Acceptance: on the (1,2)-stride schedule the epoch-boundary m_agg
    rebuild happens AFTER the in-flight payload (permuted under the OLD
    stride) is retired — so right after any step, m_agg is exactly the
    side-weighted neighbor sum of the CURRENT x_tilde under the stride
    that step's resync installed.  A rebuild-before-drain bug would mix
    old-stride deltas into the new-stride shadow and break this identity.

    The check starts at the first resync step (step 3 for period=2): the
    synthetic tree gives every node a DIFFERENT x0, so init_state's
    shared-x0 seeding of m_agg is deliberately wrong until the first
    rebuild installs the true neighbor sums — exactly the state of
    affairs the resync exists to repair."""
    body = """
tree = make_tree(jax.random.PRNGKey(13))
cfg = ConsensusConfig(algorithm="adc_dgd", quant_mode="fixed",
                      fixed_step0=1e-2, wire_packing="async", staleness=1,
                      ring_strides=(1, 2), schedule_period=2)
rt = ConsensusRuntime(cfg, ctx)
init_f, step_f = build(rt, tree)
st = init_f(tree)
x = tree
out = {"side": cfg.side_weight, "per_step": []}
for k in range(1, 7):
    xh = jax.tree.map(lambda a: (a.astype(jnp.float32) + 0.01 * k)
                      .astype(a.dtype), x)
    x, st = step_f(x, xh, st, jnp.asarray(k, jnp.int32))
    sh = jax.device_get(st)
    xt = np.asarray(sh["x_tilde"], np.float64)[:, 0]
    m = np.asarray(sh["m_agg"], np.float64)[:, 0]
    diffs = {}
    for s in (1, 2):
        pred = cfg.side_weight * (np.roll(xt, s, axis=0)
                                  + np.roll(xt, -s, axis=0))
        diffs[str(s)] = float(np.max(np.abs(m - pred)))
    out["per_step"].append(diffs)
print("RESULT", json.dumps(out))
"""
    r = run_sub(body)
    # every step must be consistent with SOME stride (the active one), and
    # both strides must appear across the schedule (proving real re-wirings
    # were drained through, not a static ring in disguise)
    matched = []
    for i, diffs in enumerate(r["per_step"]):
        if i + 1 < 3:        # before the first resync (see docstring)
            continue
        best = min(diffs, key=lambda s: diffs[s])
        assert diffs[best] < 1e-5, \
            f"step {i + 1}: m_agg matches no stride ({diffs})"
        matched.append(best)
    assert len(set(matched)) == 2, \
        f"schedule never re-wired under async ({matched})"


def test_async_smoke_matrix():
    """Async staleness=1 runs (finite outputs, in-flight buffers carried)
    across the transport matrix: int8, a heterogeneous mixed plan with a
    parameterized top-k fragment, and directed-ring push-sum.  Push-sum
    mass must stay exactly 1.0 on the homogeneous ring — the in-flight
    trailer (pre-encoded to 1.0f at init) conserves it from step 1."""
    body = """
tree = make_tree(jax.random.PRNGKey(14))
out = {}
for tag, kw in (
    ("int8", {}),
    ("mixed", {"wire_codec":
               "mixed:scalar=int2,deep=int4,['b']=topk:k=128,*=int8"}),
    ("push", {"topology": "directed-ring"}),
):
    cfg = dict(algorithm="adc_dgd", quant_mode="fixed", fixed_step0=1e-2,
               wire_packing="async", staleness=1, **kw)
    x, st = trajectory(cfg, tree, steps=4)
    finite = all(bool(np.isfinite(np.asarray(l, np.float64)).all())
                 for l in jax.tree_util.tree_leaves(x))
    out[f"{tag}_finite"] = finite
    out[f"{tag}_fly_bytes"] = int(np.asarray(st["fly_self"]).shape[-1])
    if "topology" in kw:
        out["push_ps_w_dev"] = float(np.max(np.abs(
            np.asarray(st["ps_w"]) - 1.0)))
print("RESULT", json.dumps(out))
"""
    r = run_sub(body)
    for k, v in r.items():
        if k.endswith("_finite"):
            assert v, f"async {k}: non-finite params"
    assert r["mixed_fly_bytes"] != r["int8_fly_bytes"]   # real mixed plan
    assert r["push_fly_bytes"] == r["int8_fly_bytes"] + 4  # fp32 trailer
    assert r["push_ps_w_dev"] == 0.0, \
        f"async push-sum drifted: {r['push_ps_w_dev']}"


def test_telemetry_off_is_free():
    """Acceptance (telemetry satellite): with ``telemetry=False`` (the
    default) the step jaxpr is BIT-IDENTICAL to a telemetry-less build —
    no extra metric outputs, no extra ops, exactly 2 ring ppermutes —
    on the packed AND async transports.  The telemetry-off metric
    keyset is pinned so new always-on metrics cannot sneak in."""
    body = """
import sys
sys.path.insert(0, os.path.join(%r, "benchmarks"))
from consensus_step import count_eqns

tree = make_tree(jax.random.PRNGKey(4))
out = {}

def jaxpr_and_keys(cfg_kw):
    rt = ConsensusRuntime(ConsensusConfig(**cfg_kw), ctx)
    init_f, step_f = build(rt, tree)
    st = init_f(tree)
    keys_box = {}
    pspec = jax.tree.map(lambda a: P("data"), tree)
    cons_spec = {"x_tilde": P("data", None, None),
                 "m_agg": P("data", None, None)}
    if rt.cfg.wire_packing == "async":
        for fk in wire.INFLIGHT_KEYS:
            cons_spec[fk] = P("data", None)
    def probe(xp, xh, s, k):
        s = jax.tree.map(lambda a: a[0], s)
        xn, s2, m = rt.exchange(xp, xh, s, k, jax.random.PRNGKey(7))
        keys_box["keys"] = sorted(m.keys())
        return xn, jax.tree.map(lambda a: a[None], s2)
    probe_f = jax.shard_map(
        probe, mesh=mesh, in_specs=(pspec, pspec, cons_spec, P()),
        out_specs=(pspec, cons_spec), check_vma=False)
    jaxpr = jax.make_jaxpr(probe_f)(tree, tree, st,
                                    jnp.asarray(2, jnp.int32))
    return jaxpr, keys_box["keys"]

for mode in ("packed", "async"):
    kw = dict(algorithm="adc_dgd", wire_packing=mode)
    j_default, keys_default = jaxpr_and_keys(kw)
    j_off, _ = jaxpr_and_keys({**kw, "telemetry": False})
    out[f"{mode}_default_eq_off"] = str(j_default) == str(j_off)
    out[f"{mode}_ppermutes"] = count_eqns(j_default, "ppermute")
    out[f"{mode}_metric_keys"] = keys_default
    cfg = ConsensusConfig(**kw)
    out[f"{mode}_extra_keys"] = list(cfg.telemetry_metric_keys())
    on = ConsensusConfig(**kw, telemetry=True)
    _, keys_on = jaxpr_and_keys({**kw, "telemetry": True})
    out[f"{mode}_on_adds_exactly"] = (
        sorted(keys_on) == sorted(keys_default
                                  + list(on.telemetry_metric_keys())))
print("RESULT", json.dumps(out))
""" % REPO
    r = run_sub(body)
    pinned = ["collectives_per_step", "overflow_frac", "residual_norm",
              "wire_bytes_per_step"]
    for mode in ("packed", "async"):
        assert r[f"{mode}_default_eq_off"], \
            f"{mode}: default != explicit telemetry=False jaxpr"
        assert r[f"{mode}_ppermutes"] == 2, r
        # frozen telemetry-off metric keyset: any always-on addition
        # must consciously update this pin (it costs every user)
        assert r[f"{mode}_metric_keys"] == pinned, r
        assert r[f"{mode}_extra_keys"] == [], r
        assert r[f"{mode}_on_adds_exactly"], r
