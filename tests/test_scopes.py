"""The program's named scopes and host spans (PERF.md §3, DESIGN.md §13).

  * the compiled one-node train step carries ``embed``, ``layers``,
    ``attention``, ``attention/core``, ``mlp``, ``head_loss`` and
    ``optimizer`` in its HLO ``op_name`` metadata, with forward, backward
    and recomputed ops told apart by the transform JAX writes there
  * the scopes cost nothing: with ``jax.named_scope`` a null context the
    compiled HLO, debug information stripped, is the same program (one-node
    step and the packed four-node ring)
  * the exchange runs under ``exchange/noise``, ``exchange/encode``,
    ``exchange/permute`` and ``exchange/combine`` on the packed,
    pipelined and async transports; each pipelined chunk has its own
    encode and combine; every Pallas wire kernel carries its ``name=``
  * ``SyntheticLMDataset.global_batch_arrays`` writes one ``input.build``
    host span per call into a profiler trace, and ``train.py
    --profile-dir/--profile-steps`` writes a trace

Multi-device cases run in one python with 4 forced host devices (jax locks
the device count at first init; this process keeps seeing one device).
"""
import contextlib
import glob
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODEL_SCOPES = ("embed", "layers", "attention", "attention/core", "mlp",
                "head_loss", "optimizer")
EXCHANGE_SCOPES = ("exchange/noise", "exchange/encode", "exchange/permute",
                   "exchange/combine")


def op_paths(hlo_text: str) -> list[str]:
    """Every ``op_name`` of an HLO text (each of XLA's ``;``-joined ones)."""
    return [p for s in re.findall(r'op_name="([^"]*)"', hlo_text)
            for p in s.split(";")]


def has_scope(paths, scope: str) -> bool:
    """A path holds ``scope`` as whole components (also inside a transform
    wrapper such as ``transpose(jvp(head_loss))``)."""
    pat = re.compile(rf"(^|[/(]){re.escape(scope)}($|[/)])")
    return any(pat.search(p) for p in paths)


def direction(path: str) -> str:
    if "rematted_computation" in path:
        return "recompute"
    if "transpose(" in path:
        return "bwd"
    return "fwd" if "jvp(" in path else "update"


def canonical(hlo_text: str) -> str:
    """The HLO text without debug information (each op's ``metadata`` and
    the source tables it points to), its instructions renamed in order of
    first appearance: XLA numbers names in the order it creates them,
    which the scopes may shift (``transpose.448`` for ``transpose.444``)."""
    text = re.sub(r", metadata=\{[^}]*\}", "", hlo_text)
    text = re.sub(r"\n(?:FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(?:\d+ .*\n)*", "\n", text)
    names: dict[str, str] = {}
    return re.sub(r"%([\w.\-]+)",
                  lambda m: "%" + names.setdefault(m.group(1),
                                                   f"v{len(names)}"), text)


def one_node_hlo() -> str:
    """The compiled one-node ADC-DGD train step of a tiny Llama config:
    1,024-token rows, so the attention's inner checkpoint is on."""
    from repro.configs import get_config, reduced
    from repro.launch import train as LT
    from repro.launch.mesh import make_cpu_mesh
    cfg = reduced(get_config("smollm-135m"), d_model=64)
    setup = LT.build_train_setup(cfg, make_cpu_mesh(), consensus_nodes=1,
                                 algorithm="adc_dgd", optimizer="adam",
                                 global_batch=2, seq_len=1024)
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        setup.state_shape, setup.state_sharding)
    batch = {k: jax.ShapeDtypeStruct((2, 1024), jnp.int32, sharding=sh)
             for k, sh in setup.batch_sharding.items()}
    return setup.train_step.lower(state, batch).compile().as_text()


@pytest.fixture(scope="module")
def one_node():
    return one_node_hlo()


def test_train_step_carries_model_scopes_and_directions(one_node):
    paths = op_paths(one_node)
    for s in MODEL_SCOPES:
        assert has_scope(paths, s), s
    by_dir = {d: [p for p in paths if direction(p) == d]
              for d in ("fwd", "bwd", "recompute", "update")}
    for d in ("fwd", "bwd", "recompute"):
        assert by_dir[d], d
        # the attention's core runs in every direction of the step
        assert has_scope(by_dir[d], "attention/core"), d
    # the optimizer runs outside the differentiated loss
    opt = [p for p in paths if has_scope([p], "optimizer")]
    assert opt and all(direction(p) == "update" for p in opt)
    # the program names no direction itself
    assert not has_scope(paths, "forward")
    assert not has_scope(paths, "backward")


# ---------------------------------------------------------------------------
# four devices: the ring, the exchange's scopes and the kernels' names
# ---------------------------------------------------------------------------

RING_BODY = r"""
import contextlib, os, re, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.configs import get_config, reduced
from repro.core.distributed import ConsensusConfig, ConsensusRuntime
from repro.launch import train as LT
from repro.launch.mesh import make_cpu_mesh
from repro.models.sharding import ParallelContext
from repro.core import wire

out = {}

def ring_hlo():
    cfg = reduced(get_config("smollm-135m"), d_model=64)
    setup = LT.build_train_setup(cfg, make_cpu_mesh(data=4), consensus_nodes=4,
                                 algorithm="adc_dgd", wire_packing="packed",
                                 use_pallas=True, global_batch=4, seq_len=64)
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        setup.state_shape, setup.state_sharding)
    batch = {k: jax.ShapeDtypeStruct((4, 64), jnp.int32, sharding=sh)
             for k, sh in setup.batch_sharding.items()}
    return setup.train_step.lower(state, batch).compile().as_text()

scoped = ring_hlo()
real = jax.named_scope
jax.named_scope = lambda name: contextlib.nullcontext()
try:
    plain = ring_hlo()
finally:
    jax.named_scope = real
out["ring_scoped"], out["ring_plain"] = scoped, plain
out["ring_paths"] = sorted(set(
    p for s in re.findall(r'op_name="([^"]*)"', scoped) for p in s.split(";")
    if "exchange" in p))

# the exchange alone, kernels in interpret mode (check_vma off, as the
# wire tests run it), without injected noise
mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
ctx = ParallelContext(tp=1, data_size=4, n_nodes=4, in_shard_map=True)
k = jax.random.split(jax.random.PRNGKey(0), 2)
tree = {"w": jax.random.normal(k[0], (4, 3, 37), jnp.float32),
        "big": jax.random.normal(k[1], (4, 150000), jnp.float32)}

def pallas_names(jaxpr, acc):
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            acc.append(str(e.params["name"]))
        for v in e.params.values():
            for c in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(c, "eqns"):
                    pallas_names(c, acc)
                elif hasattr(getattr(c, "jaxpr", None), "eqns"):
                    pallas_names(c.jaxpr, acc)
    return acc

for mode in ("packed", "pipelined", "async"):
    rt = ConsensusRuntime(ConsensusConfig(
        algorithm="adc_dgd", wire_packing=mode, pipeline_chunks=3,
        use_pallas=True), ctx)
    pspec = jax.tree.map(lambda a: P("data"), tree)
    cons_spec = {"x_tilde": P("data", None, None),
                 "m_agg": P("data", None, None)}
    if mode == "async":
        for fk in wire.INFLIGHT_KEYS:
            cons_spec[fk] = P("data", None)
    init_f = jax.jit(jax.shard_map(
        lambda p: jax.tree.map(lambda a: a[None], rt.init_state(p)),
        mesh=mesh, in_specs=(pspec,), out_specs=cons_spec, check_vma=False))
    st = init_f(tree)

    def step(xp, xh, s, kk):
        s = jax.tree.map(lambda a: a[0], s)
        xn, s2, _ = rt.exchange(xp, xh, s, kk, jax.random.PRNGKey(7))
        return xn, jax.tree.map(lambda a: a[None], s2)

    step_f = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(pspec, pspec, cons_spec, P()),
        out_specs=(pspec, cons_spec), check_vma=False))
    kk = jnp.asarray(2, jnp.int32)
    text = step_f.lower(tree, tree, st, kk).compile().as_text()
    paths = sorted(set(p for s in re.findall(r'op_name="([^"]*)"', text)
                       for p in s.split(";")))
    out[mode] = {
        "paths": [p for p in paths if "exchange" in p],
        "kernels": pallas_names(jax.make_jaxpr(step_f)(tree, tree, st,
                                                       kk).jaxpr, []),
        "chunks": len(rt.wire_plan_for(
            wire.WireLayout.for_tree(jax.tree.map(lambda a: a[0], tree)))
            .transfer_units(3 if mode == "pipelined" else None)),
    }
print("RESULT", json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(RING_BODY)],
                          capture_output=True, text=True, timeout=1200,
                          env=env, cwd=REPO)
    if proc.returncode != 0:
        raise AssertionError(f"subprocess failed:\n{proc.stderr[-4000:]}")
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise AssertionError(f"no RESULT line:\n{proc.stdout[-2000:]}")


@pytest.mark.parametrize("program", ["one_node", "ring_packed"])
def test_scopes_change_only_metadata(program, monkeypatch, one_node,
                                     request):
    if program == "ring_packed":
        r = request.getfixturevalue("four_devices")
        # the scopes are in the text, and only in its debug information
        assert r["ring_scoped"] != r["ring_plain"]
        assert canonical(r["ring_scoped"]) == canonical(r["ring_plain"])
        assert has_scope(r["ring_paths"], "exchange/encode")
        return
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = one_node_hlo()
    assert plain != one_node
    assert not has_scope(op_paths(plain), "attention")
    assert canonical(plain) == canonical(one_node)


#: the Pallas kernels each int8 transport launches (fixed quantization step)
INT8_KERNELS = {"int8_encode", "int8_combine"}


@pytest.mark.parametrize("mode", ["packed", "pipelined", "async"])
def test_exchange_scopes_and_kernel_names(four_devices, mode):
    r = four_devices[mode]
    paths = r["paths"]
    for s in EXCHANGE_SCOPES:
        assert has_scope(paths, s), (mode, s)
    if mode == "pipelined":
        assert r["chunks"] == 3
        for c in range(r["chunks"]):
            for stage in ("encode", "combine"):
                assert has_scope(paths, f"exchange/{stage}/chunk{c}"), (
                    stage, c)
    else:
        assert not any("chunk" in p for p in paths), mode
    # every kernel launch carries its fixed name, and runs in its stage
    assert r["kernels"] and set(r["kernels"]) == INT8_KERNELS, r["kernels"]
    enc = [p for p in paths if "/int8_encode/" in p]
    comb = [p for p in paths if "/int8_combine/" in p]
    assert enc and all("exchange/encode" in p for p in enc)
    assert comb and all("exchange/combine" in p for p in comb)


# ---------------------------------------------------------------------------
# host spans on the profiler's clock
# ---------------------------------------------------------------------------

def _host_events(trace_dir: str, name: str) -> int:
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return sum(e.name == name for plane in ProfileData.from_file(path).planes
               if plane.name.startswith("/host:")
               for line in plane.lines for e in line.events)


def test_input_build_is_a_host_span(tmp_path):
    from repro.data import SyntheticLMDataset
    ds = SyntheticLMDataset(64, 16, 4, n_shards=2)
    ds.global_batch_arrays(0)               # outside the trace: no span
    with jax.profiler.trace(str(tmp_path)):
        a = ds.global_batch_arrays(1)
        b = ds.global_batch_arrays(2)
    assert a["tokens"].shape == b["tokens"].shape == (4, 16)
    assert _host_events(str(tmp_path), "input.build") == 2


def test_train_cli_profile_steps_write_a_trace(tmp_path, capsys):
    from repro.launch import train as LT
    out = tmp_path / "profile"
    LT.main(["--reduced", "--steps", "3", "--batch", "2", "--seq", "32",
             "--profile-dir", str(out), "--profile-steps", "1:3"])
    assert "[profile] steps 1:3" in capsys.readouterr().out
    assert _host_events(str(out), "input.transfer") == 2
    with pytest.raises(SystemExit):
        LT.main(["--reduced", "--steps", "2", "--profile-dir", str(out),
                 "--profile-steps", "2:4"])
    with pytest.raises(SystemExit):
        LT.main(["--profile-steps", "3:3"])
