"""The scope reduction (bench/scopes.py) on a hand-made record and op-name
map, and on small traces recorded on a TPU v5e with the compiled step's
op-name map (one chip, smollm-135m: before and after the flash kernels);
a scope the program adds, and a kernel, read by new files alone."""
import gzip
import json
from pathlib import Path

import pytest

from bench import harness as H
from bench import scopes as S
from bench import trace as TR

DATA = Path(__file__).resolve().parent / "data"

OP_MAP = {
    "fusion.1": "jit(step_body)/jvp()/while/body/attention/core/dot_general",
    "while.2": "jit(step_body)/transpose(jvp())/while",
    "fusion.3": "jit(step_body)/transpose(jvp())/while/body/mlp/dot_general",
    "copy.4": "jit(step_body)/transpose(jvp())/checkpoint/"
              "rematted_computation/attention/transpose",
    "fusion.5": "jit(step_body)/optimizer/mul",
    "fusion.6": "jit(step_body)/transpose(jvp(head_loss))/dot_general",
}


def hand_made():
    # window [100, 200); device 0 busy [100, 160) and [180, 195): while.2
    # holds fusion.3 on its line; idle [160, 180) inside the benchmark's
    # input span and the program's input.build, idle [195, 200) in readback
    return {
        "devices": {0: [["fusion.1", 100, 20], ["while.2", 120, 30],
                        ["fusion.3", 125, 15], ["copy.4", 150, 10],
                        ["fusion.5", 180, 10], ["fusion.6", 190, 5],
                        ["fusion.1", 250, 10]]},
        "host": [["window", 100, 100], ["input", 160, 18],
                 ["dispatch", 178, 2], ["readback", 195, 5]],
        "program": [["input.build", 161, 12], ["input.transfer", 174, 3],
                    ["input.build", 300, 5]],
    }


@pytest.mark.parametrize("path,direction", [
    ("jit(step_body)/jvp()/while/body/attention/core/dot_general", "fwd"),
    ("jit(step_body)/jvp(embed)/jit(_take)/gather", "fwd"),
    ("jit(step_body)/transpose(jvp())/while/body/mlp/dot_general", "bwd"),
    ("jit(step_body)/transpose(jvp(head_loss))/dot_general", "bwd"),
    ("jit(step_body)/transpose(jvp())/checkpoint/rematted_computation/"
     "attention/core/exp", "recompute"),
    ("jit(step_body)/transpose(jvp())/checkpoint/attention/core/checkpoint/"
     "rematted_computation/mul", "recompute"),
    ("jit(step_body)/optimizer/mul", "update"),
    ("jit(step_body)/exchange/encode/int8_encode/pallas_call", "update"),
    ("", "update"),
])
def test_direction_rules(path, direction):
    assert S.direction(path) == direction


@pytest.mark.parametrize("path,scope", [
    ("jit(step_body)/jvp()/while/body/attention/core/while/body/exp",
     "attention/core"),
    ("jit(step_body)/jvp()/while/body/attention/dot_general", "attention"),
    ("jit(step_body)/transpose(jvp(head_loss))/jit(take_along_axis)",
     "head_loss"),
    ("jit(step_body)/exchange/encode/chunk1/jit(q)/int8_encode/pallas_call",
     "exchange/encode"),
    ("jit(step_body)/exchange/permute/ppermute", "exchange/permute"),
    ("jit(step_body)/exchange/add", "exchange"),
    ("jit(step_body)/transpose(jvp(layers))/while/body/closed_call/"
     "checkpoint/rematted_computation/attention/core/while/body/exp",
     "attention/core"),
    ("jit(step_body)/transpose(jvp(layers))/while/body/dynamic_slice",
     "layers"),
    # a declared name outside the scope it is nested in elsewhere is a
    # label of its own
    ("jit(step_body)/jvp()/while/body/core/exp", "core"),
    ("jit(step_body)/jvp()/while/body/attn_core/exp", "unscoped"),
    ("jit(step_body)/jvp()/add", "unscoped"),
    ("", "unscoped"),
])
def test_innermost_scope(path, scope):
    assert S.scope(path, S.declared_scopes()) == scope


def test_declared_scopes_are_the_programs():
    """The names the program's source passes to ``jax.named_scope``: every
    scope PERF.md lists, and no name made at run time (``chunk<c>``)."""
    assert S.declared_scopes() == {
        "embed", "layers", "attention", "core", "mlp", "head_loss",
        "optimizer", "exchange", "noise", "encode", "permute", "combine"}


def test_declared_scopes_read_the_source(tmp_path):
    (tmp_path / "a.py").write_text(
        'with jax.named_scope("moe"):\n'
        '    with jax.named_scope( \'router\' ):\n'
        '        with jax.named_scope(f"chunk{c}"):\n'
        '            with jax.named_scope("mlp/gate"):\n'
        '                pass\n')
    (tmp_path / "pkg").mkdir()
    (tmp_path / "pkg" / "b.py").write_text('jax.named_scope("dispatch")')
    assert S.declared_scopes(tmp_path) == {"moe", "router", "mlp", "gate",
                                           "dispatch"}


def test_op_names_from_hlo_text():
    text = "\n".join([
        'HloModule jit_step_body, entry_computation_layout={()->f32[]}',
        '%fused_computation.1 (p: f32[4]) -> f32[4] {',
        '  %p = f32[4]{0} parameter(0)',
        '  ROOT %e = f32[4]{0} exponential(%p), metadata={op_name="x/exp"}',
        '}',
        '%body.3 (t: (f32[4])) -> (f32[4]) {',
        '  %t = (f32[4]{0}) parameter(0)',
        '  %get-tuple-element.5 = f32[4]{0} get-tuple-element(%t), index=0',
        '  %copy.6 = f32[4]{0} copy(%get-tuple-element.5)',
        '  ROOT %tuple.7 = (f32[4]{0}) tuple(%copy.6)',
        '}',
        'ENTRY %main.9 (a: f32[4]) -> f32[4] {',
        '  %a = f32[4]{0} parameter(0), metadata={op_name="state[\'w\']"}',
        '  %fusion.7 = f32[4]{0} fusion(%a), kind=kLoop, '
        'calls=%fused_computation.1, metadata={op_name='
        '"jit(step_body)/jvp()/mlp/exp;jit(step_body)/jvp()/mlp/mul" '
        'stack_frame_id=3}',
        '  %copy-start.2 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%fusion.7)',
        '  %copy-done.2 = f32[4]{0} copy-done(%copy-start.2)',
        '  %while.8 = (f32[4]{0}) while(%copy-done.2), condition=%cond.4, '
        'body=%body.3, metadata={op_name="jit(step_body)/jvp()/layers/while"}',
        '  ROOT %int8_encode.1 = u8[4]{0} custom-call(%copy-done.2), '
        'custom_call_target="tpu_custom_call", frontend_attributes='
        '{kernel_metadata={}}, metadata={op_name="jit(step_body)/exchange/'
        'encode/int8_encode/pallas_call"}',
        '}'])
    m = S.op_names(text)
    assert m["fusion.7"] == "jit(step_body)/jvp()/mlp/exp"
    assert m["e"] == "x/exp"
    # XLA's own copies take the path of the op they copy ...
    assert m["copy-done.2"] == m["copy-start.2"] == m["fusion.7"]
    # ... or, copying a loop's carried value, the loop's
    assert m["copy.6"] == m["get-tuple-element.5"] == m["while.8"]
    declared = S.declared_scopes()
    assert S.scope(m["copy.6"], declared) == "layers"
    assert S.scope(m["int8_encode.1"], declared) == "exchange/encode"
    # an argument of the step is no op of a scope
    assert "a" not in m


def test_scope_reduction_hand_made():
    raw = hand_made()
    base = TR.reduce({"devices": raw["devices"], "host": raw["host"]})
    plain = S.reduce(raw)
    assert plain == base                # no map: bench.trace's numbers only
    r = S.reduce(raw, OP_MAP)
    for k in base:
        assert r[k] == base[k], k       # the map adds, and changes nothing
    ns = 1e-9
    assert r["scopes"] == {
        "fwd/attention/core": pytest.approx(20 * ns),
        "bwd/unscoped": pytest.approx(15 * ns),   # while.2 around fusion.3
        "bwd/mlp": pytest.approx(15 * ns),
        "recompute/attention": pytest.approx(10 * ns),
        "update/optimizer": pytest.approx(10 * ns),
        "bwd/head_loss": pytest.approx(5 * ns),
    }
    assert sum(r["scopes"].values()) == pytest.approx(r["busy_s"])
    assert r["scope_coverage"] == pytest.approx(60 / 75)
    assert r["program_spans"] == {"input.build": [pytest.approx(12 * ns)],
                                  "input.transfer": [pytest.approx(3 * ns)]}
    ops = r["breakdown"]["device_ops"]
    assert ops[0] == ["fusion.1 fwd/attention/core", pytest.approx(20 * ns)]
    assert {n for n, _ in ops} == {
        "fusion.1 fwd/attention/core", "while.2 bwd/unscoped",
        "fusion.3 bwd/mlp", "copy.4 recompute/attention",
        "fusion.5 update/optimizer", "fusion.6 bwd/head_loss"}
    assert r["unscoped_ops"] == [("while.2 bwd/unscoped",
                                  pytest.approx(15 * ns))]
    assert r["breakdown"]["idle_gaps"] == [
        ["input > input.build", pytest.approx(20 * ns)],
        ["readback", pytest.approx(5 * ns)]]


def test_metric_readers():
    r = S.reduce(hand_made(), OP_MAP)
    obs = {"steps": 2, "trace": r}
    want = {"step.forward_ms": 20, "step.backward_ms": 35,
            "step.recompute_ms": 10, "step.optimizer_ms": 10,
            "step.attention_ms": 30, "step.head_loss_ms": 5}
    for name, ns in want.items():
        assert H.read_metric(name, obs) == pytest.approx(ns * 1e-6 / 2), name
    assert H.read_metric("input.build_ms", obs) == pytest.approx(12e-6)
    # without the op-name map (a program or harness without scopes) the
    # readers find nothing and say so
    bare = {"steps": 2, "trace": S.reduce(hand_made())}
    for name in list(want) + ["input.build_ms"]:
        assert H.read_metric(name, bare) is None, name


#: the seven scope readers on the recorded window, as the closed scope
#: list of the harness before the program's declared names replaced it
#: read them (per step of one step)
RECORDED_READINGS = {
    "step.forward_ms": 209.415253, "step.backward_ms": 94.669682,
    "step.recompute_ms": 47.12534700000002,
    "step.optimizer_ms": 6.456519000000001,
    "step.attention_ms": 322.615165, "step.head_loss_ms": 0.0,
    "input.build_ms": 55.136087}


def test_recorded_readings_unchanged():
    with gzip.open(DATA / "v5e_smollm_node_scoped.json.gz", "rt") as f:
        raw = json.load(f)
    raw["devices"] = {int(k): v for k, v in raw["devices"].items()}
    obs = {"steps": 1, "trace": S.reduce(raw, raw["op_names"], 1)}
    assert {m: H.read_metric(m, obs) for m in RECORDED_READINGS} == \
        RECORDED_READINGS


def test_nested_scope_has_its_own_label():
    """A scope the program opens inside a declared one (``mlp/router``)
    reads as its own label; the step readers, which match the first
    component, read as before."""
    raw = hand_made()
    nested = dict(OP_MAP)
    nested["fusion.3"] = ("jit(step_body)/transpose(jvp())/while/body/mlp/"
                          "router/dot_general")
    nested["fusion.6"] = ("jit(step_body)/transpose(jvp(head_loss))/"
                          "softmax/dot_general")
    declared = S.declared_scopes() | {"router", "softmax"}
    before = S.reduce(raw, OP_MAP, declared=declared)
    after = S.reduce(raw, nested, declared=declared)
    assert "bwd/mlp/router" in after["scopes"]
    assert "bwd/mlp" not in after["scopes"]
    assert "bwd/head_loss/softmax" in after["scopes"]
    for name in ("step.forward_ms", "step.backward_ms", "step.recompute_ms",
                 "step.optimizer_ms", "step.attention_ms",
                 "step.head_loss_ms"):
        assert H.read_metric(name, {"steps": 2, "trace": after}) == \
            H.read_metric(name, {"steps": 2, "trace": before}), name


#: a reader of a scope the program does not have yet, and one of a kernel
#: that only a stub configuration counts
NEW_READERS = {
    "moe.router_ms": '''
from bench.scopes import ms_per_step


def read(obs):
    return ms_per_step(obs, lambda d, s: s == "moe/router")
''',
    "moe_gate_roofline": '''
from bench.counts import roofline_share


def read(obs):
    return roofline_share(obs, "moe_gate")
'''}


def test_new_scope_and_kernel_read_by_new_files(tmp_path, monkeypatch):
    """A scope and a kernel that the program adds become per-layer metrics
    through new files alone: the program's source declares the scope, a
    reader file in a directory of the ``bench`` package reads it, and the
    kernel's counts come from the configuration's reference module."""
    import bench
    src = tmp_path / "src"
    src.mkdir()
    (src / "moe.py").write_text('with jax.named_scope("moe"):\n'
                                '    with jax.named_scope("router"):\n'
                                '        pass\n')
    (tmp_path / "metrics").mkdir()
    for name, text in NEW_READERS.items():
        (tmp_path / "metrics" / f"{name}.py").write_text(text)
    monkeypatch.setattr(bench, "__path__", [*bench.__path__, str(tmp_path)])
    op_map = dict(OP_MAP, **{"fusion.3": "jit(step_body)/jvp()/while/body/"
                             "moe/router/dot_general"})
    raw = hand_made()
    raw["devices"][0] += [["moe_gate.4 tpu_custom_call", 300, 10],
                          ["moe_gate.7 tpu_custom_call", 190, 4]]
    raw["host"][0] = ["window", 100, 250]
    r = S.reduce(raw, op_map, declared=S.declared_scopes(src))
    obs = {"steps": 2, "trace": r, "peaks": {"bf16_flops_per_s": 1e12,
                                             "hbm_bytes_per_s": 1e11},
           "kernel_counts": {"moe_gate": {"flops": 6e3, "bytes": 100.0}}}
    assert H.read_metric("moe.router_ms", obs) == pytest.approx(15e-6 / 2)
    # two calls, of 10 and 4 ns: 7 ns a call against the 6 ns that its
    # flops need at the peak
    assert r["kernels"]["moe_gate"] == {"seconds": pytest.approx(14e-9),
                                        "calls": 2}
    assert H.read_metric("moe_gate_roofline", obs) == pytest.approx(
        100 * 6 / 7)


def test_recorded_scoped_trace():
    with gzip.open(DATA / "v5e_smollm_node_scoped.json.gz", "rt") as f:
        raw = json.load(f)
    raw["devices"] = {int(k): v for k, v in raw["devices"].items()}
    r = S.reduce(raw, raw["op_names"], 1)
    # the scope self times tile the device's busy time
    assert sum(r["scopes"].values()) == pytest.approx(r["busy_s"], rel=1e-3)
    assert r["scope_coverage"] > 0.95
    dirs = {k.split("/")[0] for k in r["scopes"]}
    assert {"fwd", "bwd", "recompute"} <= dirs
    assert any(k.endswith("/attention/core") for k in r["scopes"])
    assert all(" " in n for n, _ in r["breakdown"]["device_ops"])
    assert r["program_spans"]["input.build"]


#: the flash kernels' roofline shares of the whole 10 s window the slice
#: was cut from (TPU v5 lite, smollm-135m.chip1.local)
WINDOW_ROOFLINES = {"flash_attn_fwd_roofline": 28.224428457792104,
                    "flash_attn_dq_roofline": 24.918712547937005,
                    "flash_attn_dkv_roofline": 30.7679252697349}


def test_recorded_flash_kernels():
    """A slice of a traced smollm window on a TPU v5e with the flash
    kernels: each kernel's time and calls, within the device's busy time,
    and the roofline readers on the cell's counts reading as the whole
    window did."""
    with gzip.open(DATA / "v5e_smollm_flash.json.gz", "rt") as f:
        raw = json.load(f)
    raw["devices"] = {int(k): v for k, v in raw["devices"].items()}
    r = S.reduce(raw, raw["op_names"], 1)
    kernels = r["kernels"]
    assert set(kernels) == {"flash_attn_fwd", "flash_attn_dq",
                            "flash_attn_dkv"}
    assert sum(k["seconds"] for k in kernels.values()) <= r["busy_s"]
    obs = {"steps": 1, "trace": r, "peaks": H.peaks_for("TPU v5 lite"),
           "kernel_counts": H.kernel_counts(
               H.cell_files("smollm-135m.chip1.local"))}
    for name, whole in WINDOW_ROOFLINES.items():
        assert H.read_metric(name, obs) == pytest.approx(whole, abs=0.5)


def test_run_cell_reduces_by_scope(monkeypatch):
    """A traced run of a cell (CPU size) whose trace is the recorded v5e
    one, with its op-name map: the result carries the seven scope metrics
    and the breakdown by scope."""
    import time
    from bench.tests.tiny import tiny_files
    with gzip.open(DATA / "v5e_smollm_node_scoped.json.gz", "rt") as f:
        raw = json.load(f)
    raw["devices"] = {int(k): v for k, v in raw["devices"].items()}
    monkeypatch.setattr(S, "load", lambda d: raw)
    monkeypatch.setattr(S, "op_names", lambda text: raw["op_names"])
    files = tiny_files("smollm-135m.chip1.local")
    files["per_layer"] = H.load_json(H.ROOT / "BENCHMARK.json")["per_layer"]
    r = H.run_cell(files, 2**31 + 3, 0.5, True, time.perf_counter(),
                   H.device_info(1))
    assert r["correct"], r["compared"]
    got = set(r["metrics"])
    assert {"step.forward_ms", "step.backward_ms", "step.recompute_ms",
            "step.optimizer_ms", "step.attention_ms", "step.head_loss_ms",
            "input.build_ms", "input.host_ms", "device.idle_frac"} <= got
    assert r["breakdown"] == S.reduce(raw, raw["op_names"], 1)["breakdown"]
    assert r["device"]["busy_s"] > 0
    assert list(r)[-1] == "compared"
