"""The scope reduction (bench/scopes.py) on a hand-made record and op-name
map, and on a small trace recorded on a TPU v5e with the compiled step's
op-name map (one chip, smollm-135m)."""
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
    ("jit(step_body)/jvp()/while/body/core/exp", "unscoped"),
    ("jit(step_body)/jvp()/add", "unscoped"),
    ("", "unscoped"),
])
def test_innermost_scope(path, scope):
    assert S.scope(path) == scope


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
    assert S.scope(m["copy.6"]) == "layers"
    assert S.scope(m["int8_encode.1"]) == "exchange/encode"
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
