"""The trace reduction on a hand-made record and on a small trace
recorded on a TPU v5e (one chip, smollm-135m, two steps of the window)."""
import gzip
import json
from pathlib import Path

import pytest

from bench import trace as TR

DATA = Path(__file__).resolve().parent / "data"


def hand_made():
    # window [100, 200); device 0 busy [100,130) + [130,150) + [170,190);
    # device 1 busy [110, 120)
    return {
        "devices": {
            0: [["fusion.1", 100, 30], ["collective-permute-start.2", 130, 20],
                ["fusion.1", 170, 20], ["outside", 250, 10]],
            1: [["fusion.1", 110, 10]],
        },
        "host": [["window", 100, 100], ["input", 150, 15],
                 ["dispatch", 165, 5], ["readback", 190, 10]],
    }


def test_busy_union_and_idle():
    r = TR.reduce(hand_made())
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busiest_busy_s"] == pytest.approx(70e-9)
    assert r["busy_s"] == pytest.approx((70e-9 + 10e-9) / 2)
    assert r["idle_frac"] == pytest.approx(0.3)


def test_idle_gaps_are_labelled_by_host_span():
    r = TR.reduce(hand_made())
    assert r["idle_gaps"] == [["input", pytest.approx(20e-9)],
                              ["readback", pytest.approx(10e-9)]]


def test_per_op_sums():
    r = TR.reduce(hand_made())
    ops = dict(r["top_ops"])
    assert ops["fusion.1"] == pytest.approx((50e-9 + 10e-9) / 2)
    assert ops["collective-permute-start.2"] == pytest.approx(20e-9 / 2)
    assert "outside" not in ops
    assert r["host_spans"]["input"] == [pytest.approx(15e-9)]


def test_merge():
    assert TR.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]


def recorded(name):
    with gzip.open(DATA / name, "rt") as f:
        raw = json.load(f)
    raw["devices"] = {int(k): v for k, v in raw["devices"].items()}
    return raw


def test_recorded_node_trace():
    raw = recorded("v5e_smollm_node.json.gz")
    r = TR.reduce(raw, 1)
    lo, hi = r["lo"], r["hi"]
    # busy time against a brute-force timeline at 1 us
    import numpy as np
    t = np.zeros((hi - lo) // 1000 + 1, bool)
    for _, s, d in raw["devices"][0]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            t[(a - lo) // 1000:(b - lo) // 1000] = True
    assert r["busy_s"] == pytest.approx(t.sum() * 1e-6, rel=1e-3)
    assert 0 < r["idle_frac"] < 1
    # self times tile the busy time: ops nest, they do not overlap
    total = sum(TR.self_times([(n, max(s, lo), min(s + d, hi) - max(s, lo))
                               for n, s, d in raw["devices"][0]
                               if s + d > lo and s < hi]).values())
    assert total / 1e9 == pytest.approx(r["busy_s"], rel=1e-3)
    gaps = sum(g for _, g in r["idle_gaps"])
    assert gaps <= r["window_s"] - r["busy_s"] + 1e-9
    assert r["host_spans"]["input"]


def test_op_names():
    assert TR.op_name("%while.340 = (s32[]) while(%t), body=%b") == "while.340"
    assert TR.op_name('%custom-call.3 = u8[8] custom-call(f32[8] %y), '
                      'custom_call_target="tpu_custom_call", backend_config='
                      '{"kernel_name": "_payload_fixed_kernel"}') == (
        "custom-call.3 tpu_custom_call _payload_fixed_kernel")


def test_kernels_time_and_calls():
    """Every Pallas kernel's self time and calls in the window, averaged
    over the chips, by the kernel's name without its instruction's
    number."""
    raw = hand_made()
    raw["devices"][0] += [["flash_attn_fwd.16 tpu_custom_call", 150, 4],
                          ["flash_attn_fwd.17 tpu_custom_call", 190, 6],
                          ["flash_attn_fwd.17 tpu_custom_call", 260, 6],
                          ["custom-call.3 tpu_custom_call int8_encode", 140,
                           5]]
    raw["devices"][1] += [["flash_attn_fwd.16 tpu_custom_call", 120, 8]]
    r = TR.reduce(raw)
    assert r["kernels"] == {
        "flash_attn_fwd": {"seconds": pytest.approx((10e-9 + 8e-9) / 2),
                           "calls": 1.5},
        "int8_encode": {"seconds": pytest.approx(5e-9 / 2), "calls": 0.5}}
    assert sum(k["seconds"] for k in r["kernels"].values()) <= r["busy_s"]
    assert TR.kernel_name("fusion.12") is None
    assert TR.kernel_name("flash_attn_dq.10 tpu_custom_call") == \
        "flash_attn_dq"
