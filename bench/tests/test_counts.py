"""The count functions (bench/counts.py, and the Llama/Qwen3 reference
module's model counts) against the program's own shapes."""
import math

import jax
import pytest

from bench import counts
from bench import harness as H
from bench import reference as R


def _program_params(conf) -> int:
    from repro.models import transformer as T
    from repro.models.params import ParamDef
    from repro.models.sharding import ParallelContext
    ctx = ParallelContext(tp=1, data_size=1, n_nodes=1, in_shard_map=True)
    defs = T.build_defs(H.model_config(conf), ctx)
    return sum(math.prod(d.shape) for d in jax.tree.leaves(
        defs.storage, is_leaf=lambda x: isinstance(x, ParamDef)))


@pytest.mark.parametrize("name,layers,want", [
    ("smollm-135m", 30, 134_515_008),
    ("qwen3-0.6b", 28, 596_049_920),
])
def test_param_count_matches_program(name, layers, want):
    conf = H.load_json(H.BENCH / "configs" / f"{name}.json")
    conf = dict(conf, num_hidden_layers=layers,
                program={"n_periods": layers})
    assert R.param_count(conf) == want
    assert _program_params(conf) == want
    specs = R.leaf_specs(R.Model.from_config(conf))
    assert sum(math.prod(s) for _, s, _ in specs) == want


def test_flops_per_token():
    conf = H.load_json(H.BENCH / "configs" / "smollm-135m.json")
    n = R.matmul_params(conf)
    assert n == 134_479_872
    assert R.flops_per_token(conf, 2048) == 6 * n + 12 * 30 * 9 * 64 * 2048


def test_wire_rows_and_kernel_bytes():
    conf = H.load_json(H.BENCH / "configs" / "smollm-135m.json")
    from repro.launch import train as LT
    from repro.models import transformer as T
    from repro.models.sharding import ParallelContext
    ctx = ParallelContext(tp=1, data_size=1, n_nodes=1, in_shard_map=True)
    layout = LT.consensus_wire_layout(T.build_defs(H.model_config(conf), ctx),
                                      ctx)
    rows = layout.n_rows
    assert rows == 262_752
    # the same rows from the reference's leaves, by the traffic's rule
    assert counts.wire_rows([math.prod(shape) for _, shape, _ in
                             R.leaf_specs(R.Model.from_config(conf))]) == rows
    assert counts.encode_bytes(rows) == 262_752 * (2048 + 516)
    assert abs(counts.encode_bytes(rows) / 1e6 - 673.7) < 0.05
    assert abs(counts.combine_bytes(rows) / 1e9 - 3.097) < 0.001


def test_peaks_table_refuses_unknown_kind():
    assert H.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        H.peaks_for("TPU v9 imaginary")


def test_flash_counts():
    """Causal FLOPs of one call: forward 4 b h s^2/2 hd, the backward's 2.5
    times that split 2 : 3 between dq and dkv (bench/counts.py)."""
    c = counts.flash_counts(8, 9, 3, 2048, 64)
    fwd = 4 * 8 * 9 * 2048 ** 2 / 2 * 64
    assert c["flash_attn_fwd"]["flops"] == fwd
    assert c["flash_attn_dq"]["flops"] == fwd
    assert c["flash_attn_dkv"]["flops"] == 1.5 * fwd
    q, kv = 8 * 9 * 2048 * 64, 8 * 3 * 2048 * 64
    assert c["flash_attn_fwd"]["bytes"] == 2 * (q + 2 * kv) + 4 * q + \
        4 * 8 * 9 * 2048


@pytest.mark.parametrize("cell,rows,heads", [
    ("smollm-135m.chip1.local", 8, 9), ("qwen3-0.6b.chip1.local", 1, 16),
    ("ring4-s2048-b8", 8, 9)])
def test_kernel_counts_of_each_cell(cell, rows, heads):
    if cell.startswith("ring4"):
        # the queued ring cell: the node's configuration on the ring traffic
        files = dict(H.cell_files("smollm-135m.chip1.local"),
                     traffic=H.load_json(H.BENCH / "traffic" / f"{cell}.json"))
    else:
        files = H.cell_files(cell)
    got = H.kernel_counts(files)
    conf, tr = files["config"], files["traffic"]
    want = counts.flash_counts(rows, heads, conf["num_key_value_heads"],
                               tr["seq_len"], conf["head_dim"])
    assert {k: got[k] for k in want} == want
    ring = tr["nodes"] > 1
    assert ("int8_encode" in got) == ring == ("int8_combine" in got)
    if ring:
        assert got["int8_encode"]["bytes"] == counts.encode_bytes(262_752)


def test_roofline_share():
    obs = {"trace": {"kernels": {"k": {"seconds": 2e-3, "calls": 4}}},
           "kernel_counts": {"k": {"flops": 197e6, "bytes": 819e3 * 0.5}},
           "peaks": H.peaks_for("TPU v5 lite")}
    # the flops bound a call at 1 us against 0.5 ms a call
    assert counts.roofline_share(obs, "k") == pytest.approx(0.2)
    obs["kernel_counts"]["k"]["bytes"] = 819e6 * 0.25
    assert counts.roofline_share(obs, "k") == pytest.approx(50.0)
    assert counts.roofline_share(obs, "absent") is None
    assert counts.roofline_share(dict(obs, peaks=None), "k") is None
