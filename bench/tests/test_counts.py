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
    assert counts.encode_bytes(rows) == 262_752 * (2048 + 516)
    assert abs(counts.encode_bytes(rows) / 1e6 - 673.7) < 0.05
    assert abs(counts.combine_bytes(rows) / 1e9 - 3.097) < 0.001


def test_peaks_table_refuses_unknown_kind():
    assert H.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        H.peaks_for("TPU v9 imaginary")
