"""Cells of the benchmark cut to a size a CPU test can hold: the same
files and code paths as the chip's cells, at widths of a few dozen.  Four
layers of 128 tokens: at two layers of 64 the bfloat16 control departs
from the reference less than at the chip's sizes (PERF.md)."""
from __future__ import annotations

from bench import harness as H

#: program overrides and the matching published keys of a tiny model
TINY = {
    "smollm-135m": ({"d_model": 64, "n_periods": 4, "n_heads": 4,
                     "n_kv_heads": 2, "d_ff": 128, "vocab_size": 512},
                    {"hidden_size": 64, "num_hidden_layers": 4,
                     "num_attention_heads": 4, "num_key_value_heads": 2,
                     "head_dim": 16, "intermediate_size": 128,
                     "vocab_size": 512}),
    "qwen3-0.6b": ({"d_model": 64, "n_periods": 4, "n_heads": 4,
                    "n_kv_heads": 2, "head_dim": 32, "d_ff": 128,
                    "vocab_size": 512},
                   {"hidden_size": 64, "num_hidden_layers": 4,
                    "num_attention_heads": 4, "num_key_value_heads": 2,
                    "head_dim": 32, "intermediate_size": 128,
                    "vocab_size": 512}),
}


#: cells with their configuration and traffic, as BENCHMARK.json names them
CELLS = {
    "qwen3-0.6b.chip1.local": ("qwen3-0.6b", "node1-s4096-b1", 1),
    "smollm-135m.chip1.local": ("smollm-135m", "node1-s2048-b8", 1),
}


def tiny_files(cell: str, seq_len: int = 128, seqs_per_node: int = 2) -> dict:
    config, traffic, chips = CELLS[cell]
    files = {
        "cell": {"name": cell, "config": config, "traffic": traffic,
                 "chips": chips},
        "config": H.load_json(H.BENCH / "configs" / f"{config}.json"),
        "traffic": H.load_json(H.BENCH / "traffic" / f"{traffic}.json"),
        "limits": H.load_json(H.BENCH / "workloads" / f"{cell}.json")[
            "limits"],
        "end_to_end": [], "per_layer": [],
    }
    conf = files["config"]
    program, published = TINY[conf["arch"]]
    conf["program"] = dict(program)
    conf.update(published)
    tr = files["traffic"]
    tr["seq_len"], tr["seqs_per_node"] = seq_len, seqs_per_node
    return files
