"""Cells of the benchmark cut to a size a CPU test can hold: the same
files and code paths as the chip's cells, at widths of a few dozen.  Four
layers of 128 tokens: at two layers of 64 the bfloat16 control departs
from the reference less than at the chip's sizes (PERF.md)."""
from __future__ import annotations

from bench import harness as H

#: program overrides and the matching published keys of a tiny model, by
#: the configuration's ``arch``; a reference module of another family
#: defines its own ``TINY``
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


def cells() -> dict:
    """The cells of BENCHMARK.json by name."""
    return {w["name"]: w for w in H.load_json(H.ROOT / "BENCHMARK.json")[
        "workloads"]}


def tiny_files(cell, seq_len: int = 128, seqs_per_node: int = 2,
               limits: dict | None = None) -> dict:
    """The files of ``cell`` (a name in BENCHMARK.json, or an entry of
    its own), cut to a tiny model and ``seqs_per_node`` rows of
    ``seq_len`` tokens a node; ``limits`` in place of the cell's own."""
    spec = cell if isinstance(cell, dict) else cells()[cell]
    config = next(c for c in H.load_json(H.ROOT / "BENCHMARK.json")[
        "configs"] if c["name"] == spec["config"])
    files = {
        "cell": dict(spec),
        "config": H.load_json(H.ROOT / config["file"]),
        "traffic": H.load_json(H.BENCH / "traffic"
                               / f"{spec['traffic']}.json"),
        "limits": limits or H.load_json(
            H.BENCH / "workloads" / f"{spec['name']}.json")["limits"],
        "end_to_end": [], "per_layer": [],
    }
    conf = files["config"]
    # a family's reference module may bring its own tiny sizes
    program, published = getattr(H.reference_module(conf), "TINY",
                                 TINY)[conf["arch"]]
    conf["program"] = dict(program)
    conf.update(published)
    tr = files["traffic"]
    tr["seq_len"], tr["seqs_per_node"] = seq_len, seqs_per_node
    return files
