"""Operations and bytes the benchmark divides by, from shapes alone.

Model FLOPs per training token (PaLM, Chowdhery et al. 2022, app. B):
``6 N + 12 L H Q T``, with N every matrix weight counted once (the tied
vocabulary head included, the embedding lookup not), L layers, H query
heads of size Q and T the sequence length.  Recomputed operations are not
counted.

The wire kernels' required bytes per exchange, for a packed buffer of R
rows of B elements (int8 codes plus one f32 scale per row):

- encode: read the f32 differential, write the payload: R (4B + B + 4);
- combine: read three payloads and both f32 shadows, write both shadows
  and the f32 mixed result: R (3 (B + 4) + 2 * 4B + 3 * 4B).

The noise buffer the encode reads today is not counted: drawing the bits
in the kernel does the same work without it.
"""
from __future__ import annotations

__all__ = ["matmul_params", "param_count", "flops_per_token",
           "wire_kernel_bytes", "encode_bytes", "combine_bytes"]

SCALE_BYTES = 4


def _dims(conf: dict):
    heads = conf["num_attention_heads"]
    hd = conf.get("head_dim") or conf["hidden_size"] // heads
    return (conf["hidden_size"], conf["intermediate_size"],
            conf["num_hidden_layers"], heads, conf["num_key_value_heads"],
            hd, conf["vocab_size"])


def matmul_params(conf: dict) -> int:
    """Matrix weights, each once; the (tied) vocabulary head once."""
    d, f, L, h, kvh, hd, v = _dims(conf)
    per_layer = d * h * hd * 2 + d * kvh * hd * 2 + 3 * d * f
    return L * per_layer + v * d


def param_count(conf: dict) -> int:
    """Every parameter: matrices, norm gains (two per layer, one final,
    and per-head query/key norms where the model has them)."""
    d, _, L, _, _, hd, _ = _dims(conf)
    norms = L * 2 * d + d
    if conf["model_type"] == "qwen3":
        norms += L * 2 * hd
    return matmul_params(conf) + norms


def flops_per_token(conf: dict, seq_len: int) -> float:
    d, _, L, h, _, hd, _ = _dims(conf)
    return 6.0 * matmul_params(conf) + 12.0 * L * h * hd * seq_len


def encode_bytes(rows: int, block: int = 512) -> int:
    return rows * (4 * block + block + SCALE_BYTES)


def combine_bytes(rows: int, block: int = 512) -> int:
    payload = block + SCALE_BYTES
    return rows * (3 * payload + 2 * 4 * block + 3 * 4 * block)


def wire_kernel_bytes(setup) -> dict | None:
    """Required bytes of one exchange's encode and combine on one device of
    a ring, from the program's packed layout; None without an exchange."""
    from repro.launch import train as LT
    cons = setup.consensus
    if cons.cfg.algorithm != "adc_dgd" or setup.ctx.total_consensus_nodes < 2:
        return None
    layout = LT.consensus_wire_layout(setup.defs, setup.ctx, cons)
    return {"rows": layout.n_rows,
            "encode": encode_bytes(layout.n_rows, layout.block),
            "combine": combine_bytes(layout.n_rows, layout.block)}
