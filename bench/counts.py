"""Operations and bytes the benchmark divides by, from shapes alone, and
a kernel's share of its roofline.  A model's own operation counts
(``flops_per_token``, ``param_count``) sit with its reference module
(``bench/reference.py`` for the Llama/Qwen3 family), which may also add
counts of its own kernels (``kernel_counts(conf, traffic)``).

``kernel_counts`` gives, per Pallas kernel the program names, the
operations (``flops``, matrix-unit FLOPs) and HBM ``bytes`` that one call
requires on one chip:

- ``flash_attn_fwd``: causal attention of the chip's rows over its heads,
  two products (scores Q K^T, then P V) over the half of the score matrix
  on and below the diagonal: 4 b h (s^2 / 2) hd.  Bytes: Q, K, V read as
  bfloat16 operands, the float32 output and its log-sum-exp written.
- ``flash_attn_dq`` and ``flash_attn_dkv``: the backward's five products
  (2.5 times the forward: the scores recomputed, dO V^T, dQ, dK, dV).  A
  kernel counts the products that give its own outputs (dq: dS K; dkv:
  P^T dO and dS^T Q) and half of the two that both need (Q K^T and
  dO V^T): dq 2 products, dkv 3.  Bytes: Q, K, V, dO read as operands,
  the float32 statistics read, the float32 gradients written.
- ``int8_encode`` and ``int8_combine``: the wire kernels of one exchange
  over a packed buffer of R rows of B elements (int8 codes plus one f32
  scale a row).  encode reads the f32 differential and writes the
  payload: R (4B + B + 4); combine reads three payloads and both f32
  shadows, and writes both shadows and the f32 mixed result:
  R (3 (B + 4) + 2 * 4B + 3 * 4B).  The noise buffer the encode reads
  today is not counted: drawing the bits in the kernel does the same work
  without it.  R is the leaves' rows (each leaf padded to whole rows of
  B) rounded up to a multiple of 32, on a ring whose nodes each hold one
  chip.
"""
from __future__ import annotations

import math

__all__ = ["wire_rows", "encode_bytes", "combine_bytes", "flash_counts",
           "kernel_counts", "roofline_share"]

SCALE_BYTES = 4
OPERAND_BYTES = 2          # bfloat16 operands of the matrix unit
F32 = 4
BLOCK = 512                # elements in a row of the packed wire buffer
TILE_ROWS = 32             # the packed buffer's height is a multiple of it


def wire_rows(leaf_sizes: list[int], block: int = BLOCK) -> int:
    rows = sum(math.ceil(max(n, 1) / block) for n in leaf_sizes)
    return math.ceil(max(rows, 1) / TILE_ROWS) * TILE_ROWS


def encode_bytes(rows: int, block: int = BLOCK) -> int:
    return rows * (4 * block + block + SCALE_BYTES)


def combine_bytes(rows: int, block: int = BLOCK) -> int:
    payload = block + SCALE_BYTES
    return rows * (3 * payload + 2 * 4 * block + 3 * 4 * block)


def flash_counts(b: int, h: int, kvh: int, s: int, hd: int) -> dict:
    """One call of each flash kernel over b rows of s tokens, h query and
    kvh key/value heads of size hd."""
    product = 2.0 * b * h * (s * s / 2) * hd     # one causal product
    q = b * h * s * hd
    kv = b * kvh * s * hd
    stats = b * h * s * F32
    return {
        "flash_attn_fwd": {
            "flops": 2 * product,
            "bytes": (q + 2 * kv) * OPERAND_BYTES + q * F32 + stats},
        "flash_attn_dq": {
            "flops": 2 * product,
            "bytes": (2 * q + 2 * kv) * OPERAND_BYTES + 2 * stats + q * F32},
        "flash_attn_dkv": {
            "flops": 3 * product,
            "bytes": ((2 * q + 2 * kv) * OPERAND_BYTES + 2 * stats
                      + 2 * kv * F32)},
    }


def kernel_counts(conf: dict, traffic: dict, leaf_sizes: list[int]) -> dict:
    """Per kernel, the flops and bytes one call requires on one chip of the
    cell: the flash kernels over the chip's rows and heads; the wire
    kernels where the cell is a ring of nodes, one chip each."""
    mesh = traffic["mesh"]
    heads = conf["num_attention_heads"]
    hd = conf.get("head_dim") or conf["hidden_size"] // heads
    out = flash_counts(
        traffic["nodes"] * traffic["seqs_per_node"] // mesh["data"],
        heads // mesh["model"],
        max(1, conf["num_key_value_heads"] // mesh["model"]),
        traffic["seq_len"], hd)
    if traffic["nodes"] > 1 and mesh["data"] == traffic["nodes"] \
            and mesh["model"] == 1:
        rows = wire_rows(leaf_sizes)
        out["int8_encode"] = {"flops": 0.0, "bytes": encode_bytes(rows)}
        out["int8_combine"] = {"flops": 0.0, "bytes": combine_bytes(rows)}
    return out


def roofline_share(obs: dict, kernel: str) -> float | None:
    """The kernel's share of its roofline in the traced window, in percent:
    the least time a call could take on the chip (the larger of its flops
    over the bf16 peak and its bytes over the HBM peak, bench/peaks.json)
    over the time a call took (its device self time over its calls).  None
    where the trace holds no call of it, or nothing counts it."""
    run = obs.get("trace", {}).get("kernels", {}).get(kernel)
    need = obs.get("kernel_counts", {}).get(kernel)
    peaks = obs.get("peaks")
    if not (run and run["calls"] and run["seconds"] > 0 and need and peaks):
        return None
    least = max(need["flops"] / peaks["bf16_flops_per_s"],
                need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (run["seconds"] / run["calls"])
