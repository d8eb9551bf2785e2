"""Bytes the benchmark divides by, from shapes alone.  A model's own
operation counts (``flops_per_token``, ``param_count``) sit with its
reference module (``bench/reference.py`` for the Llama/Qwen3 family).

The wire kernels' required bytes per exchange, for a packed buffer of R
rows of B elements (int8 codes plus one f32 scale per row):

- encode: read the f32 differential, write the payload: R (4B + B + 4);
- combine: read three payloads and both f32 shadows, write both shadows
  and the f32 mixed result: R (3 (B + 4) + 2 * 4B + 3 * 4B).

The noise buffer the encode reads today is not counted: drawing the bits
in the kernel does the same work without it.
"""
from __future__ import annotations

__all__ = ["wire_kernel_bytes", "encode_bytes", "combine_bytes"]

SCALE_BYTES = 4


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
