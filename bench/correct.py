"""The comparison that decides ``correct``.

Both sides give the loss of each checked step, the gradient of the first
step (the program's worked out from Adam's first moment) and the change of
every parameter leaf over the checked steps.  Per leaf, ``leaf_table``
takes the reference's norm, the program's norm and the norm of their
difference; the numbers compared are read from that table:

- ``loss_gap``: the largest |program - reference| loss over the checked
  steps (nats);
- ``grad_norm_gap``: over the leaves, the largest gap between the
  program's and the reference's gradient norm, over the reference's norm
  of that leaf or of the median leaf, whichever is larger;
- ``change_norm_gap``: the same for the parameters' change, over the
  leaves that count (see ``counted_leaves``);
- ``grad_diff`` and ``change_diff``: over the same leaves, the largest
  norm of the difference itself, over the reference's norm of that leaf.

A limit of None prints the number without comparing it.
"""
from __future__ import annotations

import numpy as np

__all__ = ["compare", "passes", "counted_leaves", "leaf_table"]

#: leaves whose reference gradient is under this share of the median
#: leaf's move under Adam by round-off alone, and are left out of the change
ROUNDOFF_SHARE = 1e-3


def _norm(a) -> float:
    """2-norm, squares summed in float64."""
    return float(np.sqrt(np.sum(np.square(a), dtype=np.float64)))


def counted_leaves(ref: dict) -> list[str]:
    norms = {k: _norm(v) for k, v in ref["grad1"].items()}
    med = float(np.median(list(norms.values())))
    return [k for k, v in norms.items() if v >= ROUNDOFF_SHARE * med]


def leaf_table(prog: dict, ref: dict) -> dict:
    """Per quantity (``grad1``, ``change``), per leaf that counts:
    [reference norm, program norm, norm of the difference]."""
    leaves = {"grad1": list(ref["grad1"]), "change": counted_leaves(ref)}
    return {q: {k: [_norm(ref[q][k]), _norm(prog[q][k]),
                    _norm(np.subtract(prog[q][k], ref[q][k]))]
                for k in names}
            for q, names in leaves.items()}


def _worst(rows: dict, diff: bool) -> float:
    med = float(np.median([r[0] for r in rows.values()]))
    worst = 0.0
    for ref_n, prog_n, diff_n in rows.values():
        den = ref_n if diff else max(ref_n, med)
        num = diff_n if diff else abs(prog_n - ref_n)
        worst = max(worst, num / den if den > 0 else float("inf"))
    return worst


def compare(prog: dict, ref: dict, limits: dict) -> tuple[list, dict]:
    """([(name, value, limit)] for every number, the leaf table); a
    non-finite value is reported as infinite."""
    loss_gap = float(np.max(np.abs(np.subtract(prog["loss"], ref["loss"]))))
    table = leaf_table(prog, ref)
    vals = {"loss_gap": loss_gap,
            "grad_norm_gap": _worst(table["grad1"], False),
            "change_norm_gap": _worst(table["change"], False),
            "grad_diff": _worst(table["grad1"], True),
            "change_diff": _worst(table["change"], True)}
    out = []
    for name, v in vals.items():
        if not np.isfinite(v):
            v = float("inf")
        out.append((name, float(v), limits.get(name)))
    return out, table


def passes(compared: list[tuple]) -> bool:
    """Every number finite and within its limit; a cell that compares
    nothing does not pass."""
    return (any(lim is not None for _, _, lim in compared)
            and all(np.isfinite(v) and (lim is None or v <= lim)
                    for _, v, lim in compared))
