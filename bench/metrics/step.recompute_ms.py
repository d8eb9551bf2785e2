"""Device milliseconds per window step of the operations recomputed in the
backward pass to save memory (``checkpoint/rematted_computation``), every
scope (bench/scopes.py)."""
from bench.scopes import ms_per_step


def read(obs):
    return ms_per_step(obs, lambda direction, scope: direction == "recompute")
