"""Device milliseconds per window step of the backward pass: self time of
the ops under ``transpose(jvp(...))`` that are not recomputed, every scope
(bench/scopes.py)."""
from bench.scopes import ms_per_step


def read(obs):
    return ms_per_step(obs, lambda direction, scope: direction == "bwd")
