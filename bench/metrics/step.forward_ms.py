"""Device milliseconds per window step of the forward pass: self time of
the ops JAX marks ``jvp(...)`` and not ``transpose`` or recompute, every
scope (bench/scopes.py)."""
from bench.scopes import ms_per_step


def read(obs):
    return ms_per_step(obs, lambda direction, scope: direction == "fwd")
