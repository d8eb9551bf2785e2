"""Device milliseconds per window step of the program's ``attention`` scope
and ``attention/core`` inside it, forward, backward and recompute
(bench/scopes.py)."""
from bench.scopes import ms_per_step


def read(obs):
    return ms_per_step(
        obs, lambda direction, scope: scope.split("/")[0] == "attention")
