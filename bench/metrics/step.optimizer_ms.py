"""Device milliseconds per window step of the program's ``optimizer`` scope
and any scope nested in it (the local optimizer step,
bench/scopes.py)."""
from bench.scopes import ms_per_step


def read(obs):
    return ms_per_step(
        obs, lambda direction, scope: scope.split("/")[0] == "optimizer")
