"""Device milliseconds per window step of the program's ``exchange`` scope
and every scope inside it (noise, encode, the ring's permutes, combine),
averaged over the chips (bench/scopes.py)."""
from bench.scopes import ms_per_step


def read(obs):
    return ms_per_step(
        obs, lambda direction, scope: scope.split("/")[0] == "exchange")
