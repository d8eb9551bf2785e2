"""Device milliseconds per window step of the program's ``head_loss`` scope
(the output head and the cross-entropy) and any scope nested in it,
forward and backward (bench/scopes.py)."""
from bench.scopes import ms_per_step


def read(obs):
    return ms_per_step(
        obs, lambda direction, scope: scope.split("/")[0] == "head_loss")
