"""``correct`` on cells cut to a CPU size: a sound run passes; the control
(the reference in bfloat16 in the program's place) and each fault the cell
can have, planted underneath the timed path, fail.  The chip's look for a
TPU is skipped; everything else is the run the benchmark makes."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import correct as C
from bench import harness as H
from bench.tests.tiny import tiny_files

#: the cells of BENCHMARK.json, whose limits were set on the chip
CELLS = [w["name"] for w in H.load_json(H.ROOT / "BENCHMARK.json")[
    "workloads"]]
SEED = 2**31 + 17


def run(cell, step_wrapper=None):
    files = tiny_files(cell)
    return H.run_cell(files, SEED, 0.5, False, time.perf_counter(),
                      H.device_info(files["cell"]["chips"]),
                      step_wrapper=step_wrapper)


def unchanged(step, sys_):
    """A step that returns its state unchanged."""
    def f(state, batch):
        _, metrics = step(jax.tree.map(jnp.copy, state), batch)
        return state, metrics
    return f


def half_batch(step, sys_):
    """Half of each node's rows left out, the mean taken over the rest (the
    kept half stands in for the rest)."""
    def f(state, batch):
        rows = {}
        for k, v in batch.items():
            a = np.asarray(v).copy()
            for part in np.split(a, sys_.nodes):
                b = len(part) // 2
                part[b:2 * b] = part[:b]
            rows[k] = a
        return step(state, jax.device_put(rows, sys_.setup.batch_sharding))
    return f


def norms_frozen(step, sys_):
    """A step that never updates the RMSNorm gains (the smallest leaves)."""
    def frozen(name):
        return "norm" in name.split(".")[-1]

    def f(state, batch):
        keep = {n: jnp.copy(a) for n, a in zip(
            sys_.names, jax.tree.leaves(state["params"])) if frozen(n)}
        state, metrics = step(state, batch)
        leaves, tree = jax.tree.flatten(state["params"])
        leaves = [keep.get(n, a) for n, a in zip(sys_.names, leaves)]
        return dict(state, params=jax.tree.unflatten(tree, leaves)), metrics
    return f


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = run(cell)
    assert r["correct"], r["compared"]
    assert r["attempted"] > H.CHECK_STEPS and r["failed"] == 0
    assert list(r)[-1] == "compared"


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [unchanged, half_batch, norms_frozen],
                         ids=["state_unchanged", "half_batch", "norms_frozen"])
def test_fault_is_caught(cell, fault):
    assert not run(cell, fault)["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_caught(cell):
    files = tiny_files(cell)
    rec = H.readings(files, [SEED], "control")[0]
    numbers = [(n, v, files["limits"].get(n))
               for n, v in rec["numbers"].items()]
    assert not C.passes(numbers), numbers


def test_nothing_compared_does_not_pass():
    assert not C.passes([("grad_diff", 0.0, None)])
    assert C.passes([("grad_diff", 0.0, 0.1), ("loss_gap", 5.0, None)])
