"""The four-node ring cell at a CPU size, on four virtual devices: the
program's ring against its plain reference (bench/ring.py), and what has
to fail there: the program built without its exchange, the reference
without the exchange in the program's place; and a reference whose nodes
draw each other's noise, which the change shows."""
import time

import pytest

from bench import correct as C
from bench import harness as H
from bench import ring
from bench.tests.tiny import tiny_files

#: the queued ring cell, as its BENCHMARK.json entry will name it, with the
#: one-chip node's limits
RING = {"name": "smollm-135m.ring4.adc-int8", "config": "smollm-135m",
        "traffic": "ring4-s2048-b8", "chips": 4}
LIMITS = H.load_json(H.BENCH / "workloads" / "smollm-135m.chip1.local.json")[
    "limits"]
SEED = 2**31 + 41


def numbers(files, rec):
    return [(n, v, files["limits"].get(n)) for n, v in rec["numbers"].items()]


def test_ring_sound_run_is_correct():
    files = tiny_files(RING, limits=LIMITS)
    assert files["traffic"]["nodes"] == 4
    r = H.run_cell(files, SEED, 0.5, False, time.perf_counter(),
                   H.device_info(4))
    assert r["correct"], r["compared"]
    assert r["compared"]["change_diff"]["value"] < 0.02


def test_program_without_exchange_is_caught():
    files = tiny_files(RING, limits=LIMITS)
    files["traffic"]["exchange"]["algorithm"] = "none"
    r = H.run_cell(files, SEED, 0.5, False, time.perf_counter(),
                   H.device_info(4))
    assert not r["correct"], r["compared"]


@pytest.mark.parametrize("stand_in", ["no_exchange", "control"])
def test_stand_in_is_caught(stand_in):
    files = tiny_files(RING, limits=LIMITS)
    rec = H.readings(files, [SEED], stand_in)[0]
    assert not C.passes(numbers(files, rec)), rec["numbers"]


def test_swapped_noise_is_seen(monkeypatch):
    """The comparison sees the stochastic rounding: a reference whose node
    i draws node i + 1's bits reads the change far from the program's, at
    this size where a sound run agrees to a few thousandths.  (At the
    chip's size the products' rounding alone reads about as far: the
    limit there is set against the exchange left out, PERF.md.)"""
    files = tiny_files(RING, limits=LIMITS)
    sound = H.readings(files, [SEED])[0]["numbers"]["change_diff"]
    key = ring.node_key
    monkeypatch.setattr(ring, "node_key",
                        lambda k0, k, i: key(k0, k, (i + 1) % 4))
    swapped = H.readings(files, [SEED])[0]["numbers"]["change_diff"]
    assert sound < 0.005 and swapped > 20 * sound, (sound, swapped)


def test_node_rows_split_each_step():
    import numpy as np
    rows = [(np.arange(8)[:, None] * np.ones((1, 3), int),) * 2]
    parts = ring.node_rows(rows, 4)
    assert [p[0][0][:, 0].tolist() for p in parts] == [
        [0, 1], [2, 3], [4, 5], [6, 7]]
