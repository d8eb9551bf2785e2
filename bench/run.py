"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, chip, weights, compile or cache load, the first checked
steps), then ``--seconds`` of training with one step in flight, then the
comparison with the plain reference.  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed`` (train steps),
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` ``breakdown``, and last
``compared``: each number compared with its limit.  Exits non-zero, with
no result, where JAX finds no TPU or fewer chips than the cell needs.

Readings for the limits, without a window, one process for many seeds:

    python3 bench/run.py --workload <cell> --readings 1,2,3
    python3 bench/run.py --workload <cell> --readings 4,5,6 --stand-in control

``--stand-in`` puts the reference in the program's place: one precision
lower (``control``), or with a fault planted: half of each node's rows
left out (``half_batch``), or, on a ring, the exchange between the nodes
left out (``no_exchange``).
``--readings-out FILE`` appends each seed's record, with the per-leaf
norms its numbers are read from, to FILE.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--readings", type=_seeds, default=None,
                    help="seeds: the numbers compared, no window")
    ap.add_argument("--stand-in",
                    choices=["control", "half_batch", "no_exchange"],
                    default=None,
                    help="with --readings: the reference one precision "
                         "lower, or with a fault planted, in the program's "
                         "place")
    ap.add_argument("--readings-out", default=None,
                    help="with --readings: append each record to this file")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit("bench: no program (src/repro) in this checkout; "
                         "nothing was run")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness as H
    files = H.cell_files(args.workload)
    device = H.require_chip(files["cell"]["chips"])
    H.peaks_for(device["kind"])
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program of the run is cached, the small ones too, so that a
    # warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if args.readings is not None:
        H.readings(files, args.readings, args.stand_in,
                   out_path=args.readings_out)
        return 0
    result = H.run_cell(files, args.seed, args.seconds, bool(args.trace),
                        T_START, device)
    for name, c in result["compared"].items():
        print(f"compared {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    print(f"correct={result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
