"""Device time of the train step by direction and named scope.

The program runs its work under stable ``jax.named_scope``s; the names
it declares are those its source passes to ``jax.named_scope`` as string
literals (``declared_scopes``; PERF.md §3 lists them).  XLA keeps each
op's scope path in the compiled step's HLO
metadata (``op_name``), and JAX writes there the transform the op belongs
to: ``jvp(...)`` is the forward pass, ``transpose(jvp(...))`` the
backward, and ``checkpoint/rematted_computation`` an op recomputed in the
backward to save memory.  The profiler's device trace names the same ops
by their HLO instruction names (``fusion.623``).  ``op_names`` reads the
map between the two from ``compiled.as_text()``; ``reduce`` splits the
traced window's device self time by (direction, scope) on top of
``bench.trace.reduce``, whose numbers it leaves as they are.  An op's
label is the path of declared scopes that ends its own path (``scope``):
a scope the program opens under a declared one reads as its own label
(``mlp/router``) with no edit here.

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>

runs one traced window of a cell through the harness's own set-up, feed
and window, and prints the reduction and the step-by-scope metrics
(``bench/metrics/step.*_ms.py``, ``input.build_ms.py``) as one JSON line.
``--record FILE`` also writes a slice of the window's device ops around
one ``input`` span, with its op-name map, as a test record.
"""
from __future__ import annotations

import glob
import os
import re
import sys
from collections import defaultdict
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(_ROOT / "src"), str(_ROOT)]

from bench import trace as TR  # noqa: E402

__all__ = ["SOURCE", "PROGRAM_SPANS", "DIRECTIONS", "declared_scopes",
           "op_names", "direction", "scope", "load", "reduce", "ms_per_step"]

#: the program's source, whose ``jax.named_scope`` calls declare its scopes
SOURCE = _ROOT / "src"
#: host spans the program itself writes on the profiler's clock
PROGRAM_SPANS = ("input.build", "input.transfer")
DIRECTIONS = ("fwd", "bwd", "recompute", "update")

_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = (.*)$")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_OPERAND = re.compile(r"%([^\s,)]+)")
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply|branch_computations|"
                    r"called_computations)=\{?(%[^}\s]+(?:,\s*%[^}\s,]+)*)")
_TRANSFORM = re.compile(r"^[\w.-]+\((.*)\)$")
_NAMED_SCOPE = re.compile(r"named_scope\(\s*[\"']([\w./-]+)[\"']\s*\)")


def declared_scopes(source: Path = SOURCE) -> frozenset[str]:
    """Every scope name the program's source declares: the string literals
    it passes to ``jax.named_scope``, split at ``/``.  A name made at run
    time (``f"chunk{c}"``) is not declared, and its component is left out
    of labels."""
    names = set()
    for path in sorted(Path(source).rglob("*.py")):
        for lit in _NAMED_SCOPE.findall(path.read_text()):
            names.update(lit.split("/"))
    return frozenset(names)


def op_names(hlo_text: str) -> dict[str, str]:
    """HLO instruction name -> its ``op_name`` path (the first, where XLA
    joined several with ``;``).  An instruction that XLA put in without
    metadata of its own (a copy, an asynchronous copy's start and done, a
    bitcast) takes the path of its first operand that has one, else that
    of the instruction whose computation it runs in (a loop's body takes
    the loop's).  Parameters count as having none."""
    own, first, where, caller = {}, {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            h = _HEADER.match(line)
            comp = h.group(1) if h else None
            continue
        m = _LINE.match(line)
        if not m:
            continue
        name, rest = m.groups()
        where[name] = comp
        for c in _CALLS.findall(rest):
            for callee in c.split(","):
                caller.setdefault(callee.strip().lstrip("%"), name)
        p = _OP_NAME.search(rest)
        if p and " parameter(" not in f" {rest}":
            own[name] = p.group(1).split(";")[0]
        elif (o := _OPERAND.search(rest)):
            first[name] = o.group(1)
    out = dict(own)

    def resolve(name: str, depth: int = 0) -> str | None:
        if name in out or depth > 64:
            return out.get(name)
        n, seen = name, set()
        while n in first and n not in seen and n not in own:
            seen.add(n)
            n = first[n]
        path = own.get(n)
        if path is None and where.get(name) in caller:
            path = resolve(caller[where[name]], depth + 1)
        if path is not None:
            out[name] = path
        return path

    for name in where:
        resolve(name)
    return out


def direction(path: str) -> str:
    """``fwd``, ``bwd``, ``recompute`` or ``update`` (outside the
    differentiated loss: the optimizer and the exchange)."""
    if "rematted_computation" in path:
        return "recompute"
    if "transpose(" in path:
        return "bwd"
    if "jvp(" in path:
        return "fwd"
    return "update"


def _parts(path: str) -> list[str]:
    """The path's components, with transform wrappers opened:
    ``transpose(jvp(head_loss))`` -> ``head_loss``."""
    out = []
    for p in path.split("/"):
        while (m := _TRANSFORM.match(p)):
            p = m.group(1)
        out.append(p)
    return out


def scope(path: str, declared: frozenset[str]) -> str:
    """The op's label: the declared scopes that end ``path``, from the last
    one that is not opened directly inside the one before it, or
    ``unscoped``.  A scope directly inside another continues its label
    (``attention/core``, ``exchange/encode``); one opened after other
    structure (the layer loop's ``while/body``, a call, a remat) starts
    its own (``layers/while/body/.../attention`` reads ``attention``)."""
    label, nested = [], False
    for p in _parts(path):
        if p in declared:
            label = label + [p] if nested else [p]
        nested = p in declared
    return "/".join(label) or "unscoped"


def load(trace_dir: str) -> dict:
    """``bench.trace.load``'s record, plus ``program``: the program's own
    host spans (PROGRAM_SPANS) as [name, start_ns, duration_ns]."""
    from jax.profiler import ProfileData
    raw = TR.load(trace_dir)
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events if e.name in PROGRAM_SPANS]
    raw["program"] = sorted(spans, key=lambda h: h[1])
    return raw


def ms_per_step(obs: dict, keep) -> float | None:
    """Device milliseconds per window step of the (direction, scope) pairs
    that ``keep(direction, scope)`` accepts; None without a scope
    reduction."""
    sc = obs.get("trace", {}).get("scopes")
    if sc is None or not obs.get("steps"):
        return None
    return 1e3 * sum(t for k, t in sc.items()
                     if keep(*k.split("/", 1))) / obs["steps"]


def reduce(raw: dict, op_map: dict[str, str] | None = None,
           chips: int | None = None,
           declared: frozenset[str] | None = None) -> dict:
    """``bench.trace.reduce`` of the record, with, where ``op_map`` is
    given, ``scopes``: per ``direction/label`` (``scope`` over the
    ``declared`` names, by default the program's), device self time in the
    window (seconds, averaged over the chips); ``scope_coverage``: the
    share of that time which carries a program scope; ``breakdown``: the
    top ops named ``<op> <direction>/<scope>`` and the idle gaps
    ``<benchmark span> > <innermost program span>``; ``unscoped_ops``:
    the ops that carry no scope, longest first; and ``program_spans``:
    the program's host spans that start in the window, in seconds."""
    out = TR.reduce({"devices": raw["devices"], "host": raw["host"]}, chips)
    if op_map is None:
        return out
    lo, hi = out["lo"], out["hi"]
    devs = sorted(raw["devices"])[:chips] if chips else sorted(raw["devices"])
    if declared is None:
        declared = declared_scopes()
    tag = {}

    def label(name: str) -> str:
        if name not in tag:
            path = op_map.get(name.split(" ")[0], "")
            tag[name] = f"{direction(path)}/{scope(path, declared)}"
        return tag[name]

    scopes, busy, unscoped = defaultdict(float), {}, defaultdict(float)
    for d in devs:
        inside = [(n, max(s, lo), min(s + du, hi) - max(s, lo))
                  for n, s, du in raw["devices"][d] if s + du > lo and s < hi]
        busy[d] = TR.merge([(s, s + du) for _, s, du in inside])
        for n, t in TR.self_times(inside).items():
            scopes[label(n)] += t / 1e9 / len(devs)
            if label(n).endswith("/unscoped"):
                unscoped[f"{n} {label(n)}"] += t / 1e9 / len(devs)
    total = sum(scopes.values())
    scoped = sum(t for k, t in scopes.items() if not k.endswith("/unscoped"))
    out["scopes"] = dict(sorted(scopes.items(), key=lambda kv: -kv[1]))
    out["scope_coverage"] = scoped / total if total else 0.0
    out["unscoped_ops"] = sorted(unscoped.items(), key=lambda kv: -kv[1])[
        :TR.TOP]
    program = raw.get("program", [])
    out["program_spans"] = {name: [du / 1e9 for n, s, du in program
                                   if n == name and lo <= s < hi]
                            for name in PROGRAM_SPANS}
    busiest = max(devs, key=lambda d: sum(e - s for s, e in busy[d]))
    gaps = sorted(TR._gaps(busy[busiest], lo, hi),
                  key=lambda g: g[0] - g[1])[:TR.TOP]
    names = []
    for s, e in gaps:
        mid = (s + e) // 2
        inner = TR._label(mid, program)         # innermost, or "none"
        names.append([TR._label(mid, raw["host"])
                      + (f" > {inner}" if inner != "none" else ""),
                      (e - s) / 1e9])
    out["breakdown"] = {
        "device_ops": [[f"{n} {label(n)}", t] for n, t in out["top_ops"]],
        "idle_gaps": names}
    return out


# ---------------------------------------------------------------------------
# one traced window of a cell
# ---------------------------------------------------------------------------

def _record_slice(raw: dict, op_map: dict, about: str,
                  half_ns: int = 180_000_000) -> dict:
    """The window's ops on device 0 within ``half_ns`` of the middle of
    one ``input`` span, with a window span of that slice, the benchmark's
    and the program's host spans inside it, and the op-name map of the
    ops it holds."""
    inputs = [h for h in raw["host"] if h[0] == "input"]
    mid = inputs[len(inputs) // 2]
    c = mid[1] + mid[2] // 2
    lo, hi = c - half_ns, c + half_ns
    ops = [o for o in raw["devices"][0] if o[1] + o[2] > lo and o[1] < hi]
    host = [["window", lo, hi - lo]] + [
        h for h in raw["host"] if h[0] != "window" and lo <= h[1] < hi]
    program = [h for h in raw["program"] if lo <= h[1] < hi]
    names = {o[0].split(" ")[0] for o in ops}
    return {"about": about, "devices": {"0": ops}, "host": host,
            "program": program,
            "op_names": {n: p for n, p in op_map.items() if n in names}}


def main(argv=None) -> int:
    import argparse
    import gzip
    import json
    import tempfile
    import time
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--record", default=None,
                    help="write a slice of the trace with its op-name map "
                         "here (.json.gz)")
    args = ap.parse_args(argv)
    from bench import harness as H
    files = H.cell_files(args.workload)
    device = H.require_chip(files["cell"]["chips"])
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # the cache key leaves HLO metadata out by default: an executable
    # compiled from the same ops under other scopes (an older checkout's)
    # would be served, and its text would name that program's scopes
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    sys_, state = H.build_system(files, args.seed)
    state, _, _, _ = H.drive_check_steps(sys_, state)
    op_map = op_names(sys_.compiled.as_text())
    setup_s = time.perf_counter() - t_start
    chips = device["count"]
    with tempfile.TemporaryDirectory(prefix="bench-scopes-") as tmp:
        win = H.run_window(sys_, state, args.seconds, H.CompileCounter(), tmp)
        raw = load(tmp)
    red = reduce(raw, op_map, chips)
    obs = {"steps": win["steps"], "window_s": win["window_s"],
           "tokens_per_step": sys_.tokens_per_step, "chips": chips,
           "tokens_per_s_per_chip": (win["steps"] * sys_.tokens_per_step
                                     / win["window_s"] / chips),
           "trace": red}
    metrics = {m: H.read_metric(m, obs) for m in (
        "step.forward_ms", "step.backward_ms", "step.recompute_ms",
        "step.optimizer_ms", "step.attention_ms", "step.head_loss_ms",
        "input.build_ms")}
    if args.record:
        rec = _record_slice(raw, op_map, f"{device['kind']}, one chip, "
                            f"{args.workload}: 0.36 s of the window's XLA "
                            "Ops line around one input span, with the "
                            "compiled step's op-name map")
        with gzip.open(args.record, "wt") as f:
            json.dump(rec, f)
    per_step = {k: 1e3 * v / win["steps"] for k, v in red["scopes"].items()}
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "device": device,
        "setup_s": setup_s, "steps": win["steps"],
        "window_s": win["window_s"],
        "tokens_per_s_per_chip": obs["tokens_per_s_per_chip"],
        "busy_ms_per_step": 1e3 * red["busy_s"] / win["steps"],
        "idle_frac": red["idle_frac"],
        "scope_coverage": red["scope_coverage"], "metrics": metrics,
        "kernels": {k: {"calls_per_step": v["calls"] / win["steps"],
                        "ms_per_call": 1e3 * v["seconds"] / v["calls"]}
                    for k, v in red["kernels"].items() if v["calls"]},
        "scope_ms_per_step": per_step, "breakdown": red["breakdown"],
        "unscoped_ops": red["unscoped_ops"]}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
