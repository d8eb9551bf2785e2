"""From a profiler trace to device busy time, per-op time and idle gaps.

``load(dir)`` reads the ``.xplane.pb`` the JAX profiler wrote into a plain
record: per device, the ops of its "XLA Ops" line; the host spans the
benchmark put around its own calls (``window``, ``input``, ``dispatch``,
``readback``).  ``reduce(raw)`` turns that record into the numbers the
per-layer metrics read, among them every Pallas kernel's time and calls.
Times are nanoseconds on the trace's clock, which the host and device
planes share.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

__all__ = ["HOST_SPANS", "load", "reduce", "op_name", "kernel_name",
           "self_times", "merge"]

HOST_SPANS = ("window", "input", "dispatch", "readback")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
TOP = 10


def load(trace_dir: str) -> dict:
    """The raw record of the one trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {paths}")
    pd = ProfileData.from_file(paths[0])
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [[op_name(e.name), int(e.start_ns),
                             int(e.duration_ns)] for e in line.events]
            devices[int(m.group(1))] = sorted(ops, key=lambda o: o[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, int(e.start_ns), int(e.duration_ns)]
                         for e in line.events if e.name in HOST_SPANS]
    return {"devices": devices, "host": sorted(host, key=lambda h: h[1])}


def op_name(hlo: str) -> str:
    """An op's HLO instruction name (``fusion.12`` of ``%fusion.12 = ...``);
    for a Pallas kernel (a ``tpu_custom_call``) the kernel's own name
    follows, where the instruction carries one."""
    m = re.match(r"%?([^\s=]+)", hlo)
    name = m.group(1) if m else hlo
    if 'custom_call_target="tpu_custom_call"' in hlo:
        k = re.search(r'kernel_name\W+([\w.]+)', hlo) or re.search(
            r'"name"\W+([\w.]+)', hlo)
        name += " tpu_custom_call" + (f" {k.group(1)}" if k else "")
    return name


def kernel_name(op: str) -> str | None:
    """The Pallas kernel an op of the trace runs: the kernel's own name
    where ``op_name`` found one, else its instruction's name without the
    ``.N`` suffix (``flash_attn_fwd.16 tpu_custom_call`` ->
    ``flash_attn_fwd``); None for an op that is no kernel."""
    words = op.split(" ")
    if "tpu_custom_call" not in words:
        return None
    return words[2] if len(words) > 2 else re.sub(r"\.\d+$", "", words[0])


def self_times(ops: list) -> dict:
    """Per op name, device time not covered by the ops nested inside it
    (a ``while`` holds the ops of its body on the same line)."""
    out = defaultdict(float)
    stack: list[list] = []          # [name, end, child time]
    for n, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out[top[0]] += top[3] - top[2]
        if stack:
            stack[-1][2] += d
        stack.append([n, s + d, 0, d])
    for top in stack:
        out[top[0]] += top[3] - top[2]
    return out


def merge(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _label(t: int, spans: list) -> str:
    """The innermost benchmark span (other than the window) at time t."""
    best = None
    for name, s, d in spans:
        if name != "window" and s <= t < s + d:
            if best is None or d < best[1]:
                best = (name, d)
    return best[0] if best else "none"


def reduce(raw: dict, chips: int | None = None) -> dict:
    """Busy and idle time of the traced window, averaged over the cell's
    chips and taken on the busiest; per-op device self time (averaged over
    the chips); the longest idle gaps of the busiest chip, labelled by the
    host span they fall in; ``kernels``: per Pallas kernel (``kernel_name``),
    its device self seconds in the window and its calls that start there,
    both averaged over the chips."""
    windows = [h for h in raw["host"] if h[0] == "window"]
    if not windows:
        raise RuntimeError("the trace holds no window span")
    _, lo, dur = windows[-1]
    hi = lo + dur
    devs = sorted(raw["devices"])[:chips] if chips else sorted(raw["devices"])
    if not devs:
        raise RuntimeError("the trace holds no device ops")
    busy, per_op, gaps_by_dev = {}, defaultdict(float), {}
    kernels = defaultdict(lambda: {"seconds": 0.0, "calls": 0.0})
    for d in devs:
        ops = raw["devices"][d]
        spans = merge(_clip([(s, s + du) for _, s, du in ops], lo, hi))
        busy[d] = sum(e - s for s, e in spans) / 1e9
        gaps_by_dev[d] = _gaps(spans, lo, hi)
        inside = [(n, max(s, lo), min(s + du, hi) - max(s, lo))
                  for n, s, du in ops if s + du > lo and s < hi]
        for n, t in self_times(inside).items():
            per_op[n] += t / 1e9
            if (k := kernel_name(n)) is not None:
                kernels[k]["seconds"] += t / 1e9 / len(devs)
        for n, s, _ in ops:
            if lo <= s < hi and (k := kernel_name(n)) is not None:
                kernels[k]["calls"] += 1 / len(devs)
    busiest = max(devs, key=lambda d: busy[d])
    window_s = dur / 1e9
    gaps = sorted(gaps_by_dev[busiest], key=lambda g: g[0] - g[1])[:TOP]
    host = raw["host"]
    return {
        "window_s": window_s,
        "busy_s": sum(busy.values()) / len(devs),
        "busiest_busy_s": busy[busiest],
        "idle_frac": 1.0 - busy[busiest] / window_s,
        "top_ops": [[n, t / len(devs)] for n, t in
                    sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_label((s + e) // 2, host), (e - s) / 1e9]
                      for s, e in gaps],
        "host_spans": {name: [d / 1e9 for n, s, d in host
                              if n == name and lo <= s < hi]
                       for name in HOST_SPANS if name != "window"},
        "kernels": dict(sorted(kernels.items())),
        "lo": lo, "hi": hi,
    }


def _gaps(spans: list[tuple[int, int]], lo: int, hi: int) -> list:
    """The idle intervals of [lo, hi) between merged busy spans."""
    out, prev = [], lo
    for s, e in spans:
        if s > prev:
            out.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        out.append((prev, hi))
    return out
