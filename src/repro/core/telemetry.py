"""Structured telemetry for the consensus stack (schema ``telemetry/v1``).

Two pieces, both zero-cost when unused (DESIGN.md §Observability):

* **Typed per-step counters/gauges.**  The jitted step already returns a
  metrics dict; ``ConsensusConfig(telemetry=True)`` adds the extra
  in-trace counters (bytes shipped, saturation census, resync outcomes,
  staleness retirements) as metric outputs, and :class:`Telemetry` is
  the host-side registry + JSONL sink they stream into, one record per
  step.  With ``telemetry=False`` the step trace is bit-identical to a
  telemetry-less build — tests/test_wire.py pins the jaxpr.

* **Host events.**  Decisions that happen *between* traces — controller
  codec picks with their candidate table, plan re-tiers, membership
  epoch transitions, resync outcomes — are appended to the same sink as
  ``kind="event"`` records.

Where the time goes is not recorded here: the program carries named
scopes (``embed``, ``attention``, ``exchange/encode``, ...) that the JAX
profiler's device trace shows (``launch/train.py --profile-dir``).

The wire-byte arithmetic that used to live in three places
(``ConsensusRuntime.wire_bytes_per_step``, the ``wire_bytes_delivered``
metric, benchmark MB/step math) is unified here as
:class:`WireAccounting`: shipped == delivered + dropped by construction,
and the cross-check test (tests/test_telemetry.py) asserts the traced
delivered metric against the host keep-table oracles for every loss
model on every transport.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from typing import Any

__all__ = [
    "SCHEMA", "EVENT_KINDS", "STEP_METRICS",
    "WireAccounting", "timing_gate", "validate_record", "Telemetry",
]

SCHEMA = "telemetry/v1"

#: host-event record names (``kind="event"``, field ``event``)
EVENT_KINDS = ("codec_decision", "plan_retier", "membership_epoch",
               "resync", "wire_plan", "kernel_fallback", "run_end")

#: the typed registry of known per-step metrics: "counter" values are
#: non-negative per-step totals (bytes, event counts), "gauge" values are
#: instantaneous levels (fractions, norms, rates).  record_step validates
#: against this; unknown keys must be registered first.
STEP_METRICS: dict[str, str] = {
    "loss": "gauge",
    "lr": "gauge",
    "aux": "gauge",
    "collectives_per_step": "counter",
    "wire_bytes_per_step": "counter",
    "overflow_frac": "gauge",
    "residual_norm": "gauge",
    "push_sum_weight": "gauge",
    "wire_bytes_delivered": "counter",
    "delivered_frac": "gauge",
    "deadline_miss_frac": "gauge",
    "active_nodes": "gauge",
    "consensus_err": "gauge",
    # -- ConsensusConfig(telemetry=True) extras --------------------------
    "wire_bytes_shipped": "counter",
    "wire_bytes_inner": "counter",
    "wire_bytes_outer": "counter",
    "saturated_count": "counter",
    "resync_fired": "counter",
    "resync_ok": "gauge",
    "staleness_retired": "counter",
    # -- host-side timing riders -----------------------------------------
    "step_s": "gauge",
    "consensus_exchange_s": "gauge",
    "consensus_overhead_frac": "gauge",
}


# ---------------------------------------------------------------------------
# Unified wire-byte accounting
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WireAccounting:
    """The one source of wire-byte arithmetic for a configured exchange.

    ``payload_bytes`` is ONE ring direction's flat payload (codes +
    scales, excluding the push-sum trailer); a step ships
    ``directions`` of them.  ``resync_bytes_amortized`` is the
    epoch-boundary fp32 x_tilde exchange averaged over the schedule
    period (an upper bound — membership schedules stop paying it once
    clamped).  The invariant every caller leans on::

        shipped_payload == delivered_bytes(d) + dropped_bytes(d)

    for any delivered direction count ``d`` in [0, directions] — traced
    or host-side.

    Under hierarchical consensus (DESIGN.md §14) ``inner_bytes`` carries
    the *intra-pod* level — the uncompressed fp32 delta all-reduce each
    pod member pays per step (ring all-reduce model,
    ``HierarchySpec.inner_bytes_per_step``).  It is lossless (the fault
    models act on the inter-pod wire only), so the shipped ==
    delivered + dropped invariant stays a statement about the OUTER
    payload; ``shipped_per_step`` totals both levels.
    """

    payload_bytes: int                 # one direction, codes + scales
    trailer_bytes: int = 0             # push-sum fp32 weight trailer
    directions: int = 2                # ring directions per step
    resync_bytes_amortized: float = 0.0
    inner_bytes: float = 0.0           # intra-pod fp32 level (hierarchy)

    @property
    def bytes_per_direction(self) -> int:
        return self.payload_bytes + self.trailer_bytes

    @property
    def shipped_payload(self) -> float:
        """Payload bytes put on the wire per step (all directions,
        excluding the amortized resync) — the delivered+dropped total."""
        return float(self.directions * self.bytes_per_direction)

    @property
    def shipped_per_step(self) -> float:
        """Static bytes/step accounting incl. amortized resync and the
        intra-pod inner level — what
        ``ConsensusRuntime.wire_bytes_per_step`` reports."""
        return (self.shipped_payload + self.resync_bytes_amortized
                + self.inner_bytes)

    def delivered_bytes(self, delivered_directions):
        """Bytes that arrived, given how many directions survived (a
        host float or a traced scalar — the arithmetic is the same)."""
        return float(self.bytes_per_direction) * delivered_directions

    def dropped_bytes(self, delivered_directions):
        return float(self.bytes_per_direction) * (
            self.directions - delivered_directions)

    # -- constructors ----------------------------------------------------
    @classmethod
    def for_plan(cls, plan, push_sum: bool = False,
                 resync_bytes_amortized: float = 0.0) -> "WireAccounting":
        """Accounting of a packed/pipelined/async WirePlan wire."""
        from repro.core import wireplan
        return cls(payload_bytes=int(plan.payload_bytes),
                   trailer_bytes=(wireplan.PUSH_SUM_TRAILER_BYTES
                                  if push_sum else 0),
                   resync_bytes_amortized=resync_bytes_amortized)

    @classmethod
    def for_per_leaf(cls, layout, push_sum: bool = False,
                     resync_bytes_amortized: float = 0.0
                     ) -> "WireAccounting":
        """Accounting of the historical per-leaf int8 wire: each leaf is
        padded to its TILE_N-aligned blockify height, so it ships MORE
        rows than the row-granular packed payload for the same tree."""
        from repro.core import wireplan
        from repro.kernels import ops as kops
        rows = sum(kops.padded_block_rows(s.size) for s in layout.slots)
        return cls(payload_bytes=rows * kops.payload_width(),
                   trailer_bytes=(wireplan.PUSH_SUM_TRAILER_BYTES
                                  if push_sum else 0),
                   resync_bytes_amortized=resync_bytes_amortized)

    @classmethod
    def uncompressed(cls, n_params: int, itemsize: int) -> "WireAccounting":
        """The fp32/bf16 DGD baseline wire (no codec, no trailer)."""
        return cls(payload_bytes=n_params * itemsize)


def timing_gate(*timings: dict, noise_tol: float = 0.5) -> float:
    """Variance-aware speedup gate (PR 6): the more run-to-run spread the
    timed paths showed, the looser the acceptable ratio.  ``timings`` are
    timing dicts carrying ``timing_spread`` (IQR/median over repeats).
    At zero spread the gate is ``noise_tol``; spread s relaxes it by
    1/(1 + 3 s)."""
    spread = max((t.get("timing_spread", 0.0) or 0.0) for t in timings)
    return noise_tol / (1.0 + 3.0 * spread)


# ---------------------------------------------------------------------------
# telemetry/v1 records + validation
# ---------------------------------------------------------------------------

def _fail(reason: str) -> str:
    return reason


def validate_record(rec: Any) -> str | None:
    """Validate one telemetry/v1 record; returns None when valid, else a
    human-readable reason (pure stdlib — no jsonschema dependency)."""
    if not isinstance(rec, dict):
        return _fail("record is not an object")
    if rec.get("schema") != SCHEMA:
        return _fail(f"schema must be {SCHEMA!r}, got {rec.get('schema')!r}")
    kind = rec.get("kind")
    if kind == "meta":
        if not isinstance(rec.get("run_id"), str) or not rec["run_id"]:
            return _fail("meta.run_id must be a non-empty string")
        if not isinstance(rec.get("config"), dict):
            return _fail("meta.config must be an object")
        sha = rec.get("git_sha")
        if sha is not None and not isinstance(sha, str):
            return _fail("meta.git_sha must be a string or null")
        return None
    if kind == "step":
        step = rec.get("step")
        if not isinstance(step, int) or step < 0:
            return _fail("step.step must be a non-negative integer")
        metrics = rec.get("metrics")
        if not isinstance(metrics, dict) or not metrics:
            return _fail("step.metrics must be a non-empty object")
        for k, v in metrics.items():
            ty = rec.get("types", {}).get(k) or STEP_METRICS.get(k)
            if ty is None:
                return _fail(f"step.metrics[{k!r}] is not a registered "
                             "counter or gauge")
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                return _fail(f"step.metrics[{k!r}] must be a number")
            if not math.isfinite(v):
                return _fail(f"step.metrics[{k!r}] must be finite")
            if ty == "counter" and v < 0:
                return _fail(f"counter step.metrics[{k!r}] must be >= 0")
        return None
    if kind == "event":
        name = rec.get("event")
        if name not in EVENT_KINDS:
            return _fail(f"event.event must be one of {EVENT_KINDS}, "
                         f"got {name!r}")
        step = rec.get("step")
        if step is not None and (not isinstance(step, int) or step < 0):
            return _fail("event.step must be a non-negative integer or null")
        if not isinstance(rec.get("data"), dict):
            return _fail("event.data must be an object")
        return None
    return _fail(f"unknown record kind {kind!r}")


def validate_file(path: str) -> list[str]:
    """Validate every JSONL record in ``path``; returns the list of
    ``"line N: reason"`` problems (empty == clean)."""
    problems = []
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                problems.append(f"line {i}: invalid JSON ({e})")
                continue
            why = validate_record(rec)
            if why is not None:
                problems.append(f"line {i}: {why}")
    return problems


# ---------------------------------------------------------------------------
# The host-side registry + sink
# ---------------------------------------------------------------------------

class Telemetry:
    """Typed counter/gauge registry + schema-versioned JSONL sink.

    Writes ``{out_dir}/telemetry-{run_id}.jsonl`` (one record per line,
    ``meta`` first).
    """

    def __init__(self, run_id: str, out_dir: str = "obs",
                 config: dict | None = None, git_sha: str | None = None):
        self.run_id = run_id
        self.out_dir = out_dir
        os.makedirs(out_dir, exist_ok=True)
        self.path = os.path.join(out_dir, f"telemetry-{run_id}.jsonl")
        self._types = dict(STEP_METRICS)
        self._extra_types: dict[str, str] = {}
        self._f = open(self.path, "w")
        self._write({"schema": SCHEMA, "kind": "meta", "run_id": run_id,
                     "git_sha": git_sha, "config": dict(config or {}),
                     "time_unix": time.time()})

    # -- registry --------------------------------------------------------
    def register(self, name: str, kind: str) -> None:
        """Declare a metric outside the built-in registry."""
        if kind not in ("counter", "gauge"):
            raise ValueError(f"kind must be 'counter' or 'gauge', "
                             f"got {kind!r}")
        self._types[name] = kind
        self._extra_types[name] = kind

    def _write(self, rec: dict) -> None:
        self._f.write(json.dumps(rec, sort_keys=True) + "\n")

    # -- records ---------------------------------------------------------
    def record_step(self, step: int, metrics: dict) -> None:
        """Append one per-step record; values are coerced to float and
        validated against the registry (counters must be >= 0)."""
        clean = {}
        for k, v in metrics.items():
            ty = self._types.get(k)
            if ty is None:
                raise ValueError(
                    f"unregistered metric {k!r}; Telemetry.register it as "
                    "a counter or gauge first")
            v = float(v)
            if not math.isfinite(v):
                raise ValueError(f"metric {k!r} is not finite: {v}")
            if ty == "counter" and v < 0:
                raise ValueError(f"counter {k!r} must be >= 0, got {v}")
            clean[k] = v
        rec = {"schema": SCHEMA, "kind": "step", "step": int(step),
               "metrics": clean}
        if self._extra_types:
            rec["types"] = dict(self._extra_types)
        self._write(rec)

    def event(self, name: str, step: int | None = None, **data) -> None:
        """Append one host event record (``name`` in EVENT_KINDS)."""
        if name not in EVENT_KINDS:
            raise ValueError(f"unknown event {name!r}; expected one of "
                             f"{EVENT_KINDS}")
        self._write({"schema": SCHEMA, "kind": "event", "event": name,
                     "step": None if step is None else int(step),
                     "data": data})

    # -- lifecycle -------------------------------------------------------
    def flush(self) -> None:
        self._f.flush()

    def close(self) -> None:
        if self._f.closed:
            return
        self._f.flush()
        self._f.close()

    def __enter__(self) -> "Telemetry":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
