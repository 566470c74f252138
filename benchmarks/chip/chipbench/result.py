"""The record a run leaves and the result line made from it.

An entry returns a :class:`Record`.  The harness reads each per-layer
metric from it with that metric's own ``read`` and prints the contract's
last line: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` (traced runs) and, last, ``checks``: every number compared
with its limit.
"""

from __future__ import annotations

import json
import math
import sys
from dataclasses import dataclass, field


@dataclass
class Check:
    """One number compared with its limit; it passes at or below it."""

    name: str
    value: float
    limit: float | None

    @property
    def ok(self) -> bool:
        return (self.limit is not None and math.isfinite(self.value)
                and self.value <= self.limit)


@dataclass
class Record:
    """What one run measured.  ``end_to_end`` holds values by metric name;
    the rest is what per-layer readers read.  ``spans`` and ``counters``
    are the benchmark's own; what the program's recorder took in the
    window is kept apart, in ``program_spans`` and ``program_counters``,
    so that no name the program chooses can stand in for a yardstick."""

    attempted: int
    failed: int
    end_to_end: dict[str, float]
    checks: list[Check]
    memory_peak_bytes: int
    window_s: float
    spans: dict[str, list[float]] = field(default_factory=dict)
    counters: dict[str, float] = field(default_factory=dict)
    trace: dict | None = None          # devtrace.reduce's output
    program_spans: dict[str, list[float]] = field(default_factory=dict)
    program_counters: dict[str, float] = field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def result_line(cell, rec: Record, device: dict, trace: bool,
                setup_s: float) -> dict:
    """The contract's last line for ``cell`` from ``rec``."""
    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = cell.metric_reader(m["name"]).read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = dict(rec.end_to_end, setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=int(rec.memory_peak_bytes))
    line = {"correct": rec.correct, "attempted": int(rec.attempted),
            "failed": int(rec.failed), "metrics": metrics, "device": dev}
    if trace and rec.trace is not None:
        dev["busy_s"] = rec.trace["busy_s"]
        dev["window_s"] = rec.trace["window_s"]
        line["breakdown"] = {"device_ops": rec.trace["device_ops"],
                             "idle_gaps": rec.trace["idle_gaps"]}
    line["checks"] = {c.name: {"value": c.value if math.isfinite(c.value)
                               else str(c.value), "limit": c.limit}
                      for c in rec.checks}
    return line


def print_checks(rec: Record, file=sys.stderr) -> None:
    """Each compared number beside its limit, as the last lines of
    standard error."""
    for c in rec.checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=file, flush=True)


def dumps(line: dict) -> str:
    return json.dumps(line, allow_nan=False)
