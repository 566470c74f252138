"""The profiler's trace of a traced window, reduced to device metrics.

A traced run records a sub-window of its measured window with
``jax.profiler``.  The reduction reads the ``.xplane.pb`` file with JAX's own
``ProfileData`` and keeps three kinds of events:

* the XLA operations on each TPU (planes ``/device:TPU:<n>``, line
  ``XLA Ops``), from which busy time is the union of their intervals;
* the benchmark's own host spans (``bench.<name>`` annotations), which
  bound the window and name each idle gap by what the host was doing.

Device and host events share the trace's clock.
"""

from __future__ import annotations

import glob
import gzip
import os
import re
import time
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "serve."
MODULE_HASH = re.compile(r"\(\d+\)$")
TRACE_SECONDS = 4.0      # the traced sub-window: a few hundred serving steps


@dataclass
class Trace:
    """Events of one trace, as plain tuples (name, start_ns, end_ns)."""

    ops: dict[int, list[tuple[str, int, int]]] = field(default_factory=dict)
    modules: dict[int, list[tuple[str, int, int]]] = field(
        default_factory=dict)
    spans: list[tuple[str, int, int]] = field(default_factory=list)
    program_spans: list[tuple[str, int, int]] = field(default_factory=list)


def op_name(event_name: str) -> str:
    """An operation's name without the HLO text the trace may carry after
    it (``%fusion.3 = bf16[8,3072] fusion(...)`` reads ``fusion.3``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def read_xplane(path: str) -> Trace:
    """The device operations and the benchmark's host spans of a trace
    (an ``.xplane.pb`` file, or one compressed with gzip)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                tr.ops.setdefault(int(m.group(1)), []).extend(
                    (op_name(ev.name), ev.start_ns,
                     ev.start_ns + ev.duration_ns)
                    for ev in line.events)
            elif m and line.name == MODULES_LINE:
                tr.modules.setdefault(int(m.group(1)), []).extend(
                    (MODULE_HASH.sub("", ev.name), ev.start_ns,
                     ev.start_ns + ev.duration_ns)
                    for ev in line.events)
            elif not m:
                for ev in line.events:
                    for prefix, out in ((SPAN_PREFIX, tr.spans),
                                        (PROGRAM_PREFIX, tr.program_spans)):
                        if ev.name.startswith(prefix):
                            out.append((ev.name[len(prefix):], ev.start_ns,
                                        ev.start_ns + ev.duration_ns))
    return tr


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir},"
                                f" found {len(files)}")
    return files[0]


def _union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The union of ``intervals`` clipped to ``[lo, hi]``, sorted."""
    out: list[list[int]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _self_times(events) -> dict[str, int]:
    """Nanoseconds of each operation name not covered by an operation
    nested inside it (a loop's body runs inside the loop's own event)."""
    out: dict[str, int] = defaultdict(int)
    stack: list[list] = []            # [name, start, end, nested_ns]

    def close(item):
        name, a, b, nested = item
        out[name] += (b - a) - nested

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            close(stack.pop())
        if stack:
            b = min(b, stack[-1][2])
            stack[-1][3] += b - a
        stack.append([name, a, b, 0])
    while stack:
        close(stack.pop())
    return out


def reduce(tr: Trace, chips: list[int], top: int = 10) -> dict:
    """Busy and window seconds averaged over ``chips``, the operations that
    took most device time, and the longest idle gaps on the first chip named
    by the host span that covers each gap's middle.

    The window runs from the first to the last host span; with no spans,
    from the first to the last device operation."""
    if tr.spans:
        lo = min(a for _, a, _ in tr.spans)
        hi = max(b for _, _, b in tr.spans)
    else:
        every = [e for c in chips for e in tr.ops.get(c, [])]
        if not every:
            raise ValueError("the trace holds no device operation")
        lo = min(a for _, a, _ in every)
        hi = max(b for _, _, b in every)
    busy_ns = []
    self_ns: dict[str, float] = defaultdict(float)
    for c in chips:
        evs = [e for e in tr.ops.get(c, []) if e[2] > lo and e[1] < hi]
        busy_ns.append(sum(b - a for a, b in _union(
            [(a, b) for _, a, b in evs], lo, hi)))
        for name, ns in _self_times(evs).items():
            self_ns[name] += ns / len(chips)
    modules: dict[str, list] = {}
    for c in chips:
        for name, a, b in tr.modules.get(c, []):
            if a >= lo and b <= hi:
                m = modules.setdefault(name, [0, 0.0])
                m[0] += 1 / len(chips)
                m[1] += (b - a) / 1e9 / len(chips)
    first = _union([(a, b) for _, a, b in tr.ops.get(chips[0], [])], lo, hi)
    edges = [lo] + [x for ab in first for x in ab] + [hi]
    named = tr.spans + tr.program_spans
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            mid = (a + b) / 2
            covering = [(e - s, n) for n, s, e in named if s <= mid < e]
            gaps.append((min(covering)[1] if covering else "no span",
                         (b - a) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    ops = sorted(self_ns.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": sum(busy_ns) / len(chips) / 1e9,
            "window_s": (hi - lo) / 1e9,
            "device_ops": [[n, ns / 1e9] for n, ns in ops],
            "modules": modules,
            "idle_gaps": [[n, s] for n, s in gaps[:top]]}


class SubWindow:
    """Traces the middle ``trace_s`` seconds of a window of ``seconds`` that
    opened at ``t0``.  The driving loop calls :meth:`poll` between steps,
    so tracing starts and stops only there; :meth:`close` stops a trace
    still running when the window ends.  :attr:`span` is what the trace
    holds on the host's clock (``time.perf_counter``): from the return of
    ``start_trace`` to the call of ``stop_trace``, so that neither call's
    own stall lies inside it."""

    def __init__(self, trace_dir: str | None, t0: float, seconds: float,
                 trace_s: float):
        self.trace_dir = trace_dir
        self.start_at = t0 + max(0.0, (seconds - trace_s) / 2)
        self.trace_s = trace_s
        self.stop_at: float | None = None
        self.running = False
        self.started: float | None = None
        self.stopped: float | None = None

    @property
    def span(self) -> tuple[float, float] | None:
        """``(started, stopped)`` once a trace has been taken, else None."""
        if self.started is None or self.stopped is None:
            return None
        return self.started, self.stopped

    def poll(self, now: float) -> None:
        import jax
        if not self.trace_dir:
            return
        if self.stop_at is None and now >= self.start_at:
            jax.profiler.start_trace(self.trace_dir)
            self.started = time.perf_counter()
            self.running, self.stop_at = True, now + self.trace_s
        elif self.running and now >= self.stop_at:
            self.close()

    def close(self) -> None:
        if self.running:
            import jax
            self.stopped = time.perf_counter()
            jax.profiler.stop_trace()
            self.running = False
