"""Host spans and counters of the program, kept in memory.

A :class:`Spans` recorder stores named intervals on ``time.perf_counter``'s
clock and named counts.  Code that can be traced takes an optional recorder
and holds :data:`OFF` when given none: its methods return at once, so a site
costs an attribute lookup and a call, with no clock reading, no list append
and no profiler annotation.

With ``annotate`` each :meth:`Spans.span` is also a
``jax.profiler.TraceAnnotation`` named ``serve.<name>``, so in a profiler
trace the span sits on the device's clock and an idle gap on the device can
be put down to the host work around it.
"""

from __future__ import annotations

import time
from contextlib import contextmanager, nullcontext

ANNOTATION_PREFIX = "serve."


class Spans:
    """Named intervals (``intervals``: ``(name, t0, t1)`` in seconds) and
    named counts (``counters``)."""

    def __init__(self, *, annotate: bool = False):
        self.annotate = annotate
        self.intervals: list[tuple[str, float, float]] = []
        self.counters: dict[str, int] = {}

    def now(self) -> float:
        """The recorder's clock, for an interval closed by :meth:`record`."""
        return time.perf_counter()

    @contextmanager
    def span(self, name: str):
        """Record the time the ``with`` body takes; yields its start."""
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)
        else:
            ann = nullcontext()
        with ann:
            t0 = time.perf_counter()
            try:
                yield t0
            finally:
                self.intervals.append((name, t0, time.perf_counter()))

    def record(self, name: str, t0: float, t1: float) -> None:
        """Store an interval whose ends were read elsewhere."""
        self.intervals.append((name, t0, t1))

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    def durations(self, name: str, t0: float = float("-inf"),
                  t1: float = float("inf")) -> list[float]:
        """Durations of the ``name`` intervals that started in ``[t0, t1)``."""
        return [b - a for n, a, b in self.intervals
                if n == name and t0 <= a < t1]


class _Off:
    """The recorder switched off: nothing is read, stored or annotated."""

    _NULL = nullcontext()

    def now(self) -> None:
        return None

    def span(self, name: str):
        return self._NULL

    def record(self, name: str, t0, t1) -> None:
        pass

    def count(self, name: str, n: int = 1) -> None:
        pass


OFF = _Off()
