"""What every run needs around the program: the chip check, JAX's compile
events, device memory, the table of peaks, host spans and seeds."""

from __future__ import annotations

import json
import time
from contextlib import contextmanager
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def chips(n: int):
    """The first ``n`` TPU devices; raises :class:`NoChip` otherwise.  There
    is no fallback to the CPU."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no backend: {e}") from e
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU attached (JAX backend: "
                     f"{devices[0].platform})")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device not in the table is
    an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name} (have {sorted(table)})")
    return table[device_kind]


class CompileEvents:
    """Counts of JAX's own compile events, from its monitoring hooks:
    lowerings (every program traced to MLIR, whether then compiled or
    fetched from the persistent cache) and backend compiles with their
    seconds."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring
        self.lowerings = 0
        self.backend_compiles = 0
        self.backend_s = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == self.LOWER:
            self.lowerings += 1
        elif name == self.BACKEND:
            self.backend_compiles += 1
            self.backend_s += secs

    def snapshot(self) -> tuple[int, int, float]:
        return self.lowerings, self.backend_compiles, self.backend_s

    def since(self, snap) -> dict:
        lo, bc, bs = snap
        return {"lowerings": self.lowerings - lo,
                "backend_compiles": self.backend_compiles - bc,
                "backend_compile_s": self.backend_s - bs}


def memory_peak_bytes(devices) -> int:
    """The peak bytes in use on the fullest of ``devices``."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Spans:
    """Host spans of the benchmark's own code, kept in memory.  With
    ``annotate`` each span is also written into the profiler's trace as
    ``bench.<name>``, so idle gaps on the device can be named by what the
    host was doing."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.spans: dict[str, list[tuple[float, float]]] = {}

    @contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            if self.annotate:
                import jax
                with jax.profiler.TraceAnnotation(f"bench.{name}"):
                    yield
            else:
                yield
        finally:
            self.spans.setdefault(name, []).append((t0, time.perf_counter()))

    def durations(self, name: str, t0: float = float("-inf"),
                  t1: float = float("inf")) -> list[float]:
        """Durations of the ``name`` spans that started in ``[t0, t1)``."""
        return [b - a for a, b in self.spans.get(name, []) if t0 <= a < t1]


def jax_key(seed: int):
    """A JAX PRNG key for any whole ``seed``, also one past 32 bits."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
