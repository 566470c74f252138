"""The program's own serving spans in the benchmark: the device's
``XLA Modules`` line, idle gaps named by the program's ``serve.`` spans
while the window stays the benchmark's, and the five readers of what the
program records."""

from pathlib import Path

import pytest

from chipbench import cells, devtrace
from chipbench.result import Record

DATA = Path(__file__).resolve().parent / "data"
CHAT = DATA / "chat-v5e-0.25s.xplane.pb.gz"
READERS = ("serve.queue_wait_ms", "serve.prefill_us_per_tok",
           "serve.decode_ms", "serve.host_ms", "serve.decode_device_ms")


def _read(name, rec):
    return cells.load_cell("qwen3-0.6b.chat").metric_reader(name).read(rec)


def test_the_chip_trace_names_its_modules():
    tr = devtrace.read_xplane(str(CHAT))
    lam = [(b - a) / 1e6 for n, a, b in tr.modules[0] if n == "jit__lambda"]
    assert len(lam) == 17
    assert sum(1 for d in lam if d == pytest.approx(10.31, abs=0.01)) == 16
    assert sum(1 for n, _, _ in tr.modules[0] if n == "jit_scatter") == 2
    assert all("(" not in n for n, _, _ in tr.modules[0])
    out = devtrace.reduce(tr, [0])
    # the window and the busy time read as they did before modules were read
    assert out["busy_s"] == pytest.approx(0.174433496, abs=1e-12)
    assert out["window_s"] == pytest.approx(0.21111787, abs=1e-12)
    # inside the window: the prefill (4.75 ms) and 15 of the decodes
    count, seconds = out["modules"]["jit__lambda"]
    assert count == 16
    assert seconds == pytest.approx(0.004745 + 15 * 0.010307, abs=2e-5)


def test_program_spans_name_gaps_but_do_not_bound_the_window():
    tr = devtrace.Trace(
        ops={0: [("a", 100, 200), ("b", 400, 500)]},
        modules={0: [("jit_serve_decode", 100, 200),
                     ("jit_serve_decode", 400, 500),
                     ("jit_serve_decode", 560, 700)]},
        spans=[("step", 50, 550)],
        program_spans=[("tick", 0, 600), ("fetch", 180, 420),
                       ("dispatch", 20, 90)])
    out = devtrace.reduce(tr, [0])
    assert out["window_s"] == pytest.approx(500e-9)
    assert out["busy_s"] == pytest.approx(200e-9)
    assert out["idle_gaps"][0] == ["fetch", pytest.approx(200e-9)]
    assert [n for n, _ in out["idle_gaps"]] == ["fetch", "dispatch", "step"]
    # a module execution counts only inside the window
    assert out["modules"] == {"jit_serve_decode":
                              [2, pytest.approx(200e-9)]}


def _record(spans, counters=None, trace=None):
    """A record whose program recorder took ``spans`` and ``counters``."""
    return Record(attempted=1, failed=0, end_to_end={}, checks=[],
                  memory_peak_bytes=0, window_s=1.0, trace=trace,
                  program_spans=spans, program_counters=counters or {})


@pytest.mark.parametrize("name,expected", [
    ("serve.queue_wait_ms", 85.0),
    ("serve.prefill_us_per_tok", 1e6 * 0.03 / 600),
    ("serve.decode_ms", 11.0),
    ("serve.host_ms", 1e3 * (0.036 - 0.024) / 3),
    ("serve.decode_device_ms", 10.0),
])
def test_each_reader_reads_what_the_program_recorded(name, expected):
    rec = _record(
        spans={"step": [0.012] * 3, "tick": [0.012] * 3,
               "fetch": [0.008] * 3, "decode": [0.010, 0.012],
               "prefill": [0.01, 0.02],
               "queue": [0.001 * i for i in range(101)]},
        counters={"prefill_tokens": 600},
        trace={"busy_s": 1.0, "window_s": 1.0, "device_ops": [],
               "idle_gaps": [],
               "modules": {"jit_serve_decode": [4, 0.04]}})
    assert _read(name, rec) == pytest.approx(expected)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_gives_none(name):
    assert _read(name, _record({})) is None
    assert _read(name, _record({"step": [0.01]}, trace={
        "busy_s": 1.0, "window_s": 1.0, "device_ops": [],
        "idle_gaps": []})) is None


@pytest.mark.parametrize("name", READERS[:-1])
def test_a_reader_of_the_program_reads_nothing_of_the_benchmarks(name):
    spans = {"tick": [0.012] * 3, "fetch": [0.008] * 3, "decode": [0.01],
             "prefill": [0.01], "queue": [0.001]}
    rec = Record(attempted=1, failed=0, end_to_end={}, checks=[],
                 memory_peak_bytes=0, window_s=1.0, spans=spans,
                 counters={"prefill_tokens": 600})
    assert _read(name, rec) is None
