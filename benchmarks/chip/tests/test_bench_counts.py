"""Each configuration brings its own operation and byte counts: qwen3's
module gives the dense counts, the serving entry reads every count through
the cell's configuration module and no architecture key of its file, and
``serve.decode_roofline`` reads the decode program's share of its roofline
from those counts and the device trace, over the traced sub-window alone;
what the program records stays apart from the benchmark's yardsticks."""

import json
import re
import time
import types
from pathlib import Path

import jax
import pytest
import repro.core.spans

import tiny
from chipbench import cells, devtrace, timeline
from chipbench.result import Record

HERE = Path(__file__).resolve().parents[1]
QWEN = cells.load_cell("qwen3-0.6b.chat")
PARAM_BYTES = 1_192_099_840      # every bf16 weight but the embedding table
EMBED_ROW = 1024 * 2
KV_ROW = 28 * 2 * 8 * 128 * 2


@pytest.mark.parametrize("prompt_len,expected", [
    (1, 1192198144.0), (128, 114947784704.0), (1024, 1022630821888.0),
    (2048, 2285468647424.0)])
def test_qwen3_prefill_flops(prompt_len, expected):
    assert QWEN.reference.prefill_flops(QWEN.config, prompt_len) == expected


@pytest.mark.parametrize("position,expected", [
    (0, 1192198144.0), (127, 1221328896.0), (1279, 1485570048.0),
    (2079, 1669070848.0)])
def test_qwen3_decode_flops(position, expected):
    assert QWEN.reference.decode_flops(QWEN.config, position) == expected


@pytest.mark.parametrize("positions", [[0], [399, 400, 401],
                                       [127, 1279, 2079, 5, 6, 7, 8, 9]])
def test_qwen3_decode_bytes_by_hand(positions):
    expected = (PARAM_BYTES + EMBED_ROW * len(positions)
                + KV_ROW * sum(p + 1 for p in positions))
    assert QWEN.reference.decode_bytes(QWEN.config, positions) == expected


def test_no_harness_file_reads_an_architecture_key():
    keys = re.compile(r"head_dim|intermediate_size|num_key_value_heads")
    for d in ("chipbench", "entries", "metrics"):
        for f in (HERE / d).glob("*.py"):
            assert not keys.search(f.read_text()), f


def _stub_reference(real, full_doc: dict):
    """A configuration module with counts of its own: weights and reference
    from qwen3's module at the tiny sizes of ``full_doc``, whatever document
    the entry passes."""
    return types.SimpleNamespace(
        make_weights=lambda doc, key, device: real.make_weights(
            full_doc, key, device),
        token_gaps=lambda doc, *a, **k: real.token_gaps(full_doc, *a, **k),
        prefill_flops=lambda doc, n: 1e9,
        decode_flops=lambda doc, position: 1.0,
        decode_bytes=lambda doc, positions: 10**6 + len(positions))


def test_the_serving_entry_counts_with_the_configuration_module(monkeypatch):
    cell = tiny.serving_cell()
    full = cell.config
    stub = _stub_reference(cell.reference, full)
    monkeypatch.setattr(cells.Cell, "reference", property(lambda self: stub))
    # only what the entry may read: the architecture lives in the module
    cell.config = {k: full[k] for k in ("name", "entry", "model", "serving",
                                        "check")}
    rec, _ = cell.entry.run(cell, 2**33 + 7, 2.0, False, jax.devices()[:1])
    c = rec.counters
    decoded = c["decode_flops"]                 # one per decoded token
    assert decoded > 0 and decoded == int(decoded)
    prefills = (c["model_flops"] - decoded) / 1e9
    assert prefills >= 1 and prefills == int(prefills)
    assert c["decode_steps"] >= 1
    assert c["decode_bytes"] == 10**6 * c["decode_steps"] + decoded
    assert c["peak_hbm_bytes_per_s"] is None     # no peaks off the chip
    # untraced, the decode is counted over the whole window
    assert c["traced_flops"] == c["model_flops"]
    assert c["traced_s"] == pytest.approx(rec.window_s)
    assert rec.correct, rec.checks


def _log(uid, prompt_len, times, replica=0):
    return timeline.RequestLog(uid=uid, due=0.0, prompt_len=prompt_len,
                               out_len=len(times), in_window=True,
                               replica=replica, token_times=list(times))


COUNTS = types.SimpleNamespace(
    prefill_flops=lambda doc, n: 1000.0 * n,
    decode_flops=lambda doc, position: 1.0 + position,
    decode_bytes=lambda doc, positions: 10**6 + 10 * sum(positions))


def test_decode_work_is_counted_over_the_traced_sub_window_alone():
    # steps end at 1, 2, ..., 10; the trace holds the steps ending at 5-7.
    # Eight lanes decode through steps 1-4 and 8-10, two lanes in 5-7.
    logs = [_log(f"busy{i}", 10, [1, 2, 3, 4]) for i in range(6)]
    logs += [_log(f"late{i}", 20, [8, 9, 10]) for i in range(6)]
    logs += [_log(f"long{i}", 100 * (i + 1), range(1, 11))
             for i in range(2)]
    work = serve_entry().window_work(COUNTS, {}, logs, (0.0, 10.0),
                                     (4.5, 7.5))
    # in the sub-window: the two long lanes' tokens 4-6, at positions
    # 103-105 and 203-205, one decode execution per step
    positions = [[100 + k, 200 + k] for k in (3, 4, 5)]
    assert work["decode_steps"] == 3
    assert work["decode_bytes"] == sum(10**6 + 10 * sum(p)
                                       for p in positions)
    assert work["decode_flops"] == sum(1.0 + q for p in positions
                                       for q in p)
    assert work["traced_flops"] == work["decode_flops"]
    assert work["traced_s"] == pytest.approx(3.0)
    # the whole window: every prefill and every decode
    prefills = 6 * 10 + 6 * 20 + 100 + 200
    decodes = (6 * sum(1.0 + 10 + j for j in range(3))
               + 6 * sum(1.0 + 20 + j for j in range(2))
               + sum(1.0 + 100 * (i + 1) + j for i in range(2)
                     for j in range(9)))
    assert work["model_flops"] == 1000.0 * prefills + decodes


def test_a_prefill_in_the_sub_window_counts_for_the_step_not_the_decode():
    logs = [_log("new", 16, [5, 5, 6]), _log("old", 8, [1, 2, 3, 4, 5, 6])]
    work = serve_entry().window_work(COUNTS, {}, logs, (0.0, 6.0),
                                     (4.5, 6.0))
    # step 5 decodes "old" at 11 and "new" at 16 (its token 1), step 6
    # both again; "new"'s token 0 is its prefill's
    assert work["decode_steps"] == 2
    assert work["decode_bytes"] == (2 * 10**6 + 10 * (11 + 16 + 12 + 17))
    assert work["traced_flops"] == (1000.0 * 16 + (1 + 11) + (1 + 16)
                                    + (1 + 12) + (1 + 17))


def test_the_trace_span_leaves_the_profiler_stalls_out(monkeypatch, tmp_path):
    calls = []

    def stall(name):
        def f(*a, **k):
            calls.append((name, time.perf_counter()))
            time.sleep(0.05)
        return f

    monkeypatch.setattr(jax.profiler, "start_trace", stall("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace", stall("stop"))
    t0 = time.perf_counter()
    sub = devtrace.SubWindow(str(tmp_path), t0, 0.02, 0.02)
    assert sub.span is None
    sub.poll(time.perf_counter())
    time.sleep(0.03)
    sub.poll(time.perf_counter())
    a, b = sub.span
    (_, t_start), (_, t_stop) = calls
    assert t_start + 0.05 <= a < b <= t_stop
    assert devtrace.SubWindow(None, t0, 1.0, 0.02).span is None


class _ProgramSpans(repro.core.spans.Spans):
    """A program recorder that also takes names the benchmark uses for its
    own yardsticks: a ``step`` interval, ``model_flops`` and ``chips``."""

    def record(self, name, t0, t1):
        super().record(name, t0, t1)
        super().record("step", t0, t0 + 100.0)

    def count(self, name, n=1):
        super().count(name, n)
        super().count("model_flops", 10**20)
        super().count("chips", 10**6)


def test_the_program_cannot_name_over_a_yardstick(monkeypatch):
    cell = tiny.serving_cell("qwen3-0.6b.longdoc")
    full = cell.config
    stub = _stub_reference(cell.reference, full)
    monkeypatch.setattr(cells.Cell, "reference", property(lambda self: stub))
    monkeypatch.setattr(repro.core.spans, "Spans", _ProgramSpans)
    # traced, with no trace directory: the program's recorder is on and
    # the profiler off
    rec, _ = cell.entry.run(cell, 2**33 + 8, 2.0, True, jax.devices()[:1])
    assert rec.program_counters["model_flops"] >= 10**20
    assert rec.program_spans["step"] and max(rec.program_spans["step"]) > 99
    c = rec.counters
    assert c["chips"] == 1
    decoded = c["decode_flops"]
    prefills = (c["model_flops"] - decoded) / 1e9
    assert prefills >= 1 and prefills == int(prefills)
    assert max(rec.spans["step"]) < 99
    for name in ("serve.tick_ms", "serve.mfu"):
        reader = cell.metric_reader(name)
        apart = Record(**dict(vars(rec), program_spans={},
                              program_counters={}))
        assert reader.read(rec) == reader.read(apart), name
    assert cell.metric_reader("serve.prefill_us_per_tok").read(rec) > 0
    assert rec.correct, rec.checks


def serve_entry():
    return cells.load_module(HERE / "entries" / "serve.py")


def _record(counters, modules, window_s=1.0):
    trace = None if modules is None else {
        "busy_s": 1.0, "window_s": 1.0, "device_ops": [], "idle_gaps": [],
        "modules": modules}
    return Record(attempted=1, failed=0, end_to_end={}, checks=[],
                  memory_peak_bytes=0, window_s=window_s, counters=counters,
                  trace=trace)


PEAKS = {"peak_flops_per_s": 197e12, "peak_hbm_bytes_per_s": 819e9}
DECODE = {"decode_flops": 8 * 1.2e9, "decode_bytes": 2 * 1.3e9,
          "decode_steps": 2}
MODULES = {"jit_serve_decode": [4, 4 * 4.25e-3], "jit_serve_prefill": [1, 1]}


def _roofline(rec):
    return QWEN.metric_reader("serve.decode_roofline").read(rec)


def test_decode_roofline_is_bounded_by_bytes_here():
    got = _roofline(_record(dict(PEAKS, **DECODE), MODULES))
    assert got == pytest.approx(100 * (1.3e9 / 819e9) / 4.25e-3)
    assert 30 < got < 45


def test_decode_roofline_takes_the_operations_where_they_bound():
    counters = dict(PEAKS, decode_flops=2 * 1e12, decode_bytes=2 * 1e6,
                    decode_steps=2)
    got = _roofline(_record(counters, {"jit_serve_decode": [1, 0.01]}))
    assert got == pytest.approx(100 * (1e12 / 197e12) / 0.01)


@pytest.mark.parametrize("counters,modules", [
    (dict(PEAKS, **DECODE), None),                            # untraced
    (dict(PEAKS, **DECODE), {}),                              # no modules
    (dict(PEAKS, **DECODE), {"jit_serve_prefill": [1, 1.0]}),  # no decode
    (dict(PEAKS, **DECODE), {"jit_serve_decode": [0, 0.0]}),
    (dict(DECODE, peak_flops_per_s=None, peak_hbm_bytes_per_s=None),
     MODULES),                                                # off the chip
    (dict(PEAKS, decode_flops=0.0, decode_bytes=0, decode_steps=0),
     MODULES),                                                # no decode
    (dict(PEAKS), MODULES),
])
def test_decode_roofline_with_nothing_to_read_gives_none(counters, modules):
    assert _roofline(_record(counters, modules)) is None


def test_every_configuration_module_gives_the_serving_functions():
    bench = cells.load_benchmark()
    for c in bench["configs"]:
        doc = json.loads((cells.ROOT / c["file"]).read_text())
        if doc["entry"] != "serve":
            continue
        mod = cells.load_module(HERE / "configs" / f"{c['name']}.py")
        for fn in ("make_weights", "token_gaps", "prefill_flops",
                   "decode_flops", "decode_bytes"):
            assert callable(getattr(mod, fn)), (c["name"], fn)


def _step_mfu(rec):
    return QWEN.metric_reader("serve.step_mfu").read(rec)


def test_step_mfu_reads_the_traced_sub_window_not_the_window():
    counters = dict(PEAKS, chips=1, model_flops=45 * 2e12,
                    traced_flops=4 * 1e12, traced_s=4.0)
    rec = _record(counters, MODULES, window_s=45.0)
    assert _step_mfu(rec) == pytest.approx(100 * 1e12 / 197e12)
    mfu = QWEN.metric_reader("serve.mfu").read(rec)
    assert mfu == pytest.approx(100 * 2e12 / 197e12)


@pytest.mark.parametrize("counters", [
    dict(PEAKS, chips=1, traced_flops=1e12),                 # no sub-window
    dict(PEAKS, chips=1, traced_flops=0.0, traced_s=4.0),    # no tokens
    dict(PEAKS, chips=1, model_flops=1e12),                  # whole window
    dict(chips=1, traced_flops=1e12, traced_s=4.0,
         peak_flops_per_s=None),                             # off the chip
])
def test_step_mfu_with_nothing_to_read_gives_none(counters):
    assert _step_mfu(_record(counters, MODULES)) is None
