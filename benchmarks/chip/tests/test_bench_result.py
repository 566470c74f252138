"""The last line's schema, and the checks beside their limits."""

import json
import math

import pytest

from chipbench import cells
from chipbench.result import Check, Record, dumps, result_line


def _record(**kw):
    base = dict(attempted=120, failed=1,
                end_to_end={"ttft_p85_ms": 212.5, "itl_p95_ms": 21.25,
                            "output_tok_s": 900.0},
                checks=[Check("mean_logit_gap", 0.1, 0.5)],
                memory_peak_bytes=3 << 30, window_s=30.0,
                spans={"step": [0.01, 0.03]},
                counters={"model_flops": 1e15, "chips": 1,
                          "peak_flops_per_s": 197e12},
                trace={"busy_s": 2.5, "window_s": 4.0,
                       "device_ops": [["fusion.1", 1.5]],
                       "idle_gaps": [["step", 0.25]]})
    base.update(kw)
    return Record(**base)


DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_untraced_line_holds_the_end_to_end_metrics():
    cell = cells.load_cell("qwen3-0.6b.chat")
    line = result_line(cell, _record(), DEVICE, False, 21.5)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True
    assert set(line["metrics"]) == {"ttft_p85_ms", "itl_p95_ms", "setup_s"}
    assert line["metrics"]["setup_s"] == {"value": 21.5, "unit": "s"}
    assert line["device"]["memory_peak_bytes"] == 3 << 30
    assert line["checks"] == {"mean_logit_gap": {"value": 0.1,
                                                "limit": 0.5}}
    assert json.loads(dumps(line)) == line


def test_traced_line_holds_the_per_layer_metrics_and_breakdown():
    cell = cells.load_cell("qwen3-0.6b.longdoc")
    line = result_line(cell, _record(), DEVICE, True, 21.5)
    assert list(line)[-1] == "checks"
    m = line["metrics"]
    assert set(m) == {"serve.tick_ms", "serve.device_idle", "serve.mfu"}
    assert m["serve.tick_ms"]["value"] == pytest.approx(20.0)
    assert m["serve.device_idle"]["value"] == pytest.approx(37.5)
    assert m["serve.mfu"]["value"] == pytest.approx(
        100 * 1e15 / (30.0 * 197e12))
    assert line["device"]["busy_s"] == 2.5
    assert line["device"]["window_s"] == 4.0
    assert line["breakdown"]["device_ops"] == [["fusion.1", 1.5]]


def test_a_metric_with_nothing_to_read_is_left_out():
    cell = cells.load_cell("qwen3-0.6b.chat")
    line = result_line(cell, _record(trace=None, spans={}), DEVICE, True, 1.0)
    assert line["metrics"] == {}
    assert "breakdown" not in line


@pytest.mark.parametrize("value,limit,ok", [
    (0.1, 0.5, True), (0.5, 0.5, True), (0.6, 0.5, False),
    (math.inf, 0.5, False), (math.nan, 0.5, False), (0.0, None, False)])
def test_check_passes_at_or_below_its_limit(value, limit, ok):
    assert Check("x", value, limit).ok is ok


def test_a_check_that_read_no_number_still_prints_its_line():
    cell = cells.load_cell("qwen3-0.6b.chat")
    rec = _record(checks=[Check("mean_logit_gap", math.inf, 0.5)])
    line = json.loads(dumps(result_line(cell, rec, DEVICE, False, 1.0)))
    assert line["correct"] is False
    assert line["checks"]["mean_logit_gap"] == {"value": "inf",
                                                "limit": 0.5}


def test_a_failed_check_makes_the_run_incorrect():
    rec = _record(checks=[Check("a", 0.1, 0.5), Check("b", 2.0, 1.0)])
    assert rec.correct is False
    assert _record(checks=[]).correct is False
