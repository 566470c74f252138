"""The reduction from a profiler trace to busy time, top operations and
named idle gaps: on hand-made events, and on a small trace recorded on a
TPU v5e and kept with these tests."""

from pathlib import Path

import pytest

from chipbench import devtrace

DATA = Path(__file__).resolve().parent / "data"


def test_union_self_time_and_named_gaps():
    tr = devtrace.Trace(
        ops={0: [("while", 100, 400), ("fusion.a", 120, 200),
                 ("fusion.b", 250, 300), ("copy", 600, 700)],
             1: [("while", 100, 200)]},
        spans=[("step", 50, 450), ("step", 500, 800),
               ("arrival_wait", 450, 560)])
    out = devtrace.reduce(tr, [0, 1])
    assert out["window_s"] == pytest.approx(750e-9)
    assert out["busy_s"] == pytest.approx((400 + 100) / 2 * 1e-9)
    ops = dict(out["device_ops"])
    assert ops["while"] == pytest.approx((170 + 100) / 2 * 1e-9)
    assert ops["fusion.a"] == pytest.approx(40e-9)
    gaps = out["idle_gaps"]
    assert gaps[0] == ["arrival_wait", pytest.approx(200e-9)]
    assert sum(g for _, g in gaps) == pytest.approx(350e-9)


def test_op_names_drop_the_hlo_text():
    assert devtrace.op_name("%fusion.149 = bf16[8,3072]{1,0} fusion(%a), "
                            "kind=kOutput") == "fusion.149"
    assert devtrace.op_name("copy.3") == "copy.3"


def test_no_spans_bound_the_window_by_the_operations():
    tr = devtrace.Trace(ops={0: [("a", 10, 20), ("b", 30, 50)]})
    out = devtrace.reduce(tr, [0])
    assert out["window_s"] == pytest.approx(40e-9)
    assert out["busy_s"] == pytest.approx(30e-9)


@pytest.mark.parametrize("name", sorted(p.name for p in
                                        DATA.glob("*.xplane.pb*")))
def test_a_trace_recorded_on_the_chip(name):
    tr = devtrace.read_xplane(str(DATA / name))
    assert tr.ops and tr.spans
    out = devtrace.reduce(tr, sorted(tr.ops)[:1])
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["device_ops"] and out["idle_gaps"]
    assert {n for n, _ in out["idle_gaps"]} <= \
        {n for n, _, _ in tr.spans} | {"no span"}
