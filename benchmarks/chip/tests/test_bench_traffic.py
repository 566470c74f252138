"""Traffic generators: the same seed gives the same mix, every block holds
the same work, and a new mix is found by its name alone."""

import json
import shutil
from collections import Counter

import numpy as np
import pytest

from chipbench import cells

SEED = 2**33 + 17          # past 32 bits, as a run's seed may be


def _take(gen, n):
    out = []
    while len(out) < n:
        due = gen.next_due()
        out.extend(gen.release(1e9 if due is None else due, 0))
    return out[:n]


@pytest.mark.parametrize("traffic", ["chat", "longdoc"])
def test_same_seed_same_mix(traffic):
    cell = cells.load_cell(f"qwen3-0.6b.{traffic}")
    a = _take(cell.generator.make(cell.traffic, SEED, 1000), 40)
    b = _take(cell.generator.make(cell.traffic, SEED, 1000), 40)
    c = _take(cell.generator.make(cell.traffic, SEED + 1, 1000), 40)
    assert [(r.due, r.out_len, r.prompt.tolist()) for r in a] == \
        [(r.due, r.out_len, r.prompt.tolist()) for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]


@pytest.mark.parametrize("seed", [1, SEED])
def test_every_block_asks_for_the_same_work(seed):
    cell = cells.load_cell("qwen3-0.6b.chat")
    gen = cell.generator.make(cell.traffic, seed, 1000)
    reqs = _take(gen, 3 * gen.block)
    want_p = Counter(gen.prompt_block)
    want_o = Counter(gen.output_block)
    gaps = np.diff([0.0] + [r.due for r in reqs])
    for k in range(3):
        blk = reqs[k * gen.block:(k + 1) * gen.block]
        assert Counter(len(r.prompt) for r in blk) == want_p
        assert Counter(r.out_len for r in blk) == want_o
        assert sorted(gaps[k * gen.block:(k + 1) * gen.block]) == \
            pytest.approx(sorted(gen.gap_block))
    assert np.mean(gen.gap_block) == pytest.approx(
        1.0 / cell.traffic["rate_per_s"])


def test_backlog_keeps_the_queue_full():
    cell = cells.load_cell("qwen3-0.6b.longdoc")
    gen = cell.generator.make(cell.traffic, SEED, 1000)
    assert len(gen.release(0.0, 0)) == cell.traffic["depth"]
    assert len(gen.release(1.0, cell.traffic["depth"] - 3)) == 3
    assert gen.release(2.0, cell.traffic["depth"] + 5) == []


def test_a_new_mix_is_found_by_its_name(tmp_path, monkeypatch):
    """A mix added as a data file, and a cell naming it, run through the
    same generator without a line of code changed."""
    here = tmp_path / "chip"
    shutil.copytree(cells.HERE, here, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    burst = dict(json.loads((here / "traffic" / "chat.json").read_text()),
                 rate_per_s=40.0)
    (here / "traffic" / "chat_burst.json").write_text(json.dumps(burst))
    bench = cells.load_benchmark()
    bench["workloads"].append({"name": "qwen3-0.6b.chat_burst",
                               "config": "qwen3-0.6b",
                               "traffic": "chat_burst", "chips": 1,
                               "why": "test"})
    monkeypatch.setattr(cells, "HERE", here)
    cell = cells.load_cell("qwen3-0.6b.chat_burst", bench)
    gen = cell.generator.make(cell.traffic, SEED, 1000)
    reqs = _take(gen, 100)
    assert reqs[-1].due == pytest.approx(100 / 40.0, rel=0.2)


def test_the_rate_a_sweep_offers_reaches_the_generator():
    import jax
    import tiny
    from chipbench.runtime import Spans
    cell = tiny.serving_cell()
    sess = cell.entry.Session(cell, 1, jax.devices()[:1], Spans())
    due = []
    for rate in (5.0, 50.0):
        logs, _ = sess.drive(1, 1.0, None, 0.0, give_up_s=5.0,
                             traffic=dict(cell.traffic, rate_per_s=rate))
        while sess.server.busy:
            sess.server.step()
        due.append(sum(r.in_window for r in logs))
    assert due[1] > 5 * due[0]
