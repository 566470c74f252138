"""``BENCHMARK.json`` and the files it names: every cell finds its
configuration, reference, entry, traffic and generator, every per-layer
metric its reader, by name alone; and each configuration's file states the
sizes that the program is built with."""

import re

import pytest

from chipbench import cells

BENCH = cells.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_names_and_units():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(name):
    cell = cells.load_cell(name)
    assert cell.entry.run and cell.generator.make and cell.reference
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.metric_reader(m["name"]).read)
        assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_qwen3_file_states_what_the_program_runs():
    doc = cells.load_cell("qwen3-0.6b.chat").config
    m = doc["model"]
    pairs = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
             "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
             "intermediate_size": "d_ff", "vocab_size": "vocab",
             "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"}
    for hf, ours in pairs.items():
        assert doc[hf] == m[ours], hf
    assert m["dtype"] == doc["torch_dtype"] and m["qk_norm"] is True
    assert doc["tie_word_embeddings"] is False
    assert doc["reduced"] == ["tie_word_embeddings"]
