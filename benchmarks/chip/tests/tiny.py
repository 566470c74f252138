"""Cells cut to a size the CPU runs in seconds, for the fault and control
tests: the same code paths at tiny widths.

The limits here are the tiny cells' own, set on the CPU for the fault
tests: sound runs of the tiny served model read a mean logit gap of
0-0.00063 over three seeds, a decode that hands back its cache unchanged
0.50-0.60 and a token altered where it is produced 2.8-3.2; sound runs of the tiny search read an error gap of 0, and
an altered score 0.05.  On the CPU JAX's default precision is exact
float32, so the tiny search's reference is exact float32 (on a TPU it is
float32 with matrix operands rounded to bfloat16, one pass).  At tiny
widths the int8 control reads only a few times the sound runs
(``test_bench_control``); the cells' limits at their own sizes come from
``calibrate.py`` on the chip."""

import copy

from chipbench import cells

TINY_MODEL = {"num_hidden_layers": 2, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "intermediate_size": 128, "vocab_size": 512}


def serving_cell(name: str = "qwen3-0.6b.chat"):
    cell = cells.load_cell(name)
    doc = copy.deepcopy(cell.config)
    doc.update(TINY_MODEL)
    doc["model"].update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                        head_dim=16, d_ff=128, vocab=512)
    doc["check"].update(sample_tokens=64, mean_logit_gap=0.01)
    cell.config = doc
    tr = copy.deepcopy(cell.traffic)
    tr.update(max_len=64, prompt_lens=[[8, 2], [16, 1], [32, 1]],
              output_lens=[[4, 2], [8, 1], [16, 1]])
    if "rate_per_s" in tr:
        tr["rate_per_s"] = 20.0
    cell.traffic = tr
    return cell


# The search cell's entries, as ``BENCHMARK.json`` held them before the cell
# was left out (the program crashes the TPU compiler on some candidates; see
# PERF.md): its harness is kept, and tested here, for its return.
SEARCH_BENCH = {
    "configs": [{"name": "gevo-2fcnet",
                 "file": "benchmarks/chip/configs/gevo-2fcnet.json"}],
    "workloads": [{"name": "gevo-2fcnet.search", "config": "gevo-2fcnet",
                   "traffic": "search", "chips": 1}],
    "end_to_end": [{"name": "evals_per_s"}, {"name": "setup_s"}],
    "per_layer": [{"name": "search.compile_ms"},
                  {"name": "search.device_idle"}],
}


def search_cell():
    cell = cells.load_cell("gevo-2fcnet.search", SEARCH_BENCH)
    doc = copy.deepcopy(cell.config)
    doc["check"].update(max_checked=6, mean_error_gap=0.0045,
                        reference="f32", probe="f64")
    cell.config = doc
    return cell
