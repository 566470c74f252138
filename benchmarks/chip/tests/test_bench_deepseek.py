"""The DeepSeek-V2-Lite cell: its configuration and traffic load by name,
its module's operation and byte counts hold by hand, the expert-batch
reader reads the program's routed counters, and the serving entry runs it
at tiny widths with the check passing."""

import copy
import re
from pathlib import Path

import jax
import pytest

from chipbench import cells
from chipbench.result import Record

HERE = Path(__file__).resolve().parents[1]
CELL = cells.load_cell("deepseek-v2-lite.batch")

# a tiny document in the configuration's keys: 2 layers, the first dense
TINY = {"num_hidden_layers": 2, "first_k_dense_replace": 1,
        "hidden_size": 4, "num_attention_heads": 2, "qk_nope_head_dim": 3,
        "qk_rope_head_dim": 2, "v_head_dim": 5, "kv_lora_rank": 6,
        "intermediate_size": 7, "moe_intermediate_size": 8,
        "n_routed_experts": 2, "num_experts_per_tok": 2,
        "n_shared_experts": 2, "vocab_size": 11,
        "deployment": {"published_n_routed_experts": 4, "expert_block": 0}}
# by hand, per layer: wq 4*2*5=40, wkv_a 4*8=32, kv_norm 6, wkv_b 6*2*8=96,
# wo 2*5*4=40, ln1 + ln2 8: 222 attention and norms; dense SwiGLU 3*4*7=84;
# router 4*4=16, shared 3*4*16=192; one expert 3*4*8=96; head 44, ln_f 4
NON_EXPERT = 222 + 84 + 222 + 16 + 192 + 44 + 4
EXPERT = 96
LATENT_ROW = 2 * (6 + 2)


def test_the_cell_loads_by_name():
    assert CELL.chips == 1
    assert CELL.config["entry"] == "serve"
    assert CELL.config["model"]["experts_held"] == 8
    assert CELL.config["model"]["n_experts"] == 64
    assert CELL.config["reduced"] == ["n_routed_experts"]
    assert CELL.config["serving"] == {"max_slots": 32, "prefill_chunk": 1}
    assert CELL.traffic == {
        "generator": "backlog", "depth": 32,
        "prompt_lens": [[256, 4], [512, 3], [1024, 2], [2048, 1]],
        "output_lens": [[128, 4], [256, 3], [512, 3]], "max_len": 2560}
    assert {m["name"] for m in CELL.end_to_end} == {
        "itl_p95_ms", "output_tok_s", "setup_s"}
    assert "serve.moe_tokens_per_expert" in {m["name"] for m in
                                             CELL.per_layer}
    assert callable(CELL.reference.token_gaps)


def test_decode_bytes_by_hand():
    # one busy lane at position 3: the held experts it reaches, 2 (1 - 2/4)
    # = 1 per expert layer; 4 latent rows in each of 2 layers
    assert CELL.reference.decode_bytes(TINY, [3]) == 2 * (
        NON_EXPERT + 4 + 1 * EXPERT + 4 * LATENT_ROW)
    # two lanes: 2 (1 - (2/4)^2) = 1.5 experts a layer
    assert CELL.reference.decode_bytes(TINY, [0, 9]) == 2 * (
        NON_EXPERT + 2 * 4 + 1.5 * EXPERT + 11 * LATENT_ROW)


def test_decode_and_prefill_flops_by_hand():
    # per layer: projections 40 + 32 + 40 = 112; absorbed decode adds
    # q_nope into the latent 2*3*6 = 36 and the value out of it 2*6*5 = 60;
    # prefill expands keys and values, 6*2*8 = 96
    attn_decode, attn_prefill = 2 * (112 + 36 + 60), 2 * (112 + 96)
    pair_decode, pair_prefill = 2 * 2 * (2 * 6 + 2), 2 * 2 * (3 + 2 + 5)
    # dense 2*84; expert layer: shared 2*192, router 2*16, and the top-2 at
    # 2 of 4 experts held: one expert evaluation, 2*96
    ffn = 2 * 84 + 2 * 192 + 2 * 16 + 2 * 96
    head = 2 * 44
    assert CELL.reference.decode_flops(TINY, 4) == (
        2 * (attn_decode + 5 * pair_decode) + ffn + head)
    assert CELL.reference.prefill_flops(TINY, 3) == (
        3 * (2 * attn_prefill + ffn) + 2 * pair_prefill * 6 + head)


def test_the_published_yarn_numbers():
    inv_freq, factor, scale = CELL.reference.yarn(CELL.config)
    base = 1e4 ** -(2 * jax.numpy.arange(32) / 64)
    assert factor == 1.0
    assert scale == pytest.approx(0.114721, abs=1e-6)
    # plain below index 10, over 40 from 23 on
    assert inv_freq[:11] == pytest.approx(base[:11], rel=1e-6)
    assert inv_freq[23:] == pytest.approx(base[23:] / 40, rel=1e-6)


def _record(counters):
    return Record(attempted=1, failed=0, end_to_end={}, checks=[],
                  memory_peak_bytes=0, window_s=1.0,
                  program_counters=counters)


@pytest.mark.parametrize("counters,expected", [
    ({"moe_pairs": 960, "moe_pairs_here": 300, "moe_expert_slots": 100},
     3.0),
    ({"moe_pairs_here": 0, "moe_expert_slots": 8}, 0.0),
    ({"prefill_tokens": 5}, None),
    ({"moe_pairs_here": 7, "moe_expert_slots": 0}, None),
])
def test_tokens_per_expert_reads_the_routed_counters(counters, expected):
    reader = CELL.metric_reader("serve.moe_tokens_per_expert")
    assert reader.read(_record(counters)) == expected


def test_no_harness_file_reads_a_deepseek_key():
    keys = re.compile(r"[\"'](kv_lora_rank|n_routed_experts|"
                      r"num_experts_per_tok|moe_intermediate_size|"
                      r"qk_rope_head_dim|rope_scaling|first_k_dense_replace|"
                      r"deployment)[\"']")
    for d in ("chipbench", "entries", "metrics"):
        for f in (HERE / d).glob("*.py"):
            assert not keys.search(f.read_text()), f


TINY_DEEPSEEK = {"num_hidden_layers": 3, "hidden_size": 64,
                 "num_attention_heads": 4, "qk_nope_head_dim": 16,
                 "qk_rope_head_dim": 16, "v_head_dim": 16,
                 "kv_lora_rank": 32, "intermediate_size": 160,
                 "moe_intermediate_size": 32, "n_routed_experts": 4,
                 "num_experts_per_tok": 3, "vocab_size": 512}


def tiny_cell(name: str = "deepseek-v2-lite.batch"):
    """The DeepSeek-V2-Lite cell at tiny widths, as ``tiny.py`` cuts the
    qwen3 cells: 3 layers (one dense), 4 of 8 routed experts held (the
    second block), YaRN over an original context of 64 positions, so its
    ramp falls inside the tiny prompts."""
    cell = cells.load_cell(name)
    doc = copy.deepcopy(cell.config)
    doc.update(TINY_DEEPSEEK)
    doc["rope_scaling"]["original_max_position_embeddings"] = 64
    doc["deployment"].update(published_n_routed_experts=8, expert_block=1)
    doc["model"].update(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
                        d_ff=160, moe_d_ff=32, n_experts=8, experts_held=4,
                        expert_block=1, top_k=3, vocab=512, kv_lora_rank=32,
                        qk_rope_dim=16, qk_nope_dim=16, v_head_dim=16,
                        yarn_original_max_pos=64)
    doc["serving"].update(max_slots=4)
    doc["check"].update(sample_tokens=64, mean_logit_gap=0.01)
    cell.config = doc
    tr = copy.deepcopy(cell.traffic)
    tr.update(depth=4, max_len=64, prompt_lens=[[8, 2], [16, 1], [32, 1]],
              output_lens=[[4, 2], [8, 1], [16, 1]])
    cell.traffic = tr
    return cell


def test_the_tiny_cell_serves_correctly_and_counts_its_experts():
    """Traced, with no trace directory: the program's recorder is on, so
    decode fetches the routed counts; 4 of 8 experts are held, so about
    half the top-3 pairs land here."""
    cell = tiny_cell()
    rec, _ = cell.entry.run(cell, 2**33 + 11, 2.0, True, jax.devices()[:1])
    assert rec.correct, rec.checks
    c = rec.program_counters
    assert c["moe_pairs"] > 0 and c["moe_expert_slots"] > 0
    # per decode execution, busy lanes (at most 4) x top-3 x 2 expert
    # layers and 4 held experts x 2 expert layers
    assert c["moe_pairs"] % (3 * 2) == 0
    assert c["moe_pairs"] <= 3 * c["moe_expert_slots"]
    assert 0.3 < c["moe_pairs_here"] / c["moe_pairs"] < 0.7
    assert cell.metric_reader("serve.moe_tokens_per_expert").read(rec) > 0
    assert rec.counters["decode_bytes"] > 0
    # what serve.mfu reads on the chip (the CPU has no peak to divide by)
    assert rec.counters["model_flops"] > 0
