"""deepseek-v2-lite [moe] — 27L d_model=2048 16H, MLA without q-LoRA
(kv_lora 512, qk_nope 128, qk_rope 64, v_head 128), YaRN (factor 40 over
4096 positions), layer 0 dense (SwiGLU 10944), layers 1-26 MoE: 64 routed
experts of 1408 (softmax gate, greedy top-6, not renormalised) and 2 shared.
vocab=102400.  [hf: deepseek-ai/DeepSeek-V2-Lite config.json]

The full model holds every routed expert; the chip benchmark runs the share
of one chip of eight (``experts_held=8``), see
``benchmarks/chip/configs/deepseek-v2-lite.json``.
"""

from repro.models.common import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,           # the leading dense layer's SwiGLU
    first_k_dense_replace=1,
    moe_d_ff=1408,
    n_experts=64,
    n_shared_experts=2,
    top_k=6,
    norm_topk_prob=False,
    vocab=102400,
    mla=True,
    q_lora_rank=0,
    kv_lora_rank=512,
    qk_rope_dim=64,
    qk_nope_dim=128,
    v_head_dim=128,
    rope_theta=1e4,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    norm_eps=1e-6,
)


def smoke():
    return CONFIG.scaled(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
                         d_ff=160, moe_d_ff=32, n_experts=8,
                         n_shared_experts=2, top_k=3, vocab=512,
                         kv_lora_rank=32, qk_rope_dim=16, qk_nope_dim=16,
                         v_head_dim=16, yarn_original_max_pos=64,
                         dtype="float32")
