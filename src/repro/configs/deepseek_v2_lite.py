"""DeepSeek-V2-Lite 16B-A2.4B — MoE decoder with multi-head latent attention
(MLA): 27 layers at d_model 2048, 16 heads whose keys and values come from
a 512-wide latent plus one 64-wide rotary key all heads share (qk_nope 128,
qk_rope 64, v 128; no query compression), YaRN rotary scaling (factor 40
over 4096 positions), a leading dense SwiGLU layer of width 10944, then 26
layers of 64 routed experts of width 1408 (softmax, greedy top-6, weights
not renormalised) beside 2 shared experts (one SwiGLU of width 2816), and
an output head of its own, untied from the embedding table.
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json]

Tri-LoRA adapts wq, wkv_a and wo; the up-projection wkv_b stays frozen so
decode can absorb it into the query and the output (DESIGN.md §17)."""
from repro.models.config import ModelConfig, register

CONFIG = register(ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=10944,
    vocab_size=102400,
    tie_embeddings=False,
    rope_theta=10_000.0,
    pos_type="rope",
    layer_pattern=("mla",),
    mlp_type="swiglu",
    norm_type="rmsnorm",
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    yarn_factor=40.0,
    yarn_original_max=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    n_experts=64,
    top_k=6,
    moe_d_ff=1408,
    shared_d_ff=2816,
    norm_topk=False,
    n_dense_layers=1,
    lora_targets=("wq", "wkv_a", "wo"),
    source="arXiv:2405.04434",
))
