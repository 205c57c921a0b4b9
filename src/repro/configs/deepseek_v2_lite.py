"""deepseek-v2-lite — MLA with a direct q projection, shared + routed experts
[hf:deepseek-ai/DeepSeek-V2-Lite, config.json; arXiv:2405.04434].

27L d_model=2048, vocab=102400, untied head, RMSNorm eps 1e-6, SiLU. MLA
with no q-LoRA: 16 heads, kv_lora_rank=512, qk_nope=128, qk_rope=64,
v_head=128, no attention bias; YaRN rope (theta 1e4, factor 40, original
4096 positions, beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707,
so the cos/sin tables are not rescaled).
Layer 0 is a dense SwiGLU of width 10944 (first_k_dense_replace=1); layers
1-26 are MoE: 64 routed experts of width 1408, greedy top-6 over a softmax
with norm_topk_prob false and routed_scaling_factor 1 (the gates are not
scaled), and 2 shared experts run as one SwiGLU of width 2816 for every
token.

The router is frozen and carries no adapter, and the training loss is the LM
loss alone: a balance term over the pack would couple packed adapters.
"""
from repro.configs.base import AttentionConfig, ModelConfig, MoEConfig, register


@register
def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        family="moe",
        n_layers=27,
        d_model=2048,
        d_ff=10_944,
        vocab_size=102_400,
        attention=AttentionConfig(
            n_heads=16,
            n_kv_heads=16,
            head_dim=192,  # qk head dim = nope(128) + rope(64)
            rope_theta=10_000.0,
            q_lora_rank=0,
            kv_lora_rank=512,
            qk_nope_head_dim=128,
            qk_rope_head_dim=64,
            v_head_dim=128,
            yarn_factor=40.0,
            yarn_original_max_positions=4096,
            yarn_beta_fast=32.0,
            yarn_beta_slow=1.0,
            yarn_mscale_all_dim=0.707,
        ),
        moe=MoEConfig(
            n_experts=64, top_k=6, d_expert=1408, moe_every=1,
            first_k_dense=1, n_shared_experts=2, norm_topk_prob=False,
            impl="ep",
        ),
        max_seq_len=163_840,
        lora_targets=("q", "kv", "o", "gate", "up", "down"),
        citation="hf:deepseek-ai/DeepSeek-V2-Lite (MLA, shared + routed MoE)",
    )
