"""qwen3-moe-30b-a3b [moe] — hf:Qwen/Qwen3-30B-A3B (arXiv:2505.09388).

48L, d_model=2048, 32H (GQA kv=4, head_dim=128) with per-head q/k
RMSNorm before RoPE, no attention bias, RoPE theta 1e6, rms_norm_eps
1e-6; every layer a softmax router over 128 experts, top-8 renormalised
over the 8 (norm_topk_prob), SwiGLU experts of width 768, no shared
expert; vocab=151936, untied head.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-moe-30b-a3b", family="moe",
    num_layers=48, d_model=2048, num_heads=32, num_kv_heads=4,
    head_dim=128, d_ff=768, vocab_size=151_936,
    num_experts=128, num_experts_per_tok=8, qk_norm=True,
    rope_theta=1_000_000.0, norm_eps=1e-6, tie_embeddings=False,
)

SMOKE_CONFIG = ModelConfig(
    name="qwen3-moe-30b-a3b-smoke", family="moe",
    num_layers=2, d_model=128, num_heads=4, num_kv_heads=2,
    head_dim=32, d_ff=64, vocab_size=307,
    num_experts=4, num_experts_per_tok=2, qk_norm=True,
    rope_theta=1_000_000.0, norm_eps=1e-6, tie_embeddings=False,
)
