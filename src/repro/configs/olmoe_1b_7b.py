"""OLMoE-1B-7B (allenai, arXiv:2409.02060; OLMoE-1B-7B-0924 config.json).

16 layers of d_model 2048: MHA with 16 heads of 128 and an RMSNorm over the
whole q and k projection widths (2048) before the split into heads, RoPE
theta 10,000; a MoE FFN of 64 experts of width 1024, top 8 of the router's
softmax without renormalisation, no shared expert; RMSNorm eps 1e-5;
untied output head over 50,304 ids.
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="olmoe-1b-7b", family="moe",
    n_layers=16, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=1024,
    vocab_size=50304, head_dim=128, qk_norm="full", rope_theta=10_000.0,
    n_experts=64, experts_per_token=8, norm_topk_prob=False, norm_eps=1e-5,
)
# four KV heads, so that a four-device mesh shards them one a device as the
# four-chip serving engine shards the published 16
REDUCED = CONFIG.reduced().replace(n_heads=4, n_kv_heads=4, d_model=64)
