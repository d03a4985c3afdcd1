"""Trinity-Mini (afmoe) [hf:arcee-ai/Trinity-Mini, config.json].

32 layers of hidden 2048: three sliding-window layers (window 2048, RoPE)
then one full-attention layer (no position encoding), repeated; 32 query
and 4 KV heads of 128; the first 2 layers a dense SwiGLU of 6144, the other
30 sigmoid-routed over 128 experts of 1024, 8 a token, normalised and
scaled by 2.826, plus one shared expert; QK-norm, a sigmoid output gate,
sandwich norms and muP embeddings (times sqrt(2048)); vocab 200192, untied.
"""
import dataclasses

from repro.configs.base import ModelConfig

LAYER_TYPES = tuple(("sliding_attention",) * 3 + ("full_attention",)) * 8

CONFIG = ModelConfig(
    name="trinity-mini", family="afmoe",
    source="hf:arcee-ai/Trinity-Mini",
    num_layers=32, d_model=2048, num_heads=32, num_kv_heads=4, head_dim=128,
    d_ff=6144, vocab_size=200192, rope_theta=10000.0,
    sliding_window=2048, layer_types=LAYER_TYPES,
    num_dense_layers=2, num_experts=128, experts_per_token=8,
    moe_d_ff=1024, shared_d_ff=1024, route_scale=2.826,
    embed_scale=2048 ** 0.5,
)

#: One chip's share of an expert-parallel deployment over 8 TPU v5e chips:
#: every layer's attention, norms, router (all 128 outputs), shared expert
#: and the whole vocabulary, and 16 of each MoE layer's 128 routed experts
#: (0-15).  The all-to-all exchange is left out: the chip computes its own
#: experts' part for the tokens routed to them.  4.99 B parameters.
EP8_SHARE = dataclasses.replace(CONFIG, name="trinity-mini-16of128E",
                                experts_held=16, expert_offset=0)
