"""Mistral-v0.3 7B — paper evaluation model [hf:mistralai/Mistral-7B-Instruct-v0.3]."""
import dataclasses

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="mistral-7b", family="dense", source="paper §6.2",
    num_layers=32, d_model=4096, num_heads=32, num_kv_heads=8,
    d_ff=14336, vocab_size=32768, rope_theta=1_000_000.0,
)

#: One stage of a 4-stage pipeline deployment on one TPU v5e chip: every
#: width as published (d_model 4096, 32 heads, 8 KV heads, head_dim 128,
#: d_ff 14336, vocab 32768), bfloat16, depth cut to 8 of the 32 layers —
#: the other 24 would sit on three further chips as pipeline stages.  The
#: stage keeps both the embedding and the LM head, so it serves tokens end
#: to end.  2.01 B parameters (about 4.0 GB); KV costs 32 KiB per token
#: (8 layers x K and V x 8 heads x 128 x 2 B).
PIPELINE_STAGE = dataclasses.replace(CONFIG, name="mistral-7b-8of32L",
                                     num_layers=8)
