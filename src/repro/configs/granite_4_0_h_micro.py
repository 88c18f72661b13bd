"""Granite-4.0-H-Micro — hybrid Mamba-2/attention decoder, 40 layers.
[hf:ibm-granite/granite-4.0-h-micro]

36 Mamba-2 layers and 4 attention layers (at 5, 15, 25, 35: a period
of 10), each followed by a SwiGLU MLP.  Attention is GQA without
position embedding (``position_embedding_type: nope``) at scale
``attention_multiplier``; µP-style multipliers on the embedding, on
every residual branch and on the logits.
"""
import dataclasses

from repro.configs.base import ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-micro", family="hybrid",
    num_layers=40, d_model=2048, num_heads=32, num_kv_heads=8,
    d_ff=8192, vocab_size=100352, head_dim=64,
    layer_pattern=("mamba",) * 5 + ("attn",) + ("mamba",) * 4,
    ssm=SSMConfig(d_state=128, d_conv=4, expand=2, head_dim=64,
                  chunk_size=256),
    rope=False, attention_scale=0.015625, rms_eps=1e-5,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=8.0,
    source="hf:ibm-granite/granite-4.0-h-micro",
)


def smoke_config() -> ModelConfig:
    """One period of the published pattern at small widths."""
    return dataclasses.replace(
        CONFIG, name="granite-4.0-h-micro-smoke",
        num_layers=10, d_model=128, num_heads=4, num_kv_heads=2,
        head_dim=32, d_ff=256, vocab_size=512,
        ssm=SSMConfig(d_state=16, d_conv=4, expand=2, head_dim=32,
                      chunk_size=16))
