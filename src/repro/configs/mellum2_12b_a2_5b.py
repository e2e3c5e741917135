"""mellum2-12b-a2.5b — 28 layers in periods of three sliding-window
layers (window 1,024, plain RoPE) and one full layer (YaRN RoPE); every
layer sparse: 64 experts of width 896, top-8 softmax routing with the 8
weights renormalised, no shared expert
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct].

The published model also has a multi-token-prediction head
(``described_as``: "MTP head"); its config.json does not describe it, so
it is not built or served here, and speculative decoding stays the
n-gram drafter.
"""
from .base import ModelConfig, MoEConfig, YarnRope, register

CONFIG = register(ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    num_layers=28,
    d_model=2304,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=896,               # per-expert FFN width (moe_intermediate_size)
    vocab_size=98304,
    rope_theta=500_000.0,
    norm_eps=1e-6,
    window=1024,
    layer_pattern=("window", "window", "window", "full"),
    rope_scaling=YarnRope(factor=16.0, original_max_positions=8192,
                          beta_fast=32.0, beta_slow=1.0,
                          attention_factor=1.2772588722239782),
    moe=MoEConfig(num_experts=64, top_k=8, d_expert=896),
))
