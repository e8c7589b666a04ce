"""Mellum2-12B-A2.5B [moe] — 28L d_model=2304, 32 query heads over 4 KV
heads of 128, no attention bias; every layer a sparse MLP of 64 SwiGLU
experts of width 896, top-8 renormalised, no shared expert; 3 sliding-
window layers (window 1024, RoPE θ 5e5) then 1 full layer (YaRN: θ 5e5,
factor 16 over 8,192, β_fast 32, β_slow 1, attention factor 1.2773);
untied 98,304-token unembedding.
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json]

Served here cut to its first 8 layers, two whole periods (S,S,S,F) × 2:
each layer whole on one chip, the 20 left out standing for further
pipeline stages.  12.15 B parameters at 28 layers, 3.79 B at 8.
"""
from repro.configs.base import ModelConfig, MoEConfig, RopeConfig

PERIOD = ("sliding", "sliding", "sliding", "full")
LAYERS = 8

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    num_layers=LAYERS,
    d_model=2304,
    num_heads=32,
    num_kv_heads=4,
    d_ff=7168,
    vocab_size=98304,
    head_dim=128,
    rope_theta=500_000.0,
    sliding_window=1024,
    layer_types=PERIOD * (LAYERS // len(PERIOD)),
    rope_by_kind=(
        ("full", RopeConfig(theta=500_000.0, factor=16.0,
                            original_max_len=8192, beta_fast=32.0,
                            beta_slow=1.0,
                            attention_factor=1.2772588722239782)),
        ("sliding", RopeConfig(theta=500_000.0)),
    ),
    rms_eps=1e-6,
    tie_embeddings=False,
    moe=MoEConfig(num_experts=64, top_k=8, expert_d_ff=896),
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct (first 8 of 28 layers)",
)
