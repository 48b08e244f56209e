"""AI21-Jamba2-3B: Mamba-1 layers with one attention layer in every 14.
[hf:ai21labs/AI21-Jamba2-3B config.json]

Layer i attends where i % 14 == 7 (layers 7 and 21 of 28): MQA, 20 query
heads of 128 over one KV head, no positional encoding.  The other 26 are
Mamba-1 mixers (d_inner 5120, state 16, conv 4, dt rank 160) whose dt, B
and C pass an RMSNorm before dt_proj.  Every layer ends in a SwiGLU MLP of
8192; the 65536-token vocabulary is tied.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="jamba2-3b",
    family="ssm",
    n_layers=28,
    d_model=2560,
    n_heads=20,
    n_kv_heads=1,
    head_dim=128,
    d_ff=8192,
    vocab_size=65_536,
    rope_theta=0.0,                 # no positional encoding (NoPE)
    attn_layer_period=14,
    attn_layer_offset=7,
    ssm_state=16,
    ssm_conv=4,
    ssm_expand=2,
    ssm_dt_rank=160,
    ssm_ffn=True,
    ssm_dt_norms=True,
    norm_eps=1e-6,
    tie_embeddings=True,
)

SMOKE = CONFIG.with_overrides(
    name="jamba2-3b-smoke",
    n_layers=8, d_model=128, n_heads=4, n_kv_heads=1, head_dim=32,
    d_ff=256, vocab_size=384, ssm_state=8, ssm_dt_rank=8,
    attn_layer_period=4, attn_layer_offset=1, dtype="float32",
)
