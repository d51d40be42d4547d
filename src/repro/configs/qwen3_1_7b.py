"""qwen3-1.7b [dense]: qk_norm, GQA kv=8, head_dim 128, tied embeddings.

28L d_model=2048 16H (GQA kv=8) d_ff=6144 vocab=151936, bf16 weights
[hf:Qwen/Qwen3-1.7B config.json: tie_word_embeddings=true,
torch_dtype=bfloat16, rope_theta=1e6, rms_norm_eps=1e-6].
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-1.7b",
    family="dense",
    n_layers=28,
    d_model=2048,
    n_heads=16,
    n_kv_heads=8,
    d_ff=6144,
    vocab_size=151936,
    head_dim=128,
    qk_norm=True,
    rope_theta=1e6,
    norm_eps=1e-6,
    tie_embeddings=True,
    param_dtype="bfloat16",
)


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="qwen3-smoke", family="dense", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab_size=256, head_dim=32, qk_norm=True,
        remat=False)
