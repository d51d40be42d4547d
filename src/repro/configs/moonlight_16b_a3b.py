"""moonlight-16b-a3b [moe]: MLA without a query LoRA + 64 sigmoid-routed
experts top-6 (correction bias, scaled by 2.446) + 2 shared.

27L d_model=2048 16H, MLA kv_lora 512 (nope/rope/v 128/64/128, no
q_lora), layer 0 dense (d_ff 11264), 26 expert layers of 64 experts of
width 1408, vocab 163840, untied, bf16 [hf:moonshotai/Moonlight-16B-A3B
config.json, model_type deepseek_v3: scoring_func sigmoid, topk_method
noaux_tc, n_group = topk_group = 1, norm_topk_prob true, rope_theta 5e4,
rms_norm_eps 1e-5]. The whole model: every expert held, dropless routing.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,
    vocab_size=163840,
    use_mla=True,
    mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(n_experts=64, experts_per_token=6, d_ff_expert=1408,
                  n_shared_experts=2, n_dense_layers=1, d_ff_dense=11264,
                  router_group_size=4096, dropless=True,
                  routed_scaling_factor=2.446),
    rope_theta=50000.0,
    norm_eps=1e-5,
    param_dtype="bfloat16",
)


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-smoke", family="moe", n_layers=3, d_model=64,
        n_heads=4, n_kv_heads=4, d_ff=32, vocab_size=256, use_mla=True,
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=16, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16),
        moe=MoEConfig(n_experts=8, experts_per_token=2, d_ff_expert=32,
                      n_shared_experts=2, n_dense_layers=1, d_ff_dense=128,
                      router_group_size=64, dropless=True,
                      routed_scaling_factor=2.446, n_held=4),
        rope_theta=50000.0, remat=False)
