"""moonlight-16b-a3b [moonshotai/Moonlight-16B-A3B config.json;
deepseek_v3 form]: 27L d=2048, 16 heads of multi-head latent attention
(kv_lora_rank 512, q/k nope 128 + rope 64, v 128, no q LoRA), one
leading dense layer (d_ff 11264), then MoE layers of 64 routed experts
of 1408 (sigmoid scores, top-6 on scores + correction bias, gates
renormalised and scaled by 2.446) beside 2 shared experts (one FFN of
2816), vocab 163840, rope 50000, RMSNorm eps 1e-5, untied."""
from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="decoder_lm",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=1,             # one latent shared by every query head
    head_dim=128,
    attention="mla",
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    d_ff=1408,                  # routed expert width
    first_dense_layers=1,
    dense_d_ff=11264,
    vocab_size=163840,
    max_seq_len=8192,
    rope_theta=5e4,
    norm_eps=1e-5,
    ffn_activation="swiglu",
    moe=MoEConfig(num_experts=64, routing="sigmoid_bias", top_k=6,
                  normalize_gates=True, routed_scaling_factor=2.446,
                  shared_d_ff=2816, aux_loss_coef=0.0,
                  capacity_factor=1.25, group_size=2048),
)


def smoke() -> ModelConfig:
    return CONFIG.replace(
        num_layers=3, d_model=64, num_heads=4, head_dim=16, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, d_ff=32,
        dense_d_ff=96, vocab_size=311, max_seq_len=128, dtype="float32",
    ).replace_moe(num_experts=8, top_k=3, shared_d_ff=64, group_size=64)
