"""moonlight-16b-a3b — DeepSeek-V3 block (Moonshot AI's Moonlight).

[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]
27L d_model=2048, 16 heads of multi-head latent attention (kv_lora_rank
512, q_lora_rank null, qk nope 128 + rope 64, v 128), the first layer a
dense SwiGLU FFN of 11264, then 26 MoE layers: 64 routed experts of 1408,
top-6 by sigmoid score plus a selection bias (noaux_tc, one group),
renormalised and scaled by 2.446, and 2 shared experts; vocab 163840,
rope_theta 50000, rms_norm_eps 1e-5, untied head, context 8192.

The balance loss weight and the bias update speed are not in the config:
DeepSeek-V3's (arXiv:2412.19437 §2.1.2, §4.2) alpha = 1e-4 and gamma =
1e-3.  RoPE rotates the two halves of the 64 rope dims, where the
published code interleaves them: a fixed permutation of those weight
columns.

``CONFIG_EP8`` is one chip's share of an 8-way expert-parallel job (rank
0: experts 0-7 of 64 and an eighth of the vocabulary), the configuration
the benchmark trains on one chip.
"""

from repro.configs.base import ArchConfig, MLACfg, MoECfg

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,  # q/k head: 128 nope + 64 rope; v heads are 128
    d_ff=11264,  # the leading dense layer
    vocab_size=163840,
    block_pattern=(("mla", "moe"),),
    first_k_dense=1,
    mla=MLACfg(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
               v_head_dim=128),
    moe=MoECfg(
        num_experts=64, top_k=6, d_ff=1408, num_shared_experts=2,
        scoring="sigmoid", routed_scale=2.446,
        bias_update_speed=1e-3, seq_aux=True, aux_loss_coef=1e-4,
        z_loss_coef=0.0, dispatch="ragged",
    ),
    rope_theta=50_000.0,
    norm_eps=1e-5,
    tie_embeddings=False,
    source="hf:moonshotai/Moonlight-16B-A3B (deepseek_v3)",
)

CONFIG_EP8 = CONFIG.share(8)
