"""A ``deepseek_v3`` configuration small enough for the CPU: the program's
``--reduced`` Moonlight share (3 layers, the first dense, d_model 64, 2
heads of latent attention with kv_lora_rank 16, nope 16, rope 8, v 16;
8 experts top-2 of 64 with 2 held here, 1 shared expert; vocabulary 512)
with the benchmark cells' training settings."""

import copy

TINY = {
    "model_type": "deepseek_v3",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 64,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 2, "num_key_value_heads": 2, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 2, "num_experts_per_tok": 2, "n_shared_experts": 1,
    "routed_scaling_factor": 2.446, "vocab_size": 512, "rope_theta": 50000,
    "rms_norm_eps": 1e-05,
    "share": {"chips": 4, "rank": 0, "n_routed_experts": 8, "vocab_size": 512},
    "assumed": {"seq_aux_alpha": 1e-4, "bias_update_speed": 1e-3,
                "vocab_pad_multiple": 256},
    "program": {"arch": "moonlight-16b-a3b-ep8",
                "flags": ["--reduced", "--dispatch", "ragged", "--batch",
                          "2", "--seq", "64", "--steps", "1000"]},
    "training": {
        "batch": 2, "seq": 64, "master_dtype": "float32",
        "compute_dtype": "bfloat16", "check_steps": 2,
        "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                      "weight_decay": 0.1, "clip_norm": 1.0,
                      "warmup_steps": 100, "total_steps": 1000,
                      "min_lr_ratio": 0.1},
    },
}


def tiny():
    return copy.deepcopy(TINY)


def uncut(rank: int = 0):
    """The tiny configuration with every expert held, or the share of
    ``rank`` of the 4."""
    cfg = tiny()
    if rank is None:
        cfg["n_routed_experts"] = 8
        cfg["share"] = {**cfg["share"], "chips": 1}
    else:
        cfg["share"]["rank"] = rank
    return cfg
