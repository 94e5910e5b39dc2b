"""A configuration small enough for the CPU: the program's ``--reduced``
granite (2 layers, d_model 64, 4/2 heads of 16, 8 experts top-2 of 64,
vocabulary 512) with the benchmark cells' training settings."""

import copy

TINY = {
    "hidden_size": 64, "intermediate_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_local_experts": 8, "num_experts_per_tok": 2, "vocab_size": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
    "embedding_multiplier": 1.0, "residual_multiplier": 1.0,
    "attention_multiplier": 0.25, "logits_scaling": 1.0,
    "model_type": "granitemoe",
    "assumed": {"head_dim": 16, "vocab_pad_multiple": 256},
    "program": {"arch": "granite-moe-3b-a800m",
                "flags": ["--reduced", "--batch", "2", "--seq", "64",
                          "--steps", "1000"]},
    "training": {
        "batch": 2, "seq": 64, "master_dtype": "float32",
        "compute_dtype": "bfloat16", "router_aux_loss_coef": 0.01,
        "router_z_loss_coef": 0.001, "aux_loss_groups": 1,
        "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                      "weight_decay": 0.1, "clip_norm": 1.0,
                      "warmup_steps": 100, "total_steps": 1000,
                      "min_lr_ratio": 0.1},
    },
}


# Limits for the tiny size, set from seeds 11-16 read on the CPU: the
# program read at most (loss, grad, update) gaps of
# (2.7e-4, 0.075, 7.7e-3); half the batch read at least (1.9e-3, 0.15,
# 3.2e-2).  At 128 tokens a step one routing flip moves a router gradient
# by several percent, so these are wider than the cell's own limits.
TINY_LIMITS = {"loss_gap": 1.0e-3, "grad_gap": 0.15, "update_gap": 0.02}


def tiny():
    return copy.deepcopy(TINY)
