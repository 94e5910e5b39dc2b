"""AdamW with mixed-precision policy (from scratch; no optax here).

The paper's resource model charges 16 bytes/parameter for mixed-precision
training state (§III-A1).  Here the policy is explicit and searchable by the
planner:

* master weights: fp32 (``master_dtype``)
* Adam moments:   fp32 or bf16 (``optimizer_dtype`` — the planner flips this
  to bf16 when Eq 11 would otherwise be violated, e.g. grok/jamba on one pod)
* compute/grads:  bf16, cast up for the update.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class OptimizerConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1


def lr_schedule(cfg: OptimizerConfig, step: jax.Array) -> jax.Array:
    """Linear warmup + cosine decay."""
    step = step.astype(jnp.float32)
    warm = step / jnp.maximum(cfg.warmup_steps, 1)
    decay_steps = jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1)
    t = jnp.clip((step - cfg.warmup_steps) / decay_steps, 0.0, 1.0)
    cos = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * t))
    return cfg.lr * jnp.where(step < cfg.warmup_steps, warm, cos)


def _is_float(x) -> bool:
    return jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != jax.dtypes.float0


def global_norm(tree) -> jax.Array:
    leaves = [
        jnp.sum(jnp.square(g.astype(jnp.float32)))
        for g in jax.tree.leaves(tree)
        if _is_float(g)
    ]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def adamw_init(params, optimizer_dtype=jnp.float32) -> Dict[str, Any]:
    zeros = lambda p: jnp.zeros(p.shape, optimizer_dtype if _is_float(p) else p.dtype)
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


@jax.named_scope("optimizer")
def adamw_update(
    cfg: OptimizerConfig,
    params,
    grads,
    opt_state: Dict[str, Any],
) -> Tuple[Any, Dict[str, Any], Dict[str, jax.Array]]:
    """One AdamW step. Returns (new_params, new_opt_state, metrics)."""
    step = opt_state["step"] + 1
    lr = lr_schedule(cfg, step)

    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / (gnorm + 1e-9))

    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        if not _is_float(p) or not _is_float(g):
            # Non-trainable tables (e.g. the expert-migration assignment)
            # pass through untouched.
            return p, m, v
        g = g.astype(jnp.float32) * scale
        m32, v32 = m.astype(jnp.float32), v.astype(jnp.float32)
        m_new = b1 * m32 + (1 - b1) * g
        v_new = b2 * v32 + (1 - b2) * jnp.square(g)
        mh = m_new / bc1
        vh = v_new / bc2
        p32 = p.astype(jnp.float32)
        delta = mh / (jnp.sqrt(vh) + cfg.eps) + cfg.weight_decay * p32
        p_new = (p32 - lr * delta).astype(p.dtype)
        return p_new, m_new.astype(m.dtype), v_new.astype(v.dtype)

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(opt_state["m"])
    flat_v = jax.tree.leaves(opt_state["v"])
    new = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = jax.tree.unflatten(treedef, [n[0] for n in new])
    new_m = jax.tree.unflatten(treedef, [n[1] for n in new])
    new_v = jax.tree.unflatten(treedef, [n[2] for n in new])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_p, {"m": new_m, "v": new_v, "step": step}, metrics
