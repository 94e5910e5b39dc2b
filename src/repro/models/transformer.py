"""Block composition: dense / MoE / SSM / hybrid transformer stacks.

A model is a repeated ``block_pattern`` (period p) tiled ``reps`` times.
Parameters for each pattern *position* are stacked over reps so the whole
stack runs as a single ``lax.scan`` — keeping the lowered HLO O(period)
instead of O(num_layers), which is what makes 72-layer/314B-param dry-run
compiles tractable.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.sharding import MeshPlan


@jax.named_scope("block")
def apply_block(
    block: Tuple[str, str],
    params: Dict[str, Any],
    x: jax.Array,
    arch: ArchConfig,
    plan: MeshPlan,
    *,
    positions: Optional[jax.Array],
    impl: str = "xla",
    cache: Optional[Dict[str, jax.Array]] = None,
    cache_index=None,
    return_cache: bool = False,
    token_sharded: bool = True,
):
    """One (mixer, ffn) block with pre-norms and residuals."""
    mixer, ffn = block
    metrics: Dict[str, jax.Array] = {}
    new_cache = None

    h = L.rms_norm(x, params["norm_mixer"], arch.norm_eps)
    if mixer == "mla":
        out, new_cache = L.mla_proj(params["mixer"], h, arch, positions,
                                    plan=plan)
    elif mixer.startswith("attn"):
        window = arch.sliding_window if mixer == "attn_local" else None
        out, new_cache = L.attention_proj(
            params["mixer"],
            h,
            arch,
            positions,
            impl=impl,
            window=window,
            cache=cache,
            cache_index=cache_index,
            return_kv=return_cache and cache is None,
            plan=plan,
        )
    elif mixer == "mamba":
        out, new_cache = ssm_lib.mamba_block(
            params["mixer"],
            h,
            arch,
            cache=cache,
            return_cache=return_cache,
            impl=impl,
        )
    else:
        raise ValueError(mixer)
    x = x + out

    if ffn != "none":
        h = L.rms_norm(x, params["norm_ffn"], arch.norm_eps)
        if ffn == "dense":
            out = L.dense_ffn(params["ffn"], h, arch.ffn_activation)
        elif ffn == "moe":
            out, metrics = moe_lib.moe_ffn(
                params["ffn"],
                h,
                arch,
                plan,
                token_sharded=token_sharded,
                impl=impl,
            )
        else:
            raise ValueError(ffn)
        x = x + out
    return x, metrics, new_cache


def _remat(fn, mode: str):
    if mode == "none":
        return fn
    if mode == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)


def stack_forward(
    block_params: Tuple[Dict[str, Any], ...],  # per-position, leaves (reps, ...)
    x: jax.Array,
    arch: ArchConfig,
    plan: MeshPlan,
    *,
    positions: Optional[jax.Array],
    impl: str = "xla",
    token_sharded: bool = True,
    unroll: bool = False,
    pattern=None,
):
    """Run the layer stack via scan-over-reps of ``pattern`` (default the
    arch's ``block_pattern``; the leading dense layers pass their own).

    Returns (x, {"moe_aux_loss","moe_z_loss"} scalars, expert_load
    (reps, n_moe_positions, E) or None).
    """
    pattern = arch.block_pattern if pattern is None else pattern
    has_moe = any(f == "moe" for _, f in pattern)

    def body(carry, rep_params):
        h, aux, z = carry
        loads = []
        for pos, blk in enumerate(pattern):
            h, metrics, _ = apply_block(
                blk,
                rep_params[pos],
                h,
                arch,
                plan,
                positions=positions,
                impl=impl,
                token_sharded=token_sharded,
            )
            if metrics:
                aux = aux + metrics["moe_aux_loss"]
                z = z + metrics["moe_z_loss"]
                loads.append(metrics["expert_load"])
        load = jnp.stack(loads) if loads else jnp.zeros((0,), jnp.float32)
        return (h, aux, z), load

    body = _remat(body, plan.remat)
    zero = jnp.float32(0.0)
    with jax.named_scope("block"):  # the scan's carries and stacked loads
        (x, aux, z), loads = lax.scan(
            body, (x, zero, zero), block_params,
            unroll=True if unroll else 1,
        )
    return x, {"moe_aux_loss": aux, "moe_z_loss": z}, (loads if has_moe else None)
