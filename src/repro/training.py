"""Training / serving step construction with full sharding metadata.

These are the functions the launcher jits with explicit
``in_shardings``/``out_shardings`` — both for real execution and for the
multi-pod dry-run (``.lower().compile()`` on ShapeDtypeStructs).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec
from repro.models import model as model_lib
from repro.models import moe as moe_lib
from repro.models.model import LanguageModel, safe_spec
from repro.optim.optimizer import OptimizerConfig, adamw_init, adamw_update
from repro.sharding import MeshPlan

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


# ---------------------------------------------------------------------------
# Train state
# ---------------------------------------------------------------------------


def init_state(lm: LanguageModel, key, opt_cfg: OptimizerConfig):
    params = model_lib.init_params(
        lm.arch, key, DTYPES[lm.plan.master_dtype]
    )
    opt = adamw_init(params, DTYPES[lm.plan.optimizer_dtype])
    return {"params": params, **opt}


def state_specs(lm: LanguageModel) -> Dict[str, Any]:
    pspecs = model_lib.param_specs(lm.arch, lm.plan)
    return {
        "params": pspecs,
        "m": pspecs,
        "v": pspecs,
        "step": P(),
    }


def state_shardings(lm: LanguageModel) -> Dict[str, Any]:
    """The plan's ``NamedSharding`` for every train-state leaf."""
    return jax.tree.map(
        lambda s: NamedSharding(lm.plan.mesh, s),
        state_specs(lm),
        is_leaf=lambda x: isinstance(x, P),
    )


def abstract_state(lm: LanguageModel) -> Dict[str, Any]:
    params = model_lib.abstract_params(lm.arch, DTYPES[lm.plan.master_dtype])
    odt = DTYPES[lm.plan.optimizer_dtype]
    moments = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, odt), params)
    return {
        "params": params,
        "m": moments,
        "v": moments,
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# Batches
# ---------------------------------------------------------------------------


def batch_struct(arch: ArchConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input (dry-run inputs)."""
    b = shape.global_batch
    if shape.kind == "train":
        s = shape.seq_len
        out = {
            "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
        }
        if arch.frontend is not None:
            # Backbone-only modality stub: precomputed frame/patch embeddings.
            out["embeds"] = jax.ShapeDtypeStruct(
                (b, s, arch.d_model), jnp.bfloat16
            )
        return out
    if shape.kind == "prefill":
        s = shape.seq_len
        out = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        if arch.frontend is not None:
            out["embeds"] = jax.ShapeDtypeStruct((b, s, arch.d_model), jnp.bfloat16)
        return out
    # decode: one new token against a cache of seq_len
    out = {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}
    if arch.frontend is not None:
        out["embeds"] = jax.ShapeDtypeStruct((b, 1, arch.d_model), jnp.bfloat16)
    return out


def batch_specs(lm: LanguageModel, shape: ShapeSpec) -> Dict[str, Any]:
    plan = lm.plan
    struct = batch_struct(lm.arch, shape)
    seq_logical = "seq" if shape.kind != "decode" else None
    out = {}
    for k, v in struct.items():
        logical = ("batch", seq_logical) + (
            (None,) if k == "embeds" else ()
        )
        out[k] = safe_spec(plan, v.shape, logical)
    return out


# ---------------------------------------------------------------------------
# Steps
# ---------------------------------------------------------------------------


def make_train_step(
    lm: LanguageModel,
    opt_cfg: OptimizerConfig,
    gnorm_skip_cap: Optional[float] = None,
):
    """Build the jitted train step.

    The step carries its own **anomaly sentinel**: a non-finite loss or
    grad norm (or, with ``gnorm_skip_cap``, a grad-norm spike above the
    cap) selects the OLD state instead of the update — a skip-step.  The
    guard must live *inside* the jit because the trainer donates the input
    state (``donate_argnums=(0,)``): by the time the host could inspect
    the loss, the pre-step buffers are gone.  ``metrics["skipped"]``
    reports the decision to the trainer's rollback counter.

    An optional scalar ``batch["fault_scale"]`` (runtime.faults
    ``train.nonfinite``) multiplies the loss AND grads after they are
    computed — on both the AD and the schedule-executor paths — so the
    chaos suite can force an anomalous step deterministically.
    """
    compute_dtype = DTYPES[lm.plan.compute_dtype]
    pipelined = lm.plan.pp_axis is not None and lm.plan.pp > 1
    moe = lm.arch.moe
    bias_update = moe is not None and moe.bias_update_speed > 0
    if bias_update and pipelined:
        raise NotImplementedError(
            f"{lm.arch.name}: the pipeline executor returns no expert loads "
            f"for the router-bias update")

    @jax.named_scope("optimizer")
    def cast(params):
        return jax.tree.map(
            lambda p: p.astype(compute_dtype)
            if jnp.issubdtype(p.dtype, jnp.floating)
            else p,
            params,
        )

    def train_step(state, batch):
        # The injected fault scale is step metadata, not model input — pop
        # it before either loss path (the pipeline executor would otherwise
        # try to microbatch a scalar).
        batch = dict(batch)
        fault_scale = batch.pop("fault_scale", None)
        if pipelined:
            # Schedule-driven executor: the pipeline computes its own
            # backward in the bound schedule's op order (1F1B executes with
            # its Eq-4 memory profile) instead of jax.grad re-deriving a
            # GPipe-ordered reverse pipeline from the forward scan.
            loss, grads, metrics = lm.loss_and_grads(cast(state["params"]), batch)
            metrics.pop("pipeline_occupancy", None)
            metrics.pop("pipeline_wstash_occupancy", None)
            metrics.pop("pipeline_comm_inflight", None)
        else:
            def loss_fn(params):
                return lm.loss(cast(params), batch)

            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True, allow_int=True
            )(state["params"])
        if fault_scale is not None:
            loss = loss * fault_scale
            grads = jax.tree.map(
                lambda g: g * fault_scale
                if hasattr(g, "dtype") and jnp.issubdtype(g.dtype, jnp.floating)
                else g,
                grads,
            )
            metrics = {**metrics, "loss": loss}
        new_params, new_opt, opt_metrics = adamw_update(
            opt_cfg, state["params"], grads, {k: state[k] for k in ("m", "v", "step")}
        )
        metrics = {**metrics, **opt_metrics}
        if metrics.get("expert_load") is None:
            metrics.pop("expert_load", None)
        if bias_update:
            new_params = {**new_params, "blocks": moe_lib.update_router_bias(
                new_params["blocks"], metrics["expert_load"], lm.arch)}
        new_state = {"params": new_params, **new_opt}
        # Anomaly sentinel: a poisoned update must not reach the state.
        with jax.named_scope("optimizer"), jax.named_scope("sentinel"):
            ok = jnp.isfinite(loss) & jnp.isfinite(opt_metrics["grad_norm"])
            if gnorm_skip_cap is not None:
                ok = ok & (opt_metrics["grad_norm"] < gnorm_skip_cap)
            new_state = jax.tree.map(
                lambda new, old: jnp.where(ok, new, old), new_state, state
            )
            metrics["skipped"] = jnp.logical_not(ok).astype(jnp.int32)
        return new_state, metrics

    return train_step


def make_prefill_step(lm: LanguageModel):
    compute_dtype = DTYPES[lm.plan.compute_dtype]

    def prefill_step(params, batch):
        cparams = jax.tree.map(
            lambda p: p.astype(compute_dtype)
            if jnp.issubdtype(p.dtype, jnp.floating)
            else p,
            params,
        )
        return lm.prefill(cparams, batch)

    return prefill_step


def make_decode_step(lm: LanguageModel):
    compute_dtype = DTYPES[lm.plan.compute_dtype]

    def decode_step(params, cache, batch, index):
        cparams = jax.tree.map(
            lambda p: p.astype(compute_dtype)
            if jnp.issubdtype(p.dtype, jnp.floating)
            else p,
            params,
        )
        return lm.decode_step(cparams, cache, batch, index)

    return decode_step
