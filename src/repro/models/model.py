"""Language model: parameter trees, init, forward / loss / prefill / decode.

Parameters are described once by a metadata tree (:class:`ParamMeta` leaves
carrying shape + logical sharding axes + initializer), from which we derive

* materialized parameters        (``init_params``)
* ``jax.ShapeDtypeStruct`` trees (``abstract_params`` — dry-run inputs)
* ``PartitionSpec`` trees        (``param_specs`` — pjit in_shardings)

so model definition, initialization and distribution can never drift apart.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import transformer
from repro.models import ssm as ssm_lib
from repro.models.layers import rms_norm, softcap
from repro.sharding import MeshPlan

VOCAB_PAD_MULTIPLE = 256


# ---------------------------------------------------------------------------
# Parameter metadata
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParamMeta:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    init: str = "normal"  # normal | embed | zeros | ones | a_log | dt_bias | arange
    fan_in: int = 0
    dtype: Optional[str] = None  # None -> master dtype; "int32" for tables

    def stacked(self, reps: int) -> "ParamMeta":
        return ParamMeta(
            (reps,) + self.shape,
            ("layers",) + self.logical,
            self.init,
            self.fan_in,
            self.dtype,
        )


def _attn_tree(a: ArchConfig) -> Dict[str, ParamMeta]:
    d, hq, hkv = a.d_model, a.q_dim, a.kv_dim
    return {
        "wq": ParamMeta((d, hq), ("embed", "model_out"), fan_in=d),
        "wk": ParamMeta((d, hkv), ("embed", "model_out"), fan_in=d),
        "wv": ParamMeta((d, hkv), ("embed", "model_out"), fan_in=d),
        "wo": ParamMeta((hq, d), ("model_out", "embed"), fan_in=hq),
    }


def _mla_tree(a: ArchConfig) -> Dict[str, ParamMeta]:
    m, d, H = a.mla, a.d_model, a.num_heads
    r = m.kv_lora_rank
    return {
        "wq": ParamMeta((d, H * m.qk_head_dim), ("embed", "model_out"),
                        fan_in=d),
        "w_kv_a": ParamMeta((d, r + m.qk_rope_head_dim), ("embed", None),
                            fan_in=d),
        "kv_norm": ParamMeta((r,), (None,), init="zeros"),
        "w_kv_b": ParamMeta((r, H * (m.qk_nope_head_dim + m.v_head_dim)),
                            (None, "model_out"), fan_in=r),
        "wo": ParamMeta((H * m.v_head_dim, d), ("model_out", "embed"),
                        fan_in=H * m.v_head_dim),
    }


def _dense_ffn_tree(a: ArchConfig) -> Dict[str, ParamMeta]:
    d, f = a.d_model, a.d_ff
    t = {
        "w_up": ParamMeta((d, f), ("embed", "model_out"), fan_in=d),
        "w_down": ParamMeta((f, d), ("model_out", "embed"), fan_in=f),
    }
    if a.ffn_activation == "swiglu":
        t["w_gate"] = ParamMeta((d, f), ("embed", "model_out"), fan_in=d)
    return t


def _moe_tree(a: ArchConfig) -> Dict[str, ParamMeta]:
    m = a.moe
    d, f, E, Eh = a.d_model, m.d_ff, m.num_experts, m.experts_held
    t = {
        "w_router": ParamMeta((d, E), (None, None), fan_in=d),
        "w_up": ParamMeta((Eh, d, f), ("expert", None, "expert_ffn"), fan_in=d),
        "w_down": ParamMeta((Eh, f, d), ("expert", "expert_ffn", None), fan_in=f),
        # logical expert -> physical slot routing table (expert migration)
        "assignment": ParamMeta((E,), (None,), init="arange", dtype="int32"),
    }
    if m.bias_update_speed > 0:
        # Selection bias in units of the update speed: the count of its
        # +-1 moves (see ``moe.update_router_bias``); no gradient.
        t["router_bias"] = ParamMeta((E,), (None,), init="fill", fan_in=0,
                                     dtype="int32")
    if m.max_replicas > 0:
        # Hot-expert replica channels: logical id per channel, sentinel E =
        # free.  Replicated rows compute source-locally on each EP rank.
        t["replicas"] = ParamMeta(
            (m.max_replicas,), (None,), init="fill", fan_in=E, dtype="int32"
        )
    if a.ffn_activation == "swiglu":
        t["w_gate"] = ParamMeta((Eh, d, f), ("expert", None, "expert_ffn"), fan_in=d)
    if m.num_shared_experts > 0:
        fs = f * m.num_shared_experts
        t["w_shared_up"] = ParamMeta((d, fs), ("embed", "model_out"), fan_in=d)
        t["w_shared_down"] = ParamMeta((fs, d), ("model_out", "embed"), fan_in=fs)
        if a.ffn_activation == "swiglu":
            t["w_shared_gate"] = ParamMeta((d, fs), ("embed", "model_out"), fan_in=d)
    return t


def _mamba_tree(a: ArchConfig) -> Dict[str, ParamMeta]:
    s = a.ssm
    d = a.d_model
    d_in = s.expand * d
    gn = s.n_groups * s.state_size
    nh = s.num_heads(d)
    w = s.conv_width
    return {
        "w_z": ParamMeta((d, d_in), ("embed", "ssm_inner"), fan_in=d),
        "w_x": ParamMeta((d, d_in), ("embed", "ssm_inner"), fan_in=d),
        "w_B": ParamMeta((d, gn), ("embed", None), fan_in=d),
        "w_C": ParamMeta((d, gn), ("embed", None), fan_in=d),
        "w_dt": ParamMeta((d, nh), ("embed", None), fan_in=d),
        "conv_x_w": ParamMeta((d_in, w), ("ssm_inner", None), fan_in=w),
        "conv_x_b": ParamMeta((d_in,), ("ssm_inner",), init="zeros"),
        "conv_B_w": ParamMeta((gn, w), (None, None), fan_in=w),
        "conv_B_b": ParamMeta((gn,), (None,), init="zeros"),
        "conv_C_w": ParamMeta((gn, w), (None, None), fan_in=w),
        "conv_C_b": ParamMeta((gn,), (None,), init="zeros"),
        "A_log": ParamMeta((nh,), (None,), init="a_log"),
        "D": ParamMeta((nh,), (None,), init="ones"),
        "dt_bias": ParamMeta((nh,), (None,), init="dt_bias"),
        "norm_scale": ParamMeta((d_in,), ("ssm_inner",), init="zeros"),
        "out_proj": ParamMeta((d_in, d), ("ssm_inner", "embed"), fan_in=d_in),
    }


def _block_tree(a: ArchConfig, block) -> Dict[str, Any]:
    mixer, ffn = block
    t: Dict[str, Any] = {
        "norm_mixer": ParamMeta((a.d_model,), (None,), init="zeros")
    }
    if mixer.startswith("attn"):
        t["mixer"] = _attn_tree(a)
    elif mixer == "mla":
        t["mixer"] = _mla_tree(a)
    elif mixer == "mamba":
        t["mixer"] = _mamba_tree(a)
    if ffn != "none":
        t["norm_ffn"] = ParamMeta((a.d_model,), (None,), init="zeros")
        t["ffn"] = _dense_ffn_tree(a) if ffn == "dense" else _moe_tree(a)
    return t


def _stacked_blocks(a: ArchConfig, pattern, reps: int):
    return tuple(
        jax.tree.map(
            lambda m: m.stacked(reps),
            _block_tree(a, blk),
            is_leaf=lambda x: isinstance(x, ParamMeta),
        )
        for blk in pattern
    )


def param_tree(a: ArchConfig) -> Dict[str, Any]:
    vp = a.padded_vocab(VOCAB_PAD_MULTIPLE)
    tree: Dict[str, Any] = {
        "embed": ParamMeta((vp, a.d_model), ("vocab", "model_out"), init="embed"),
        "blocks": _stacked_blocks(a, a.block_pattern, a.reps),
        "final_norm": ParamMeta((a.d_model,), (None,), init="zeros"),
    }
    if a.first_k_dense:
        # The leading dense layers, stacked like one pattern position.
        tree["prefix"] = _stacked_blocks(a, (a.prefix_block,),
                                         a.first_k_dense)
    if not a.tie_embeddings:
        tree["lm_head"] = ParamMeta(
            (a.d_model, vp), ("model_out", "vocab"), fan_in=a.d_model
        )
    return tree


def _is_meta(x) -> bool:
    return isinstance(x, ParamMeta)


def _init_leaf(meta: ParamMeta, key, dtype):
    if meta.dtype == "int32":
        if meta.init == "fill":  # constant sentinel (fan_in holds the value)
            return jnp.full(meta.shape, meta.fan_in, dtype=jnp.int32)
        assert meta.init == "arange"
        return jnp.broadcast_to(
            jnp.arange(meta.shape[-1], dtype=jnp.int32), meta.shape
        )
    if meta.init == "zeros":
        return jnp.zeros(meta.shape, dtype)
    if meta.init == "ones":
        return jnp.ones(meta.shape, dtype)
    if meta.init == "a_log":
        u = jax.random.uniform(key, meta.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(dtype)
    if meta.init == "dt_bias":
        dt = jnp.exp(
            jax.random.uniform(
                key, meta.shape, jnp.float32, math.log(1e-3), math.log(0.1)
            )
        )
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if meta.init == "embed":
        return (jax.random.normal(key, meta.shape, jnp.float32) * 0.02).astype(dtype)
    scale = 1.0 / math.sqrt(max(meta.fan_in, 1))
    return (jax.random.normal(key, meta.shape, jnp.float32) * scale).astype(dtype)


def init_params(a: ArchConfig, key, dtype=jnp.float32):
    tree = param_tree(a)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_meta)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [_init_leaf(m, k, dtype) for m, k in zip(leaves, keys)]
    )


def abstract_params(a: ArchConfig, dtype=jnp.float32):
    return jax.tree.map(
        lambda m: jax.ShapeDtypeStruct(
            m.shape, jnp.int32 if m.dtype == "int32" else dtype
        ),
        param_tree(a),
        is_leaf=_is_meta,
    )


def param_specs(a: ArchConfig, plan: MeshPlan):
    return jax.tree.map(
        lambda m: plan.spec(*m.logical), param_tree(a), is_leaf=_is_meta
    )


# ---------------------------------------------------------------------------
# Shape-safe activation specs
# ---------------------------------------------------------------------------


def safe_spec(plan: MeshPlan, shape, logical) -> P:
    """plan.spec(...) but dropping any axis group that does not divide the
    corresponding dim (e.g. batch=1 long_500k decode)."""
    dims = []
    for size, name in zip(shape, logical):
        if name is None:
            dims.append(None)
            continue
        rule = plan.rules.get(name)
        if not rule:
            dims.append(None)
            continue
        div = int(np.prod([plan.mesh.shape[ax] for ax in rule]))
        if size % div != 0:
            dims.append(None)
        else:
            dims.append(rule[0] if len(rule) == 1 else tuple(rule))
    return P(*dims)


def refuse(a: ArchConfig, what: str, *, mla: bool) -> None:
    """Raise for a model ``what`` cannot run yet: with ``mla``, an MLA
    mixer (serving has no latent cache); leading dense layers always.
    Serving refuses where a cache is made (``init_cache``,
    ``init_paged_cache``, ``prefill``): the decode paths need one."""
    if mla and any(m == "mla" for m, _ in a.block_pattern):
        raise NotImplementedError(
            f"{a.name}: {what} has no latent (MLA) cache yet")
    if a.first_k_dense:
        raise NotImplementedError(
            f"{a.name}: {what} does not run leading dense layers yet")


# ---------------------------------------------------------------------------
# Language model
# ---------------------------------------------------------------------------


class LanguageModel:
    """Bundles an ArchConfig + MeshPlan.  Each layer picks its kernel from
    what it observes: attention from backend, shape and mesh
    (``layers.attention_path``), the expert FFN from the dispatch mode."""

    def __init__(self, arch: ArchConfig, plan: MeshPlan):
        self.arch = arch
        self.plan = plan
        self.vp = arch.padded_vocab(VOCAB_PAD_MULTIPLE)

    # -- embedding / head ---------------------------------------------------

    @jax.named_scope("embed")
    def _embed(self, params, batch) -> jax.Array:
        a = self.arch
        if a.frontend is not None and "embeds" in batch:
            # Match the parameter compute dtype (params are pre-cast by the
            # train step; tests may run fp32 end-to-end).
            x = batch["embeds"].astype(params["final_norm"].dtype)
        elif self.plan.pp_axis is not None:
            # Pipeline mode: gather the (bf16) table to replicated before the
            # lookup — a gather with replicated operand partitions trivially,
            # sidestepping an XLA SPMD involuntary-remat crash (see
            # sharding.default_rules).  Transient cost: one table-sized
            # all-gather per step.
            table = lax.with_sharding_constraint(
                params["embed"].astype(jnp.bfloat16),
                NamedSharding(self.plan.mesh, P(None, None)),
            )
            x = jnp.take(table, batch["tokens"], axis=0)
        else:
            x = jnp.take(params["embed"], batch["tokens"], axis=0)
        if a.scale_embeddings:
            x = x * jnp.asarray(math.sqrt(a.d_model), x.dtype)
        spec = safe_spec(self.plan, x.shape, ("batch", "seq", None))
        return lax.with_sharding_constraint(
            x, NamedSharding(self.plan.mesh, spec)
        )

    def _logits(self, w, x) -> jax.Array:
        """Shared head-logit pipeline (einsum, fp32, softcap, vocab-pad
        mask) — used by both the outside-the-pipeline head and the
        in-pipeline per-microbatch loss head, which must stay identical."""
        a = self.arch
        logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
        logits = logits.astype(jnp.float32)
        logits = softcap(logits, a.final_logit_softcap)
        # Mask the vocab padding region.
        pad_mask = jnp.arange(self.vp) < a.vocab_size
        return jnp.where(pad_mask, logits, -1e30)

    def _head(self, params, x) -> jax.Array:
        a = self.arch
        w = params["embed"].T if a.tie_embeddings else params["lm_head"]
        return self._logits(w, x)

    # -- forward ------------------------------------------------------------

    def forward(self, params, batch, *, token_sharded: bool = True):
        x, aux, loads = self._stack_out(params, batch, token_sharded)
        x = rms_norm(x, params["final_norm"], self.arch.norm_eps)
        logits = self._head(params, x)
        return logits, aux, loads

    def _loss_chunks(self, b: int, s: int) -> int:
        """Chunk the CE loss so per-device fp32 logits stay <= ~128 MB.

        A (tokens_per_device, padded_vocab) fp32 logits tensor is the
        dominant unsharded temp in LM training (gemma2: 4 GB+ per copy at
        train_4k); chunking the sequence and rematerializing the head keeps
        the live set bounded with negligible FLOP overhead.
        """
        plan = self.plan
        div = 1
        for ax_group in (plan.dp_axes, plan.sp_axes):
            d = int(np.prod([plan.mesh.shape[a] for a in ax_group]))
            div *= d
        tok_dev = max(b * s // max(div, 1), 1)
        target_tokens = max(int(128e6 // (self.vp * 4)), 1)
        need = max(1, -(-tok_dev // target_tokens))
        # round up to a divisor of s, capped
        for nc in range(need, min(s, 256) + 1):
            if s % nc == 0:
                return nc
        return 1

    def _stack_out(self, params, batch, token_sharded=True):
        """Embed + layer stack (no final norm / head)."""
        a = self.arch
        if self.plan.pp_axis is not None:
            from repro.core import pipeline

            refuse(a, "the pipeline executor", mla=False)
            x, embed_fn, embed_params = self._pipeline_inputs(params, batch)
            b, s = x.shape[:2]
            positions = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None], (b, s)
            )
            return pipeline.pipelined_stack_forward(
                params["blocks"], x, a, self.plan,
                positions=positions,
                embed_fn=embed_fn, embed_params=embed_params,
            )
        x = self._embed(params, batch)
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        if a.first_k_dense:
            x, _, _ = transformer.stack_forward(
                params["prefix"], x, a, self.plan, positions=positions,
                token_sharded=token_sharded,
                pattern=(a.prefix_block,),
            )
        return transformer.stack_forward(
            params["blocks"], x, a, self.plan,
            positions=positions,
            token_sharded=token_sharded,
        )

    def _pipeline_inputs(self, params, batch):
        """(x, embed_fn, embed_params) for the in-pipeline stage-0 embedding
        (paper-style placement; keeps the scatter-add backward pod-local)."""
        a = self.arch
        if a.frontend is not None and "embeds" in batch:
            # Precomputed frontend embeddings: no table, no embed grads —
            # safe to embed outside the pipeline.
            return self._embed(params, batch), None, None
        scale = math.sqrt(a.d_model) if a.scale_embeddings else None
        embed_grad = self.plan.embed_grad

        @jax.named_scope("embed")
        def embed_fn(table, toks):
            if not embed_grad:
                # Dry-run-only XLA-bug workaround; see MeshPlan.embed_grad.
                table = lax.stop_gradient(table)
            e = jnp.take(table, toks, axis=0)
            if scale is not None:
                e = e * jnp.asarray(scale, e.dtype)
            return e

        return batch["tokens"], embed_fn, params["embed"]

    def _make_head_fn(self):
        """Per-microbatch loss head for the schedule-executing pipeline:
        (head_params, embed_params, y (b_mu, s, d), labels) -> summed CE.

        Runs INSIDE the last pipeline stage so B(mb) can start as soon as
        F(mb) finishes there — the property that makes 1F1B a schedule
        rather than an accounting fiction."""
        a = self.arch
        tied = a.tie_embeddings

        @jax.named_scope("loss_head")
        def head_fn(head_params, embed_params, y, labels):
            h = rms_norm(y, head_params["final_norm"], a.norm_eps)
            w = embed_params.T if tied else head_params["lm_head"]
            logits = self._logits(w, h)
            lse = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
            return jnp.sum(lse - ll)

        return head_fn

    def loss_and_grads(
        self,
        params,
        batch,
        *,
        schedule: Optional[str] = None,
        vstages: Optional[int] = None,
    ):
        """Pipelined loss AND gradients under a schedule IR
        (``plan.schedule``/``plan.vstages`` unless overridden) — the
        training path for pipelined plans, replacing
        ``jax.grad``-through-the-forward so the executed op order is the
        schedule's, not reverse-mode AD's.  An overriding flat ``schedule``
        runs at V=1; pass ``vstages`` with an interleaved override to pick
        the chunk depth.

        Returns (loss, grads, metrics) with ``grads`` matching the ``params``
        tree; ``metrics["pipeline_occupancy"]`` carries the executed (PP,
        num_ticks) in-flight residual counts (and, for split-backward
        schedules, ``metrics["pipeline_wstash_occupancy"]`` the executed
        deferred-weight-grad residency).
        """
        from repro.core import pipeline

        a = self.arch
        assert self.plan.pp_axis is not None, "loss_and_grads needs a PP plan"
        refuse(a, "the pipeline executor", mla=False)
        x, embed_fn, embed_params = self._pipeline_inputs(params, batch)
        if embed_params is None and a.tie_embeddings:
            # Frontend inputs skip the in-pipeline lookup, but a tied head
            # still reads (and backprops into) the table at the last stage.
            embed_params = params["embed"]
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None], (b, s)
        )
        head_params = {"final_norm": params["final_norm"]}
        if not a.tie_embeddings:
            head_params["lm_head"] = params["lm_head"]
        loss, g, metrics, occupancy = pipeline.pipelined_step(
            params["blocks"],
            x,
            batch["labels"],
            a,
            self.plan,
            positions=positions,
            head_fn=self._make_head_fn(),
            head_params=head_params,
            schedule=schedule,
            vstages=vstages,
            embed_fn=embed_fn,
            embed_params=embed_params,
        )
        grads = {"blocks": g["blocks"], "final_norm": g["head"]["final_norm"]}
        if not a.tie_embeddings:
            grads["lm_head"] = g["head"]["lm_head"]
        if embed_params is not None:
            grads["embed"] = g["embed"]
        else:
            grads["embed"] = jnp.zeros_like(params["embed"])
        metrics = dict(metrics)
        metrics["pipeline_occupancy"] = occupancy
        return loss, grads, metrics

    def loss(self, params, batch):
        """Causal LM loss (sequence-chunked CE). Returns (loss, metrics)."""
        x, aux, loads = self._stack_out(params, batch)
        b, s, _ = x.shape
        ce = self._ce_sum(params, x, batch["labels"]) / (b * s)
        total = ce + aux["moe_aux_loss"] + aux["moe_z_loss"]
        metrics = {
            "loss": total,
            "ce": ce,
            "moe_aux_loss": aux["moe_aux_loss"],
            "moe_z_loss": aux["moe_z_loss"],
            "expert_load": loads,
        }
        return total, metrics

    @jax.named_scope("loss_head")
    def _ce_sum(self, params, x, labels):
        """Summed CE of the stack's output: final norm, head, logsumexp,
        chunked over the sequence with each chunk rematerialized."""
        a = self.arch
        b, s, d = x.shape
        nc = self._loss_chunks(b, s)

        def ce_of(x_part, labels_part):
            h = rms_norm(x_part, params["final_norm"], a.norm_eps)
            logits = self._head(params, h)
            lse = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, labels_part[..., None], axis=-1)[..., 0]
            return jnp.sum(lse - ll)

        if nc <= 1:
            return ce_of(x, labels)
        sc = s // nc
        xc = x.reshape(b, nc, sc, d).transpose(1, 0, 2, 3)
        lc = labels.reshape(b, nc, sc).transpose(1, 0, 2)
        spec = safe_spec(self.plan, (nc, b, sc, d), (None, "batch", "seq", None))
        xc = lax.with_sharding_constraint(
            xc, NamedSharding(self.plan.mesh, spec)
        )

        @jax.checkpoint
        def chunk(carry, xs):
            x_part, l_part = xs
            return carry + ce_of(x_part, l_part), None

        total_ce, _ = lax.scan(chunk, jnp.float32(0.0), (xc, lc))
        return total_ce

    # -- serving ------------------------------------------------------------

    def init_cache(self, batch: int, cache_len: int, dtype=jnp.bfloat16):
        a = self.arch
        refuse(a, "serving", mla=True)
        reps = a.num_layers // len(a.block_pattern)
        caches = []
        for mixer, _ in a.block_pattern:
            if mixer.startswith("attn"):
                shape = (reps, batch, cache_len, a.num_kv_heads, a.head_dim)
                caches.append(
                    {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
                )
            else:
                c = ssm_lib.init_ssm_cache(a, batch, dtype)
                caches.append(
                    jax.tree.map(
                        lambda t: jnp.broadcast_to(t[None], (reps,) + t.shape), c
                    )
                )
        return tuple(caches)

    def abstract_cache(self, batch: int, cache_len: int, dtype=jnp.bfloat16):
        return jax.eval_shape(
            lambda: self.init_cache(batch, cache_len, dtype)
        )

    def cache_specs(self, batch: int, cache_len: int, dtype=jnp.bfloat16):
        a = self.arch
        reps = a.num_layers // len(a.block_pattern)
        specs = []
        for mixer, _ in a.block_pattern:
            if mixer.startswith("attn"):
                shape = (reps, batch, cache_len, a.num_kv_heads, a.head_dim)
                sp = safe_spec(
                    self.plan, shape, ("layers", "batch", "kv_seq", None, None)
                )
                specs.append({"k": sp, "v": sp})
            else:
                c = ssm_lib.init_ssm_cache(a, 1, dtype)

                def spec_of(t):
                    shape = (reps, batch) + t.shape[1:]
                    logical = ("layers", "batch") + (None,) * (len(t.shape) - 1)
                    return safe_spec(self.plan, shape, logical)

                specs.append(jax.tree.map(spec_of, c))
        return tuple(specs)

    def decode_step(self, params, cache, batch, index):
        """One token: batch {"tokens": (b,1)} or {"embeds": (b,1,d)};
        index: int32 scalar — current cache fill. Returns (logits (b, vp),
        new_cache)."""
        a = self.arch
        x = self._embed(params, batch)
        b = x.shape[0]
        positions = jnp.full((b, 1), index, jnp.int32)

        def body(carry, inputs):
            h = carry
            rep_params, rep_cache = inputs
            new_caches = []
            for pos, blk in enumerate(a.block_pattern):
                h, _, nc = transformer.apply_block(
                    blk,
                    rep_params[pos],
                    h,
                    a,
                    self.plan,
                    positions=positions,
                    cache=rep_cache[pos],
                    cache_index=index,
                    token_sharded=False,
                )
                new_caches.append(nc)
            return h, tuple(new_caches)

        x, new_cache = lax.scan(body, x, (params["blocks"], cache))
        x = rms_norm(x, params["final_norm"], a.norm_eps)
        logits = self._head(params, x)[:, 0]
        return logits, new_cache

    # -- paged serving (continuous batching) --------------------------------

    def init_paged_cache(self, layout, dtype=jnp.bfloat16):
        """Per-pattern-position page pools for the serving engine.

        ``layout``: a :class:`repro.serving.kv_cache.PagedLayout`.  Returns
        a tuple (one entry per pattern position) of {"k","v"} pools shaped
        (reps, num_blocks, block_size, kv_heads, head_dim).  SSM mixers
        have no paged form yet (their per-sequence state is O(1) in context
        — paging buys nothing); the engine rejects those archs.
        """
        from repro.serving import kv_cache as kv_lib

        a = self.arch
        refuse(a, "serving", mla=True)
        reps = a.num_layers // len(a.block_pattern)
        pools = []
        for mixer, _ in a.block_pattern:
            if not mixer.startswith("attn"):
                raise NotImplementedError(
                    f"paged serving supports attention mixers only, got "
                    f"{mixer!r} in {a.name}"
                )
            pools.append(
                kv_lib.init_pages(
                    layout, reps, a.num_kv_heads, a.head_dim, dtype
                )
            )
        return tuple(pools)

    def prefill_paged(self, params, batch, cache, block_table, lengths):
        """Prompt forward that writes K/V into the paged cache.

        batch: {"tokens": (b, s_pad)} — prompts right-padded to a common
        bucket length; lengths: (b,) true prompt lengths; block_table:
        (b, nb) page ids (sentinel rows for unused slots).  Causality keeps
        real rows exact under right-padding (pads only ever attend
        backwards), and the page scatter drops pad rows via ``count=``.
        Returns (last-valid-position logits (b, vp), new_cache).
        """
        from repro.serving import kv_cache as kv_lib

        a = self.arch
        x = self._embed(params, batch)
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None], (b, s)
        )

        def body(carry, xs):
            rep_params, rep_pages = xs
            h = carry
            new_pages = []
            for pos, blk in enumerate(a.block_pattern):
                h, _, nc = transformer.apply_block(
                    blk,
                    rep_params[pos],
                    h,
                    a,
                    self.plan,
                    positions=positions,
                    return_cache=True,
                    token_sharded=True,
                )
                new_pages.append(
                    {
                        "k": kv_lib.append_tokens(
                            rep_pages[pos]["k"], block_table,
                            jnp.zeros((b,), jnp.int32), nc["k"],
                            count=lengths,
                        ),
                        "v": kv_lib.append_tokens(
                            rep_pages[pos]["v"], block_table,
                            jnp.zeros((b,), jnp.int32), nc["v"],
                            count=lengths,
                        ),
                    }
                )
            return h, tuple(new_pages)

        x, new_cache = lax.scan(body, x, (params["blocks"], cache))
        x = rms_norm(x, params["final_norm"], a.norm_eps)
        # Last VALID position per sequence (prompts are right-padded).
        idx = jnp.clip(lengths - 1, 0, s - 1)
        xt = jnp.take_along_axis(x, idx[:, None, None], axis=1)  # (b, 1, d)
        logits = self._head(params, xt)[:, 0]
        return logits, new_cache

    def decode_step_paged(
        self, params, cache, block_table, lengths, batch, *,
        return_loads: bool = False,
    ):
        """One continuous-batching decode step over all sequence slots.

        batch: {"tokens": (b, 1)}; lengths: (b,) per-sequence cache fills
        (positions of the new tokens); block_table: (b, nb).  Inactive
        slots (sentinel table rows) write nothing and produce garbage
        logits the engine ignores.  Returns (logits (b, vp), new_cache),
        plus per-layer logical expert counts (reps, n_moe_pos, E) when
        ``return_loads`` (the serving rebalancer's load feed).
        """
        a = self.arch
        x = self._embed(params, batch)
        positions = lengths[:, None]  # per-sequence RoPE positions

        def body(carry, xs):
            rep_params, rep_pages = xs
            h = carry
            new_pages = []
            loads = []
            for pos, blk in enumerate(a.block_pattern):
                pc = {
                    "k_pages": rep_pages[pos]["k"],
                    "v_pages": rep_pages[pos]["v"],
                    "block_table": block_table,
                    "lengths": lengths,
                }
                h, mets, nc = transformer.apply_block(
                    blk,
                    rep_params[pos],
                    h,
                    a,
                    self.plan,
                    positions=positions,
                    cache=pc,
                    token_sharded=False,
                )
                if mets and return_loads:
                    loads.append(mets["expert_load"])
                new_pages.append(
                    {"k": nc["k_pages"], "v": nc["v_pages"]}
                )
            ys = tuple(new_pages)
            if return_loads:
                ys = (ys, jnp.stack(loads))  # (n_moe_pos, E)
            return h, ys

        x, ys = lax.scan(body, x, (params["blocks"], cache))
        if return_loads:
            new_cache, loads = ys
        x = rms_norm(x, params["final_norm"], a.norm_eps)
        logits = self._head(params, x)[:, 0]
        if return_loads:
            return logits, new_cache, loads
        return logits, ys

    def prefill(self, params, batch):
        """Forward over a prompt, emitting (last-position logits, cache)."""
        a = self.arch
        refuse(a, "serving", mla=True)
        x = self._embed(params, batch)
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

        def body(carry, rep_params):
            h = carry
            caches = []
            for pos, blk in enumerate(a.block_pattern):
                h, _, nc = transformer.apply_block(
                    blk,
                    rep_params[pos],
                    h,
                    a,
                    self.plan,
                    positions=positions,
                    return_cache=True,
                    token_sharded=True,
                )
                caches.append(nc)
            return h, tuple(caches)

        x, cache = lax.scan(body, x, params["blocks"])
        x = rms_norm(x, params["final_norm"], a.norm_eps)
        logits = self._head(params, x[:, -1:])[:, 0]
        return logits, cache
