"""Core transformer layers: norms, rotary embeddings, GQA attention, FFN.

All functions are pure; parameters are plain dict pytrees.  Causal
attention with no cache trains through a block-sparse flash kernel where
``attention_path`` finds a one-device TPU; elsewhere ``impl`` selects "xla"
(jnp reference, used by dry-runs — GSPMD inserts the K/V all-gathers for
sequence-sharded inputs) or "pallas" (the forward-only flash-attention hand
kernel from repro.kernels, validated in interpret mode).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# Rotary position embeddings (RoPE and Qwen2-VL's M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (b, s, h, d); positions: (b, s) int32."""
    freqs = rope_freqs(x.shape[-1], theta)  # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (b, s, d/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def mrope_sections(head_dim: int) -> tuple:
    """Qwen2-VL M-RoPE: split the d/2 rotary frequencies into
    (temporal, height, width) sections — published split is (16,24,24) for
    head_dim=128; generalized proportionally for other dims."""
    half = head_dim // 2
    t = half // 4
    h = (half - t) // 2
    w = half - t - h
    return (t, h, w)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (b, s, h, d); positions: (3, b, s) int32 — (t, h, w) position ids.

    For text-only streams all three id planes are equal, which makes M-RoPE
    coincide with 1-D RoPE (the Qwen2-VL property); the structure is kept so
    the VLM frontend can supply real 3-D ids.
    """
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)  # (half,)
    sections = mrope_sections(x.shape[-1])
    # For each frequency index, pick which position plane drives it.
    plane = jnp.concatenate(
        [jnp.full((n,), i, dtype=jnp.int32) for i, n in enumerate(sections)]
    )  # (half,)
    # positions: (3, b, s) -> per-frequency positions (b, s, half)
    pos = positions[plane].transpose(1, 2, 0).astype(jnp.float32)
    angles = pos * freqs  # (b, s, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def positional_embed(
    x: jax.Array, positions: jax.Array, rope_type: str, theta: float
) -> jax.Array:
    if rope_type == "rope":
        return apply_rope(x, positions, theta)
    if rope_type == "mrope":
        pos3 = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        return apply_mrope(x, pos3, theta)
    if rope_type == "none":
        return x
    raise ValueError(rope_type)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _causal_mask(s_q: int, s_k: int, *, q_offset, window: Optional[int]):
    """Boolean (s_q, s_k) mask; q_offset shifts query positions (decode)."""
    q_pos = jnp.arange(s_q)[:, None] + q_offset
    k_pos = jnp.arange(s_k)[None, :]
    m = k_pos <= q_pos
    if window is not None:
        m &= k_pos > q_pos - window
    return m


def _causal_mask_batched(
    b: int, s_q: int, s_k: int, *, q_offset, window: Optional[int], kv_len
):
    """(b, s_q, s_k) mask for per-sequence offsets/lengths — the continuous-
    batching decode case, where each batch slot sits at its own cache fill.
    ``q_offset``/``kv_len`` may be scalars or (b,) arrays."""
    q_off = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,))
    q_pos = jnp.arange(s_q, dtype=jnp.int32)[None, :, None] + q_off[:, None, None]
    k_pos = jnp.arange(s_k, dtype=jnp.int32)[None, None, :]
    m = k_pos <= q_pos
    if window is not None:
        m &= k_pos > q_pos - window
    if kv_len is not None:
        kl = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))
        m &= k_pos < kl[:, None, None]
    return m


def attention(
    q: jax.Array,  # (b, s_q, hq, d)
    k: jax.Array,  # (b, s_k, hkv, d)
    v: jax.Array,  # (b, s_k, hkv, d)
    *,
    q_offset=0,
    window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    kv_len: Optional[jax.Array] = None,
    q_chunks: int = 1,
    plan=None,
) -> jax.Array:
    """Reference GQA attention (fp32 softmax), scores scaled by
    ``1/sqrt(q's head dim)``; v's head dim may differ.  ``kv_len`` masks
    cache slots beyond the current length during decode.

    ``q_chunks > 1`` evaluates query blocks sequentially with
    rematerialization (softmax is row-wise, so q-chunking is exact) — the
    XLA-level analogue of flash attention's memory behaviour, bounding the
    (b, h, s_q, s_k) score temp to (b, h, s_q/q_chunks, s_k).
    """
    b, s_q, hq, d = q.shape

    if q_chunks > 1 and s_q % q_chunks == 0:
        qc = s_q // q_chunks
        qparts = q.reshape(b, q_chunks, qc, hq, d).transpose(1, 0, 2, 3, 4)
        offsets = q_offset + jnp.arange(q_chunks, dtype=jnp.int32) * qc

        chunk_ns = None
        if plan is not None:
            # The (s) -> (q_chunks, qc) reshape cannot keep the sequence
            # sharding on the outer chunk dim (q_chunks < shard count), so
            # GSPMD replicates the whole chunked attention; pin the INNER
            # qc dim to the sequence axes instead.
            from jax.sharding import NamedSharding

            from repro.models.model import safe_spec

            chunk_ns = NamedSharding(
                plan.mesh,
                safe_spec(
                    plan, (q_chunks, b, qc, hq, d),
                    (None, "batch", "seq", None, None),
                ),
            )
            qparts = lax.with_sharding_constraint(qparts, chunk_ns)

        @jax.checkpoint
        def chunk(carry, xs):
            q_part, off = xs
            out = attention(
                q_part, k, v,
                q_offset=off, window=window, logit_softcap=logit_softcap,
                kv_len=kv_len, q_chunks=1,
            )
            if chunk_ns is not None:
                out = lax.with_sharding_constraint(
                    out, NamedSharding(chunk_ns.mesh, P(*chunk_ns.spec[1:]))
                )
            return carry, out

        _, outs = lax.scan(chunk, 0.0, (qparts, offsets))
        outs = (
            lax.with_sharding_constraint(outs, chunk_ns)
            if chunk_ns is not None
            else outs
        )
        return outs.transpose(1, 0, 2, 3, 4).reshape(b, s_q, hq, -1)

    hkv = k.shape[2]
    groups = hq // hkv
    qh = q.reshape(b, s_q, hkv, groups, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qh, k, preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(d)
    scores = softcap(scores, logit_softcap)
    per_seq = jnp.ndim(q_offset) > 0 or (
        kv_len is not None and jnp.ndim(kv_len) > 0
    )
    if per_seq:
        # Continuous-batching decode: each slot at its own cache fill.
        mask_b = _causal_mask_batched(
            b, s_q, k.shape[1], q_offset=q_offset, window=window,
            kv_len=kv_len,
        )
        scores = jnp.where(mask_b[:, None, None], scores, -1e30)
    else:
        mask = _causal_mask(s_q, k.shape[1], q_offset=q_offset, window=window)
        if kv_len is not None:
            mask &= (jnp.arange(k.shape[1]) < kv_len)[None, :]
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(b, s_q, hq, v.shape[-1])


def attention_path(backend: str, s: int, *, window: Optional[int],
                   cached: bool, mesh_size: int) -> str:
    """Which attention ``attention_proj`` runs: "flash" where the causal
    block-sparse kernel (``kernels.flash_attention.ops.causal_attention``)
    can run — a TPU, no cache, no window, ``s`` a multiple of its 128-row
    tiles and one device (a mesh would need the kernel under
    ``shard_map``) — and "xla" elsewhere."""
    if (backend == "tpu" and not cached and window is None and s % 128 == 0
            and mesh_size == 1):
        return "flash"
    return "xla"


@jax.named_scope("attention")
def attention_proj(params, x, cfg, positions, *, impl="xla", window=None,
                   cache=None, cache_index=None, return_kv=False, plan=None):
    """Full attention sub-layer: QKV proj -> rope -> attention -> out proj,
    under the ``attention`` scope (the compiled step's op metadata).

    The attention itself is chosen by backend and shape (``attention_path``,
    counted as ``attention.path`` at trace time): the trainable flash kernel
    where it can run, else ``impl`` selects the opt-in hand kernel
    ("pallas", forward only) or the XLA reference ("xla").

    cache: optional dict {"k": (b, S, hkv, d), "v": ...} — decode path.
    return_kv=True additionally returns the freshly computed K/V (prefill).
    Returns (out, new_cache).
    """
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dk->bsk", x, params["wq"]).reshape(
        b, s, cfg.num_heads, cfg.head_dim
    )
    k = jnp.einsum("bsd,dk->bsk", x, params["wk"]).reshape(
        b, s, cfg.num_kv_heads, cfg.head_dim
    )
    v = jnp.einsum("bsd,dk->bsk", x, params["wv"]).reshape(
        b, s, cfg.num_kv_heads, cfg.head_dim
    )
    q = positional_embed(q, positions, cfg.rope_type, cfg.rope_theta)
    k = positional_embed(k, positions, cfg.rope_type, cfg.rope_theta)

    new_cache = None
    path = _counted_path(s, window, cache is not None, plan)
    if path == "flash":
        from repro.kernels.flash_attention import ops as fa_ops

        out = fa_ops.causal_attention(
            q, k, v, logit_softcap=cfg.attn_logit_softcap
        )
        if return_kv:
            new_cache = {"k": k, "v": v}
    elif cache is not None and "block_table" in cache:
        # Paged decode (continuous batching): append the new K/V rows to
        # their (page, slot) cells, materialize the prefix via the block
        # table, attend with per-sequence offsets/lengths.  Inactive batch
        # slots carry sentinel block-table rows: their writes drop and
        # their reads are masked by kv_len.
        from repro.serving import kv_cache as kv_lib

        bt, lens = cache["block_table"], cache["lengths"]
        pk = kv_lib.append_tokens(cache["k_pages"], bt, lens, k)
        pv = kv_lib.append_tokens(cache["v_pages"], bt, lens, v)
        new_cache = dict(cache, k_pages=pk, v_pages=pv)
        ck = kv_lib.gather_pages(pk, bt).astype(q.dtype)
        cv = kv_lib.gather_pages(pv, bt).astype(q.dtype)
        out = attention(
            q, ck, cv,
            q_offset=lens,
            window=window,
            logit_softcap=cfg.attn_logit_softcap,
            kv_len=lens + s,
        )
    elif cache is not None:
        # Decode: write the new K/V at cache_index, attend over the cache.
        ck = lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), cache_index, axis=1)
        cv = lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), cache_index, axis=1)
        new_cache = {"k": ck, "v": cv}
        out = attention(
            q, ck, cv,
            q_offset=cache_index,
            window=window,
            logit_softcap=cfg.attn_logit_softcap,
            kv_len=cache_index + s,
        )
    elif impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops

        out = fa_ops.flash_attention(
            q, k, v, causal=True, window=window,
            logit_softcap=cfg.attn_logit_softcap,
        )
    else:
        out = _xla_causal(q, k, v, window, cfg.attn_logit_softcap, plan)
        if return_kv:
            new_cache = {"k": k, "v": v}
    out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
    out = jnp.einsum("bsk,kd->bsd", out, params["wo"])
    return out, new_cache


def _counted_path(s: int, window, cached: bool, plan) -> str:
    """``attention_path`` for this backend and mesh, counted as
    ``attention.path`` at trace time."""
    path = attention_path(
        jax.default_backend(), s, window=window, cached=cached,
        mesh_size=1 if plan is None else plan.mesh.size,
    )
    obs.counter("attention.path", path=path)
    return path


def _xla_causal(q, k, v, window, logit_softcap, plan):
    """Causal attention with no cache through the XLA reference, its fp32
    score temp bounded by q-chunks of ~512 rows."""
    s = q.shape[1]
    q_chunks = max(s // 512, 1) if s >= 1024 else 1
    if plan is not None and q_chunks > 1:
        # PERF: gather K/V across the sequence shards ONCE per layer.
        # Left to GSPMD, the seq-sharded contraction turns into
        # psum-of-partial-outputs + softmax-stat reductions INSIDE the
        # q-chunk loop — q_chunks x remat-visits times the traffic
        # (measured 16x on granite train_4k; EXPERIMENTS.md §Perf).
        from jax.sharding import NamedSharding

        from repro.models.model import safe_spec

        ns = NamedSharding(
            plan.mesh, safe_spec(plan, k.shape, ("batch", None, None, None))
        )
        k = _checkpoint_name(
            lax.with_sharding_constraint(k, ns), "kv_gathered"
        )
        v = _checkpoint_name(
            lax.with_sharding_constraint(v, ns), "kv_gathered"
        )
        # Keep q (and the output, below) sequence-sharded — otherwise
        # GSPMD replicates the whole attention computation to match the
        # now-replicated K/V.
        q_ns = NamedSharding(
            plan.mesh, safe_spec(plan, q.shape, ("batch", "seq", None, None))
        )
        q = lax.with_sharding_constraint(q, q_ns)
    out = attention(
        q, k, v, window=window, logit_softcap=logit_softcap,
        q_chunks=q_chunks, plan=plan,
    )
    if plan is not None and q_chunks > 1:
        out = lax.with_sharding_constraint(out, q_ns)
    return out


@jax.named_scope("attention")
def mla_proj(params, x, cfg, positions, *, plan=None):
    """Multi-head latent attention sub-layer (DeepSeek-V3, q_lora_rank
    null), in its expanded training form, under the ``attention`` scope:

    q = x Wq split into a nope and a rope part per head; the latent
    ``x W_kv_a`` splits into c (``kv_lora_rank``) and one rope key that
    every head shares; c is RMS-normed and projected up to each head's
    nope key and value (``attention.mla_kv``).  Causal attention over keys
    [k_nope, k_rope] at scale ``1/sqrt(qk_head_dim)`` runs through the
    path ``attention_path`` picks, with v's narrower head.  RoPE rotates
    the two halves of the rope dims.  No cache: serving refuses MLA."""
    m = cfg.mla
    b, s, _ = x.shape
    H, dn, dr = cfg.num_heads, m.qk_nope_head_dim, m.qk_rope_head_dim
    q = jnp.einsum("bsd,dk->bsk", x, params["wq"]).reshape(b, s, H, dn + dr)
    with jax.named_scope("attention.mla_kv"):
        kv_a = jnp.einsum("bsd,dk->bsk", x, params["w_kv_a"])
        c = rms_norm(kv_a[..., :m.kv_lora_rank], params["kv_norm"],
                     cfg.norm_eps)
        kv = jnp.einsum("bsc,ck->bsk", c, params["w_kv_b"]).reshape(
            b, s, H, dn + m.v_head_dim)
    k_rope = apply_rope(kv_a[..., None, m.kv_lora_rank:], positions,
                        cfg.rope_theta)
    q = jnp.concatenate(
        [q[..., :dn], apply_rope(q[..., dn:], positions, cfg.rope_theta)],
        axis=-1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (b, s, H, dr))], axis=-1)
    v = kv[..., dn:]
    if _counted_path(s, None, False, plan) == "flash":
        from repro.kernels.flash_attention import ops as fa_ops

        out = fa_ops.causal_attention(q, k, v)
    else:
        out = _xla_causal(q, k, v, None, None, plan)
    out = out.reshape(b, s, H * m.v_head_dim)
    return jnp.einsum("bsk,kd->bsd", out, params["wo"]), None


# ---------------------------------------------------------------------------
# Dense FFN
# ---------------------------------------------------------------------------


def dense_ffn(params, x, activation: str = "swiglu") -> jax.Array:
    if activation == "swiglu":
        gate = jnp.einsum("bsd,df->bsf", x, params["w_gate"])
        up = jnp.einsum("bsd,df->bsf", x, params["w_up"])
        h = jax.nn.silu(gate) * up
    else:  # gelu, 2-matrix
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, params["w_up"]))
    return jnp.einsum("bsf,fd->bsd", h, params["w_down"])
