"""Trainable causal GQA attention through JAX's TPU splash-attention kernel
(``causal_attention``): fully masked blocks are skipped in the forward, dq
and dkv kernels, with an fp32 online softmax and a custom VJP.

The model keeps activations as (b, s, h, d); the kernel wants (b, h, s, d).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from repro.kernels import interpret_mode


# The q/kv block of the forward and the fused backward kernel at long
# sequences; the sweep behind it (one v5e, (4, 24/8, 4096, 64)) is in
# PERF.md.
_BLOCK = 1024


def block_size(s: int) -> int:
    """The block of ``causal_attention`` at sequence ``s`` (a multiple of
    128): ``_BLOCK`` halved until it divides ``s``."""
    b = _BLOCK
    while s % b:
        b //= 2
    return b


@functools.lru_cache(maxsize=64)
def _splash_kernel(s: int, hq: int, softcap: Optional[float],
                   interpret: bool):
    """The splash kernel for one shape, built once: its mask info is
    computed on the host in numpy, which a retrace must not repeat, and
    made concrete arrays (``ensure_compile_time_eval``), so that two
    traces of one step — the scans of a leading dense layer and of the
    pattern — may share it.  GQA needs nothing here: the kernel reads kv
    head ``h // (hq // hkv)``."""
    b = block_size(s)
    sizes = splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        use_fused_bwd_kernel=True,
    )
    mask = splash.MultiHeadMask([splash.CausalMask((s, s))] * hq)
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha_single_device(
            mask, block_sizes=sizes, attn_logits_soft_cap=softcap,
            interpret=interpret,
        )


def causal_attention(
    q: jax.Array,  # (b, s, hq, d) — model layout
    k: jax.Array,  # (b, s, hkv, d)
    v: jax.Array,  # (b, s, hkv, dv); MLA's dv (128) is under its d (192)
    *,
    logit_softcap: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal GQA attention with a backward, for training and prefill;
    returns (b, s, hq, dv).

    ``s`` must be a multiple of 128 and ``hq`` of ``hkv``.  q is scaled by
    ``1/sqrt(d)`` before the kernel; for d = 64 or 256 that is exact in
    bf16, for other head dims (MLA's 192) it rounds once in q's dtype
    where ``models.layers.attention`` divides the fp32 scores instead.
    """
    interpret = interpret_mode() if interpret is None else interpret
    _, s, hq, d = q.shape
    kernel = _splash_kernel(s, hq, logit_softcap, interpret)
    q = q * jnp.asarray(1.0 / math.sqrt(d), q.dtype)
    out = jax.vmap(kernel)(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
    )
    return out.transpose(0, 2, 1, 3)
