"""Public wrapper: layout adaptation + interpret switch.

The model keeps activations as (b, s, h, d); the kernel wants (b, h, s, d).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_attention import flash_attention as fa


def flash_attention(
    q: jax.Array,  # (b, s, hq, d) — model layout
    k: jax.Array,  # (b, s, hkv, d)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    interpret: Optional[bool] = None,
    bq: int = 256,
    bk: int = 256,
) -> jax.Array:
    interpret = interpret_mode() if interpret is None else interpret
    out = fa.flash_attention(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal,
        window=window,
        softcap=logit_softcap,
        bq=bq,
        bk=bk,
        interpret=interpret,
    )
    return out.transpose(0, 2, 1, 3)
