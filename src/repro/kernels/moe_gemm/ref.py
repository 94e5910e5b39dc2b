"""Pure-jnp oracles for the grouped expert GEMM / grouped FFN.

Both the padded (E, C, d) capacity layout and the ragged sorted-rows +
offsets layout have an oracle here.  The ragged oracles gather the full
per-row expert weight (O(T·d·f) temp) — they exist for correctness
reference and as the XLA fallback of the ragged dispatch path on shapes
where that temp is acceptable; the Pallas kernels are the perf path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def grouped_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """out[e] = x[e] @ w[e]; fp32 accumulation like the kernel."""
    return jnp.einsum(
        "emk,ekn->emn", x, w, preferred_element_type=jnp.float32
    )


def grouped_ffn(tokens, w_up, w_gate, w_down, activation: str = "swiglu"):
    """tokens: (E, C, d) -> (E, C, d); the MoE expert-FFN oracle.

    Mirrors the kernel path's precision contract: the hidden activation
    stays fp32 until after the down-projection.
    """
    if activation == "swiglu":
        gate = grouped_matmul(tokens, w_gate)
        up = grouped_matmul(tokens, w_up)
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(grouped_matmul(tokens, w_up))
    return grouped_matmul(h, w_down).astype(tokens.dtype)


# ---------------------------------------------------------------------------
# Ragged (sorted rows + offsets) oracles
# ---------------------------------------------------------------------------


def row_experts(offsets: jax.Array, T: int) -> jax.Array:
    """Expert id per row of a sorted ragged layout; rows >= offsets[-1]
    (padding) map to E (one past the last expert)."""
    return jnp.searchsorted(
        offsets[1:], jnp.arange(T, dtype=offsets.dtype), side="right"
    )


def ragged_matmul(x: jax.Array, w: jax.Array, offsets: jax.Array):
    """out[t] = x[t] @ w[expert_of(t)]; zero for padding rows."""
    T = x.shape[0]
    E = w.shape[0]
    e = jnp.minimum(row_experts(offsets, T), E - 1)
    out = jnp.einsum(
        "tk,tkn->tn", x, w[e], preferred_element_type=jnp.float32
    )
    own = (jnp.arange(T, dtype=offsets.dtype) < offsets[-1])[:, None]
    return jnp.where(own, out, 0.0).astype(x.dtype)


def ragged_dw(x: jax.Array, g: jax.Array, offsets: jax.Array, E: int):
    """dW[e] = x_e^T @ g_e over each expert's rows, in fp32; zero for an
    expert with no rows."""
    e = row_experts(offsets, x.shape[0])
    onehot = (e[:, None] == jnp.arange(E)).astype(jnp.float32)
    return jnp.einsum("te,tk,tn->ekn", onehot, x.astype(jnp.float32),
                      g.astype(jnp.float32))


def ragged_ffn(tokens, w_up, w_gate, w_down, offsets,
               activation: str = "swiglu"):
    """Dropless grouped FFN oracle over sorted rows; differentiable, so it
    doubles as the jax.grad reference for the custom-VJP kernel path."""
    T = tokens.shape[0]
    E = w_up.shape[0]
    e = jnp.minimum(row_experts(offsets, T), E - 1)
    x32 = tokens.astype(jnp.float32)
    if activation == "swiglu":
        gate = jnp.einsum("tk,tkf->tf", x32, w_gate[e].astype(jnp.float32))
        up = jnp.einsum("tk,tkf->tf", x32, w_up[e].astype(jnp.float32))
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(
            jnp.einsum("tk,tkf->tf", x32, w_up[e].astype(jnp.float32))
        )
    out = jnp.einsum("tf,tfd->td", h, w_down[e].astype(jnp.float32))
    own = (jnp.arange(T, dtype=offsets.dtype) < offsets[-1])[:, None]
    return jnp.where(own, out, 0.0).astype(tokens.dtype)
