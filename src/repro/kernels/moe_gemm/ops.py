"""Public jit'd wrappers for the grouped expert GEMM kernels.

Two families:

* ``grouped_matmul`` / ``grouped_ffn`` — the padded capacity-dispatch path:
  (E, C, d) buffers, three dense launches.
* ``ragged_matmul`` / ``ragged_ffn`` — the dropless path: one (T, d) matrix
  of token rows sorted by expert + per-expert ``offsets`` (E+1,).
  ``ragged_ffn`` carries a ``jax.custom_vjp`` so the backward pass also runs
  as ragged kernels (two ragged GEMMs for dh/dx + ragged dgrads for the
  expert weights) with fp32 accumulation in both directions — ``jax.grad``
  through it never sees the Pallas internals.

Precision contract: bf16 (or fp32) inputs, fp32 accumulation everywhere,
and the hidden activation stays fp32 *between* launches — the only cast
back to the input dtype happens after the final down-projection.
"""

from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import interpret_mode
from repro.kernels.moe_gemm import moe_gemm


def grouped_matmul(x, w, *, interpret=None, **blocks):
    interpret = interpret_mode() if interpret is None else interpret
    out = moe_gemm.grouped_matmul_f32(x, w, interpret=interpret, **blocks)
    return out.astype(x.dtype)


def grouped_ffn(tokens, w_up, w_gate, w_down, activation: str = "swiglu",
                *, interpret=None, **blocks):
    """Expert FFN: three grouped GEMMs + gated activation (elementwise ops
    fused by XLA between kernel launches).

    The hidden activation h is kept in fp32 between the up/gate and down
    launches: casting it to the token dtype would silently truncate the
    fp32 accumulation the kernel exists to provide (the down-projection
    contracts over d_ffn, so the truncation error compounds with width).
    """
    interpret = interpret_mode() if interpret is None else interpret
    mm = partial(moe_gemm.grouped_matmul_f32, interpret=interpret, **blocks)
    if activation == "swiglu":
        h = jax.nn.silu(mm(tokens, w_gate)) * mm(tokens, w_up)
    else:
        h = jax.nn.gelu(mm(tokens, w_up))
    return mm(h, w_down).astype(tokens.dtype)


# ---------------------------------------------------------------------------
# Ragged (dropless) path
# ---------------------------------------------------------------------------


def _pad_rows(x: jax.Array, bm: int):
    """Pad the row dim to a multiple of bm (kernel tile granularity)."""
    T = x.shape[0]
    T_pad = ((T + bm - 1) // bm) * bm
    if T_pad == T:
        return x, T
    return jnp.pad(x, ((0, T_pad - T), (0, 0))), T


def _launch(kernel, a, b, E: int, *args, bm: int, bn, bk, interpret: bool):
    """One ragged kernel call on rows ``a`` (T, K) and ``b`` (weights
    (E, K, N), or dw's cotangent rows (T, N)), its blocks picked from
    the shapes where ``bn``/``bk`` are None, and counted at trace time as
    ``moe_gemm.tiles``."""
    name = kernel.__name__
    t = moe_gemm.ragged_tiles(name, a.shape[0], E, a.shape[1],
                              b.shape[-1], a.dtype, b.dtype,
                              bm=bm, bn=bn, bk=bk)
    obs.counter("moe_gemm.tiles", kernel=name, bm=t.bm, bn=t.bn, bk=t.bk,
                grid_steps=t.grid_steps)
    return kernel(a, b, *args, bm=t.bm, bn=t.bn, bk=t.bk,
                  interpret=interpret)


def ragged_matmul(x, w, offsets, *, interpret=None, bm=None, bn=None,
                  bk=None):
    """out[t] = x[t] @ w[expert_of(t)] for rows sorted by expert.

    x: (T, K); w: (E, K, N); offsets: (E+1,) int32 with offsets[E] <= T.
    Rows beyond offsets[E] (padding) produce zeros.  Returns x.dtype.
    Blocks left None are picked from the shapes (``moe_gemm.ragged_tiles``).
    """
    interpret = interpret_mode() if interpret is None else interpret
    E = w.shape[0]
    bm = moe_gemm.row_tile(x.shape[0], E) if bm is None else bm
    xp, T = _pad_rows(x, bm)
    out = _launch(moe_gemm.ragged_matmul_f32, xp, w, E, offsets, bm=bm,
                  bn=bn, bk=bk, interpret=interpret)
    return out[:T].astype(x.dtype)


@functools.lru_cache(maxsize=None)
def _make_ragged_ffn(activation: str, interpret: bool, bm: int, bn, bk):
    """Build the custom-VJP ragged grouped FFN for one static config.

    Forward: fused gate·up·SiLU launch (emits fp32 pre-activations as
    residuals) + one ragged down-projection GEMM.
    Backward: dh and dx as ragged GEMMs against the transposed expert
    weights, dW as ragged dgrads — fp32 accumulation throughout; cotangents
    are cast back to the primal dtypes at the boundary.  Each launch takes
    its own blocks (``_launch``); they share ``bm``, the rows' padding.
    """
    blocks = dict(bm=bm, bn=bn, bk=bk, interpret=interpret)

    def mm(x, w, offsets):
        return _launch(moe_gemm.ragged_matmul_f32, x, w, w.shape[0],
                       offsets, **blocks)

    def dw(x, g, offsets, E):
        return _launch(moe_gemm.ragged_dw_f32, x, g, E, offsets, E,
                       **blocks)

    def _hidden(x, w_up, w_gate, offsets):
        if activation == "swiglu":
            return _launch(moe_gemm.ragged_gate_up_silu_f32, x, w_gate,
                           w_gate.shape[0], w_up, offsets, **blocks)
        a_u = mm(x, w_up, offsets)
        return jax.nn.gelu(a_u), None, a_u

    @jax.custom_vjp
    def ffn(x, w_up, w_gate, w_down, offsets):
        h, _, _ = _hidden(x, w_up, w_gate, offsets)
        return mm(h, w_down, offsets)

    def fwd(x, w_up, w_gate, w_down, offsets):
        h, a_g, a_u = _hidden(x, w_up, w_gate, offsets)
        y = mm(h, w_down, offsets)
        return y, (x, w_up, w_gate, w_down, offsets, a_g, a_u)

    def bwd(res, dy):
        x, w_up, w_gate, w_down, offsets, a_g, a_u = res
        E = w_up.shape[0]
        dy = dy.astype(jnp.float32)
        if activation == "swiglu":
            sig = jax.nn.sigmoid(a_g)
            silu_g = a_g * sig
            h = silu_g * a_u
        else:
            h = jax.nn.gelu(a_u)
        # dh = dy @ w_down^T  (ragged GEMM, per-expert transposed weights)
        dh = mm(dy, jnp.swapaxes(w_down, 1, 2), offsets)
        # dW_down[e] = h_e^T @ dy_e  (ragged dgrad)
        dwd = dw(h, dy, offsets, E)
        if activation == "swiglu":
            d_silu = sig * (1.0 + a_g * (1.0 - sig))
            da_g = dh * a_u * d_silu
            da_u = dh * silu_g
            dx = mm(da_g, jnp.swapaxes(w_gate, 1, 2), offsets) + mm(
                da_u, jnp.swapaxes(w_up, 1, 2), offsets
            )
            dwg = dw(x, da_g, offsets, E).astype(w_gate.dtype)
            dwu = dw(x, da_u, offsets, E).astype(w_up.dtype)
        else:
            _, gelu_vjp = jax.vjp(jax.nn.gelu, a_u)
            (da_u,) = gelu_vjp(dh)
            dx = mm(da_u, jnp.swapaxes(w_up, 1, 2), offsets)
            dwg = None
            dwu = dw(x, da_u, offsets, E).astype(w_up.dtype)
        # Rows no expert owns carry no gradient.
        rows = jnp.arange(x.shape[0], dtype=jnp.int32)[:, None]
        dx = jnp.where(rows < offsets[-1], dx, 0.0).astype(x.dtype)
        return dx, dwu, dwg, dwd.astype(w_down.dtype), None

    ffn.defvjp(fwd, bwd)
    return ffn


def ragged_ffn(tokens, w_up, w_gate, w_down, offsets,
               activation: str = "swiglu", *, interpret=None,
               bm=None, bn=None, bk=None):
    """Dropless grouped expert FFN over sorted token rows.

    tokens: (T, d) rows sorted by expert; offsets: (E+1,) int32 prefix sums
    (offsets[E] = occupied rows <= T).  Differentiable end-to-end via the
    custom VJP; rows >= offsets[E] get zero output and zero gradient.
    Blocks left None are picked from the shapes, per launch
    (``moe_gemm.ragged_tiles``).
    """
    if activation == "swiglu" and w_gate is None:
        raise ValueError("swiglu ragged_ffn requires w_gate")
    interpret = interpret_mode() if interpret is None else interpret
    bm = moe_gemm.row_tile(tokens.shape[0], w_up.shape[0]) if bm is None \
        else bm
    xp, T = _pad_rows(tokens, bm)
    ffn = _make_ragged_ffn(activation, interpret, bm, bn, bk)
    if activation != "swiglu":
        w_gate = None
    out = ffn(xp, w_up, w_gate, w_down, offsets.astype(jnp.int32))
    return out[:T].astype(tokens.dtype)
