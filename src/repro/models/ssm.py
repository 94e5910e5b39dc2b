"""Mamba2 (SSD — state-space duality) block, TPU-native chunked form.

The SSD algorithm (arXiv:2405.21060) recasts the selective-state-space
recurrence as block matmuls: intra-chunk attention-like products + an
inter-chunk linear recurrence over per-chunk states.  That chunked matmul
structure is exactly what the MXU wants — this is the hardware adaptation of
the GPU scan kernel (DESIGN.md §2).  The inter-chunk recurrence uses
``lax.associative_scan`` so a sequence-sharded layout (chunks spread over the
"ep"/"tp" axes) lowers to a log-depth collective-permute chain instead of a
serial scan.

Sharding note: the reference CUDA implementation fuses z/x/B/C/dt into one
``in_proj``; we keep them as separate projection matrices so every output dim
shards exactly over the ("ep","tp") axes — a fused projection would put the
z/x/B/C/dt split points inside shards and force GSPMD reshards.  Same math.

The whole block is jnp, the intra-chunk term included; XLA lowers it on
every backend.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models.layers import rms_norm


def segsum(x: jax.Array) -> jax.Array:
    """Segmented cumulative sums: out[..., i, j] = sum_{k in (j, i]} x[..., k]
    for i >= j (else -inf).  Diagonal is 0."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool))
    return jnp.where(mask, seg, -jnp.inf)


def ssd_chunked(
    x: jax.Array,  # (b, l, h, p) — per-head inputs (not yet dt-scaled)
    dt: jax.Array,  # (b, l, h) — softplus'd step sizes
    a: jax.Array,  # (h,) — negative decay rates (-exp(A_log))
    B: jax.Array,  # (b, l, g, n)
    C: jax.Array,  # (b, l, g, n)
    chunk: int,
    initial_state: Optional[jax.Array] = None,  # (b, h, p, n)
    head_group: int = 32,
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD. Returns (y (b,l,h,p), final_state (b,h,p,n)).

    When the head count is large (jamba: 256), the intra-chunk decay
    matrices L (b, nc, h, cl, cl) dominate activation memory; heads are
    independent, so we scan over head groups of ``head_group`` with
    rematerialization — exact, with bounded live memory.
    """
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]

    if (
        h > head_group
        and h % head_group == 0
        and g == 1
        and initial_state is None
    ):
        ng = h // head_group
        xg = x.reshape(b, l, ng, head_group, p).transpose(2, 0, 1, 3, 4)
        dtg = dt.reshape(b, l, ng, head_group).transpose(2, 0, 1, 3)
        ag = a.reshape(ng, head_group)

        @jax.checkpoint
        def group(carry, xs):
            xi, dti, ai = xs
            y, fin = ssd_chunked(xi, dti, ai, B, C, chunk, head_group=h)
            return carry, (y, fin)

        _, (ys, fins) = lax.scan(group, jnp.float32(0.0), (xg, dtg, ag))
        y = ys.transpose(1, 2, 0, 3, 4).reshape(b, l, h, p)
        final = fins.transpose(1, 0, 2, 3, 4).reshape(b, h, p, n)
        return y, final
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2)  # (b, l, h, n)
    Ch = jnp.repeat(C, rep, axis=2)

    dA = (dt.astype(jnp.float32) * a.astype(jnp.float32))  # (b, l, h)
    xdt = x * dt[..., None].astype(x.dtype)

    assert l % chunk == 0, (l, chunk)
    nc = l // chunk

    def to_chunks(t):
        return t.reshape(b, nc, chunk, *t.shape[2:])

    xc, dAc, Bc, Cc = map(to_chunks, (xdt, dA, Bh, Ch))

    A_cs = jnp.cumsum(dAc, axis=2)  # (b, nc, cl, h)

    # Intra-chunk ("diagonal block") term.
    L = jnp.exp(segsum(dAc.transpose(0, 1, 3, 2)))  # (b, nc, h, cl, cl)
    Y_diag = jnp.einsum(
        "bclhn,bcshn,bchls,bcshp->bclhp", Cc, Bc, L.astype(Cc.dtype), xc
    )

    # Per-chunk states.
    decay_states = jnp.exp(A_cs[:, :, -1:, :] - A_cs)  # (b, nc, cl, h)
    states = jnp.einsum(
        "bclhn,bclh,bclhp->bchpn", Bc, decay_states.astype(Bc.dtype), xc
    )

    # Inter-chunk linear recurrence: s_c = exp(sum dA_c) * s_{c-1} + u_c.
    decay_chunk = jnp.exp(A_cs[:, :, -1, :]).astype(states.dtype)  # (b, nc, h)

    if initial_state is not None:
        states = states.at[:, 0].add(
            decay_chunk[:, 0][..., None, None] * initial_state.astype(states.dtype)
        )

    def combine(e1, e2):
        d1, s1 = e1
        d2, s2 = e2
        return d1 * d2, s1 * d2[..., None, None] + s2

    _, s_scan = lax.associative_scan(combine, (decay_chunk, states), axis=1)
    # states_prev[c] = state entering chunk c: the initial state for chunk 0
    # (its off-diagonal term needs it), the scanned state otherwise.
    prev0 = (
        initial_state[:, None].astype(s_scan.dtype)
        if initial_state is not None
        else jnp.zeros_like(s_scan[:, :1])
    )
    states_prev = jnp.concatenate([prev0, s_scan[:, :-1]], axis=1)
    final_state = s_scan[:, -1]

    # Off-diagonal (cross-chunk) term.
    state_decay = jnp.exp(A_cs).astype(Cc.dtype)  # (b, nc, cl, h)
    Y_off = jnp.einsum("bclhn,bchpn,bclh->bclhp", Cc, states_prev, state_decay)

    y = (Y_diag + Y_off).reshape(b, l, h, p)
    return y, final_state


def _causal_conv(x: jax.Array, w: jax.Array, bias: jax.Array) -> jax.Array:
    """Depthwise causal conv1d.  x: (b, l, c); w: (c, width)."""
    width = w.shape[-1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = lax.conv_general_dilated(
        xp,
        w.T[:, None, :].astype(x.dtype),  # (width, 1=I, c=O)
        window_strides=(1,),
        padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1],
    )
    return out + bias.astype(out.dtype)


def _conv_step(window, w, b):
    """window: (b, w, c); w: (c, width) -> (b, c)."""
    out = jnp.einsum(
        "bwc,cw->bc", window.astype(jnp.float32), w.astype(jnp.float32)
    ) + b.astype(jnp.float32)
    return out


def mamba_block(
    params: Dict[str, jax.Array],
    x: jax.Array,  # (b, l, d)
    arch: ArchConfig,
    *,
    cache: Optional[Dict[str, jax.Array]] = None,
    return_cache: bool = False,
):
    """Mamba2 mixer sub-layer.

    cache = {"ssm": (b,h,p,n), "conv_x": (b,w-1,d_in), "conv_B": ...,
    "conv_C": ...} enables single-step decode; return_cache=True makes a
    prefill pass emit one.
    """
    s = arch.ssm
    assert s is not None
    b, l, d = x.shape
    d_in = s.expand * arch.d_model
    gn = s.n_groups * s.state_size
    nh = s.num_heads(arch.d_model)

    z = jnp.einsum("bld,dk->blk", x, params["w_z"])
    xs = jnp.einsum("bld,dk->blk", x, params["w_x"])
    Bp = jnp.einsum("bld,dk->blk", x, params["w_B"])
    Cp = jnp.einsum("bld,dk->blk", x, params["w_C"])
    dt = jnp.einsum("bld,dk->blk", x, params["w_dt"])  # (b, l, nh)

    a = -jnp.exp(params["A_log"].astype(jnp.float32))  # (nh,)
    new_cache = None

    if cache is not None:
        assert l == 1
        win_x = jnp.concatenate([cache["conv_x"], xs], axis=1)
        win_B = jnp.concatenate([cache["conv_B"], Bp], axis=1)
        win_C = jnp.concatenate([cache["conv_C"], Cp], axis=1)
        xs_c = jax.nn.silu(_conv_step(win_x, params["conv_x_w"], params["conv_x_b"]))
        B_c = jax.nn.silu(_conv_step(win_B, params["conv_B_w"], params["conv_B_b"]))
        C_c = jax.nn.silu(_conv_step(win_C, params["conv_C_w"], params["conv_C_b"]))
        dt_s = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"])  # (b, nh)
        xh = xs_c.reshape(b, nh, s.head_dim).astype(x.dtype)
        Bh = jnp.repeat(
            B_c.reshape(b, s.n_groups, s.state_size), nh // s.n_groups, 1
        ).astype(x.dtype)
        Ch = jnp.repeat(
            C_c.reshape(b, s.n_groups, s.state_size), nh // s.n_groups, 1
        ).astype(x.dtype)
        decay = jnp.exp(dt_s * a)  # (b, nh)
        update = jnp.einsum("bh,bhp,bhn->bhpn", dt_s.astype(x.dtype), xh, Bh)
        ssm = cache["ssm"] * decay[..., None, None].astype(x.dtype) + update
        y = jnp.einsum("bhpn,bhn->bhp", ssm, Ch)
        y = y + xh * params["D"][None, :, None].astype(x.dtype)
        y = y.reshape(b, 1, d_in)
        new_cache = {
            "ssm": ssm,
            "conv_x": win_x[:, 1:],
            "conv_B": win_B[:, 1:],
            "conv_C": win_C[:, 1:],
        }
    else:
        xs_c = jax.nn.silu(
            _causal_conv(xs, params["conv_x_w"], params["conv_x_b"])
        ).astype(x.dtype)
        B_c = jax.nn.silu(
            _causal_conv(Bp, params["conv_B_w"], params["conv_B_b"])
        ).astype(x.dtype)
        C_c = jax.nn.silu(
            _causal_conv(Cp, params["conv_C_w"], params["conv_C_b"])
        ).astype(x.dtype)
        dt_s = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (b,l,nh)
        xh = xs_c.reshape(b, l, nh, s.head_dim)
        Bg = B_c.reshape(b, l, s.n_groups, s.state_size)
        Cg = C_c.reshape(b, l, s.n_groups, s.state_size)
        chunk = min(s.chunk_size, l)
        y, final = ssd_chunked(xh, dt_s.astype(x.dtype), a, Bg, Cg, chunk)
        y = y + xh * params["D"][None, None, :, None].astype(x.dtype)
        y = y.reshape(b, l, d_in)
        if return_cache:
            w = s.conv_width

            def tail(t):
                tl = t[:, -(w - 1):, :]
                pad = (w - 1) - tl.shape[1]
                return jnp.pad(tl, ((0, 0), (pad, 0), (0, 0))) if pad > 0 else tl

            new_cache = {
                "ssm": final.astype(x.dtype),
                "conv_x": tail(xs),
                "conv_B": tail(Bp),
                "conv_C": tail(Cp),
            }

    # Gated RMSNorm + output projection.
    y = rms_norm(
        y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
        params["norm_scale"],
        arch.norm_eps,
    )
    out = jnp.einsum("blk,kd->bld", y, params["out_proj"])
    return out, new_cache


def init_ssm_cache(arch: ArchConfig, batch: int, dtype) -> Dict[str, jax.Array]:
    s = arch.ssm
    nh = s.num_heads(arch.d_model)
    d_in = s.expand * arch.d_model
    gn = s.n_groups * s.state_size
    w = s.conv_width
    return {
        "ssm": jnp.zeros((batch, nh, s.head_dim, s.state_size), dtype),
        "conv_x": jnp.zeros((batch, w - 1, d_in), dtype),
        "conv_B": jnp.zeros((batch, w - 1, gn), dtype),
        "conv_C": jnp.zeros((batch, w - 1, gn), dtype),
    }
