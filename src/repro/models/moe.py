"""Mixture-of-Experts FFN with explicit expert-parallel all-to-all.

This implements the paper's §III training flow

    attn -> routing -> dispatch_a2a -> expert GEMM -> combine_a2a

as an explicit ``shard_map`` over the refined mesh, so the collective
schedule is fully controlled (the subject of the paper) rather than left to
GSPMD heuristics:

* tokens are sequence+batch sharded over (dp x sp) — Piper's expert-data
  parallelism: every device routes its own tokens;
* the dispatch/combine ``all_to_all`` spans exactly the ``"ep"`` axis (the
  topologically-local fast domain, paper Eq 10);
* expert weights are ZeRO-3 sharded over ("data","tp") on the d_ff dim and
  gathered at use (reduce-scattered on the backward pass, automatically via
  the all_gather transpose);
* optionally (``plan.hierarchical_a2a``) the dispatch uses HALO's
  hierarchical two-phase schedule from ``repro.core.halo`` instead of the
  flat collective;
* optionally (``plan.a2a_chunks`` > 1) the dispatch buffer is split into
  row chunks driven through ``halo.overlapped_a2a``: chunk k+1's transfer
  is issued while chunk k's expert FFN runs (double buffering), on both
  the dispatch and combine sides, for both dispatch modes, and — through
  AD — on the backward pass (docs/a2a.md).

Two dispatch modes (``MoECfg.dispatch``):

* **capacity** (GShard/Tutel-style, static shapes): each device builds an
  (E, C, d) buffer; slot overflow beyond C = ceil(T*k/E * cf) is dropped
  (the paper's zero-padding baseline — §II-A's wasted skinny-GEMM cycles).
* **ragged** (MegaBlocks-style, dropless): ``argsort`` the flat expert
  assignments into contiguous per-expert row segments, run the ragged
  grouped GEMM over exactly the occupied rows (``kernels.moe_gemm``), and
  combine through the inverse permutation.  Locally this drops nothing and
  multiplies no zeros; under EP a tiny counts-exchange pre-pass ships the
  per-(rank, expert) segment sizes, then the a2a payload is just the
  sorted rows at the capacity-mode wire size, budgeted per destination
  *rank* (E_l*C rows) rather than per expert — every token kept by
  per-expert capacity is also kept here, and usually more.  Decode
  (replicated tokens) sorts per rank by local expert id and combines the
  ragged partial outputs with psum("ep").

Everything is differentiable; expert-weight gradients reduce over the data
axis through the gather transpose.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.configs.base import ArchConfig, MoECfg
from repro.core import halo
from repro.sharding import MeshPlan


def _all_axes(plan: MeshPlan) -> Tuple[str, ...]:
    # Under pipelining the pp axis holds different LAYERS: metric reductions
    # must not mix stages (the pipeline executor masks + reduces itself).
    return tuple(a for a in plan.mesh.axis_names if a != plan.pp_axis)


@jax.named_scope("moe.router")
def _route(x_tokens: jax.Array, w_router: jax.Array, moe: MoECfg,
           bias: Optional[jax.Array] = None):
    """Top-k routing. x_tokens: (T, d) -> (weights (T,k), ids (T,k),
    scores (T,E), logits).

    softmax: the top-k of the softmax, renormalised.  sigmoid (DeepSeek-V3
    noaux_tc, one group): the top-k of the sigmoid scores plus ``bias``
    (E,) chooses the experts; their weights are the unbiased scores,
    renormalised, times ``moe.routed_scale``."""
    logits = jnp.einsum(
        "td,de->te", x_tokens.astype(jnp.float32), w_router.astype(jnp.float32)
    )
    if moe.scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = lax.top_k(probs, moe.top_k)
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        return top_w, top_i, probs, logits
    scores = jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + bias
    _, top_i = lax.top_k(lax.stop_gradient(choice), moe.top_k)
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    return top_w * moe.routed_scale, top_i, scores, logits


@jax.named_scope("moe.router")
def _aux_losses(probs, logits, top_i, moe: MoECfg, axes, rows: int = 1,
                sp_axes=()):
    """Load-balancing aux loss (Switch-style, meaned over the global token
    population via psum over every mesh axis; or, with ``moe.seq_aux``,
    DeepSeek-V3's per sequence over the ``rows`` here) + router z-loss,
    and the global expert counts."""
    T = probs.shape[0]
    E = moe.num_experts
    counts = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    totals = lax.psum(jnp.float32(T), axes) if axes else jnp.float32(T)
    counts_g = lax.psum(counts, axes) if axes else counts
    if moe.seq_aux:
        aux = _seq_aux_loss(probs, top_i, rows, moe, sp_axes, axes)
    else:
        probs_sum = lax.psum(probs.sum(0), axes) if axes else probs.sum(0)
        frac_tokens = counts_g / (totals * moe.top_k)
        frac_probs = probs_sum / totals
        aux = E * jnp.sum(frac_tokens * frac_probs) * moe.aux_loss_coef
    if not moe.z_loss_coef:
        return aux, jnp.float32(0.0), counts_g
    z_local = jnp.sum(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    z = (lax.psum(z_local, axes) if axes else z_local) / totals * moe.z_loss_coef
    return aux, z, counts_g


@jax.named_scope("moe.router")
def _seq_aux_loss(scores, top_i, rows: int, moe: MoECfg, sp_axes, axes):
    """DeepSeek-V3's sequence-wise balance loss (arXiv:2412.19437, eqs.
    17-20): per sequence, ``coef * sum_e f_e P_e`` with f_e = E/(k s) x the
    sequence's rows sent to expert e and P_e the mean of its scores
    normalised over the experts; meaned over the sequences.  A sequence
    split over the ``sp_axes`` has its sums taken over them first."""
    T, E = scores.shape
    s_l = T // rows
    norm = scores / jnp.sum(scores, axis=-1, keepdims=True)
    row = jnp.repeat(jnp.arange(T, dtype=jnp.int32) // s_l, moe.top_k)
    cnt = jnp.zeros((rows, E), jnp.float32).at[row, top_i.reshape(-1)].add(1.0)
    p_sum = norm.reshape(rows, s_l, E).sum(axis=1)
    s = jnp.float32(s_l)
    if sp_axes:
        cnt, p_sum, s = (lax.psum(t, sp_axes) for t in (cnt, p_sum, s))
    per_row = jnp.sum(cnt * E / (moe.top_k * s) * (p_sum / s), axis=-1)
    tot, n = jnp.sum(per_row), jnp.float32(rows)
    if axes:
        tot, n = lax.psum(tot, axes), lax.psum(n, axes)
    return tot / n * moe.aux_loss_coef


@jax.named_scope("moe.bias_update")
def update_router_bias(blocks, loads: jax.Array, arch: ArchConfig):
    """DeepSeek-V3's auxiliary-loss-free balancing, after a step: each MoE
    layer's ``router_bias`` (in units of ``bias_update_speed``) moves by
    sign(mean load - load) per expert.  ``blocks``: the stacked pattern
    positions; ``loads``: the step's (reps, n_moe_positions, E) counts."""
    out = list(blocks)
    moe_pos = [i for i, (_, f) in enumerate(arch.block_pattern) if f == "moe"]
    for j, i in enumerate(moe_pos):
        load = loads[:, j, :]
        move = jnp.sign(jnp.mean(load, axis=-1, keepdims=True) - load)
        ffn = dict(out[i]["ffn"])
        ffn["router_bias"] = ffn["router_bias"] + move.astype(jnp.int32)
        out[i] = {**out[i], "ffn": ffn}
    return tuple(out)


def _capacity(T: int, moe: MoECfg) -> int:
    """Per-rank expert slot budget C = ceil(T*k/E * cf) (GShard/Tutel) —
    shared by the sharded and single-rank dispatch paths."""
    return int(
        math.ceil(T * moe.top_k / moe.num_experts * moe.capacity_factor)
    )


@jax.named_scope("moe.dispatch")
def _scatter_to_buffers(xt, flat_e, pos, keep, E: int, capacity: int):
    """Token rows -> (E, C, d) capacity buffers (overflow masked to zero)."""
    src = jnp.repeat(xt, len(flat_e) // xt.shape[0], axis=0)  # (T*k, d)
    buf = jnp.zeros((E, capacity, xt.shape[-1]), xt.dtype)
    return buf.at[flat_e, pos].add(src * keep[:, None].astype(xt.dtype))


@jax.named_scope("moe.combine")
def _combine_expert_outputs(vals, flat_w, keep, T: int, k: int, d: int):
    """Weighted top-k combine of gathered expert outputs back to tokens."""
    vals = vals * (flat_w * keep.astype(jnp.float32))[:, None].astype(vals.dtype)
    return vals.reshape(T, k, d).sum(axis=1)


@jax.named_scope("moe.dispatch")
def _dispatch_indices(top_i, top_w, E: int, capacity: int):
    """Slot assignment: position of each (token,k) pair within its expert's
    capacity buffer.  Returns (flat_e, pos, keep, flat_w)."""
    flat_e = top_i.reshape(-1)  # (T*k,)
    flat_w = top_w.reshape(-1)
    one_hot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (T*k, E)
    pos_all = jnp.cumsum(one_hot, axis=0) - 1  # (T*k, E)
    pos = jnp.take_along_axis(pos_all, flat_e[:, None], axis=1)[:, 0]
    keep = pos < capacity
    pos = jnp.where(keep, pos, 0)
    return flat_e, pos, keep, flat_w


@jax.named_scope("moe.experts")
def _expert_ffn(tokens, w_up, w_gate, w_down, activation: str):
    """Grouped expert GEMM of capacity dispatch, as XLA einsums.
    tokens: (E_l, C_r, d).

    The matmuls accumulate in fp32 (``preferred_element_type``) and the
    hidden activation stays fp32 into the down-projection, as in the ragged
    kernels; only the output casts back to the tokens' dtype.
    """
    f32 = jnp.float32
    if activation == "swiglu":
        gate = jnp.einsum("ecd,edf->ecf", tokens, w_gate,
                          preferred_element_type=f32)
        up = jnp.einsum("ecd,edf->ecf", tokens, w_up,
                        preferred_element_type=f32)
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", tokens, w_up,
                                   preferred_element_type=f32))
    out = jnp.einsum("ecf,efd->ecd", h, w_down, preferred_element_type=f32)
    return out.astype(tokens.dtype)


# -- ragged (sort-based, dropless) dispatch ---------------------------------


@jax.named_scope("moe.dispatch")
def _sort_dispatch(flat_e: jax.Array, E: int):
    """Sort-based dispatch: replaces the O(T·k·E) one-hot-cumsum slot
    assignment with an O(T·k·log) argsort into contiguous per-expert row
    segments.  Returns (order, inv, offsets): ``order`` permutes flat
    (token,k) pairs into expert-sorted order, ``inv`` is its inverse, and
    ``offsets`` (E+1,) are the per-expert prefix sums."""
    order = jnp.argsort(flat_e)  # stable: ties keep token order
    inv = jnp.argsort(order)
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts).astype(jnp.int32)]
    )
    return order, inv, offsets


# The ragged path's two permutations.  Autodiff would transpose each gather
# into a scatter-add of T·k rows; since ``order`` and ``inv`` are inverse
# permutations, both backward passes are gathers instead.  Each call is
# counted at trace time as ``moe.permute``.


def _rows(x, idx):
    """x[idx] for indices in range by construction."""
    return x.at[idx].get(mode="promise_in_bounds")


def _to_expert_order(xt, order, inv, k: int):
    """Token rows (T, d) -> expert-sorted rows (T·k, d): ``xt[order // k]``.
    Backward: each token sums its k rows' cotangents, gathered by ``inv``,
    in float32."""
    obs.counter("moe.permute", op="to_expert", rows=order.shape[0])
    return _to_expert(xt, order, inv, k)


def _to_expert_rows(xt, order, inv, k):
    with jax.named_scope("moe.dispatch"):
        return _rows(xt, order // k)


def _to_expert_bwd(k, inv, g_xs):
    with jax.named_scope("moe.dispatch"):
        g = _rows(g_xs, inv).astype(jnp.float32)
        g_xt = g.reshape(-1, k, g.shape[-1]).sum(axis=1).astype(g_xs.dtype)
    return g_xt, None, None


_to_expert = jax.custom_vjp(_to_expert_rows, nondiff_argnums=(3,))
_to_expert.defvjp(
    lambda xt, order, inv, k: (_to_expert_rows(xt, order, inv, k), inv),
    _to_expert_bwd)


def _from_expert_order(ys, inv, order, w, k: int):
    """Expert-sorted rows (T·k, d) -> tokens (T, d): ``y[t] = Σ_j w[t·k+j]
    · ys[inv[t·k+j]]``.  Backward: ``ys`` takes its token's cotangent,
    gathered by ``order``, times its weight; ``w`` the float32 dot of the
    two."""
    obs.counter("moe.permute", op="from_expert", rows=inv.shape[0])
    return _from_expert(ys, inv, order, w, k)


def _from_expert_rows(ys, inv, order, w, k):
    with jax.named_scope("moe.combine"):
        vals = _rows(ys, inv)
        vals = vals * w[:, None].astype(vals.dtype)
        return vals.reshape(-1, k, vals.shape[-1]).sum(axis=1)


def _from_expert_bwd(k, res, g_y):
    ys, inv, order, w = res
    with jax.named_scope("moe.combine"):
        g_rows = _rows(g_y, order // k)  # (T·k, d), in expert order
        g_ys = g_rows * _rows(w, order)[:, None].astype(g_rows.dtype)
        f32 = jnp.float32
        g_w = jnp.sum(g_rows.astype(f32) * ys.astype(f32), axis=-1)
        g_w = _rows(g_w, inv).astype(w.dtype)
    return g_ys, None, None, g_w


_from_expert = jax.custom_vjp(_from_expert_rows, nondiff_argnums=(4,))
_from_expert.defvjp(
    lambda ys, inv, order, w, k: (_from_expert_rows(ys, inv, order, w, k),
                                  (ys, inv, order, w)),
    _from_expert_bwd)


@jax.named_scope("moe.experts")
def _ragged_rows_ffn(xs, w_up, w_gate, w_down, offsets, activation: str):
    """Grouped FFN over expert-sorted rows: always the ragged Pallas kernels
    (custom VJP, fp32 accumulation both directions), whatever kernel the
    attention uses.  The jnp oracle in ``kernels/moe_gemm/ref.py`` gathers a
    full expert weight per row (O(T·d·f) temp) and is for tests only."""
    from repro.kernels.moe_gemm import ops as moe_ops

    return moe_ops.ragged_ffn(xs, w_up, w_gate, w_down, offsets, activation)


def _moe_ragged_local(xt, top_phys, top_w, w_up, w_gate, w_down,
                      activation: str, E: int, k: int):
    """Dropless single-rank MoE compute: sort → ragged FFN → inverse
    permutation → weighted combine.  Processes every (token, k) pair —
    no capacity, no drops, no zero-padding beyond the kernel's row tile."""
    flat_e = top_phys.reshape(-1)
    flat_w = top_w.reshape(-1)
    order, inv, offsets = _sort_dispatch(flat_e, E)
    xs = _to_expert_order(xt, order, inv, k)  # (T*k, d) expert-sorted
    ys = _ragged_rows_ffn(xs, w_up, w_gate, w_down, offsets, activation)
    return _from_expert_order(ys, inv, order, flat_w, k)


@jax.named_scope("moe.dispatch")
def _moe_ragged_sharded(xt, top_phys, top_w, wu_f, wg_f, wd_f,
                        activation: str, moe: MoECfg,
                        ep_size: int, capacity: int, a2a, chunks: int = 1,
                        skip=None):
    """Dropless-style EP dispatch: sorted rows as the all-to-all payload,
    segment structure carried by a counts-exchange pre-pass.

    Rows are argsorted by global expert id (contiguous per-destination
    segments, experts contiguous per rank) and packed into a per-rank send
    buffer of S = E_l*C rows — the exact wire size of capacity mode — with
    the row budget aggregated per *rank* instead of per expert: since
    sum_e min(c_e, C) <= min(sum_e c_e, E_l*C), every token capacity mode
    keeps is kept here too (usually strictly more; the local path keeps
    all).

    **Counts exchange**: before the payload a2a, each rank ships its
    per-(destination, local-expert) *kept-row counts* — a tiny
    (ep, E_l) int32 all_to_all.  Because rows inside each source chunk
    arrive sorted by expert, those counts reconstruct the receiver-side
    expert ids exactly (``jnp.repeat`` with a static total), so the
    per-row id sideband the payload used to carry is no longer shipped.
    Fed to ``lax.ragged_all_to_all``, the same counts would also right-size
    the row payload itself; this path still ships the payload with the
    fixed-size ``all_to_all`` at the static capacity wire size, so the win
    is the id sideband + receiver-side segment metadata.  The
    second (tiny) collective is priced by
    ``resource_model.dispatch_costs`` as ``counts_bytes_per_layer``.

    Each receiver re-sorts the merged segments by local expert id
    (sentinel E_l marks empty slots, sorting them to the never-computed
    tail), runs the ragged grouped FFN over exactly the occupied rows, and
    returns results through the inverse permutations.
    """
    d = xt.shape[1]
    k = moe.top_k
    E = moe.num_experts
    E_l = E // ep_size
    flat_e = top_phys.reshape(-1)
    flat_w = top_w.reshape(-1)
    Tk = flat_e.shape[0]
    order, inv, _ = _sort_dispatch(flat_e, E)
    sorted_e = flat_e[order]
    xs = _to_expert_order(xt, order, inv, k)  # (Tk, d) expert-sorted

    S = E_l * capacity  # per-destination row budget == capacity wire size
    dest = sorted_e // E_l  # nondecreasing
    # Replica rows compute source-locally — they leave the wire entirely.
    # Positions are ranked among the VALID rows only so the kept rows pack
    # contiguously per destination (the counts-exchange reconstruction
    # requires [c_0 rows of expert 0, c_1 of expert 1, ...] with no holes).
    valid = (
        ~skip[order] if skip is not None
        else jnp.ones((Tk,), bool)
    )
    validi = valid.astype(jnp.int32)
    dcounts = jnp.zeros((ep_size,), jnp.int32).at[dest].add(validi)
    dstart = jnp.cumsum(dcounts) - dcounts
    pos = jnp.cumsum(validi) - 1 - dstart[dest]
    keep_s = valid & (pos < S)  # rank-budget overflow (sorted order)
    posd = jnp.where(keep_s, pos, S)  # out-of-range => scatter-dropped
    send_x = (
        jnp.zeros((ep_size, S, d), xt.dtype)
        .at[dest, posd].set(xs, mode="drop")
    )
    lid = (sorted_e - dest * E_l).astype(jnp.int32)
    # Kept rows per (destination rank, local expert): the counts-exchange
    # payload.  Only kept rows count — budget-dropped rows never hit the
    # wire, so the reconstruction must not include them.
    send_counts = (
        jnp.zeros((ep_size, E_l), jnp.int32)
        .at[dest, lid].add(keep_s.astype(jnp.int32))
    )

    # Counts exchange up front (one tiny collective for ALL chunks): it
    # carries the receiver-side segment structure, so every payload chunk's
    # per-row expert ids can be reconstructed before its rows arrive.
    recv_counts = lax.all_to_all(
        send_counts, "ep", split_axis=0, concat_axis=0, tiled=True
    ).reshape(ep_size, E_l)

    # Reconstruct the per-row expert ids of each received chunk from its
    # counts: chunk i is [c_i0 rows of expert 0, c_i1 of expert 1, ...,
    # sentinel padding] by construction (rows were packed in sorted order).
    ids_tmpl = jnp.arange(E_l + 1, dtype=jnp.int32)  # E_l = sentinel

    def chunk_ids(cnts):
        pad = jnp.maximum(S - jnp.sum(cnts), 0)
        reps = jnp.concatenate([cnts, pad[None]])
        return jnp.repeat(ids_tmpl, reps, total_repeat_length=S)

    recv_id = jax.vmap(chunk_ids)(recv_counts)  # (ep, S)

    def get_chunk(start, size):
        return send_x[:, start:start + size]

    def compute(recv, start, size):
        # Per-chunk receiver re-sort: slice the reconstructed ids to this
        # row range, argsort within the chunk (sentinels to the tail), run
        # the ragged grouped FFN over exactly the occupied rows, and
        # inverse-scatter back to wire order.  Each row's output depends
        # only on its own value and expert, so chunking is exact.
        rid = recv_id[:, start:start + size].reshape(ep_size * size)
        rx = recv.reshape(ep_size * size, d)
        order_c = jnp.argsort(rid)
        counts_c = jnp.zeros((E_l + 1,), jnp.int32).at[rid].add(1)
        offsets_c = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             jnp.cumsum(counts_c[:E_l]).astype(jnp.int32)]
        )
        xr = jnp.take(rx, order_c, axis=0)
        ys = _ragged_rows_ffn(xr, wu_f, wg_f, wd_f, offsets_c, activation)
        back = jnp.zeros((ep_size * size, d), ys.dtype).at[order_c].set(ys)
        return back.reshape(ep_size, size, d)

    outs = halo.overlapped_a2a(
        partial(_transport_bf16, a2a), get_chunk, compute,
        halo.chunk_slices(S, chunks),
    )
    y_buf = jnp.concatenate(outs, axis=1)  # (ep, S, d)
    vals = y_buf[dest, jnp.minimum(posd, S - 1)]
    vals = jnp.where(keep_s[:, None], vals, 0.0)
    return _from_expert_order(vals, inv, order, flat_w * keep_s[inv], k)


# A share's chunk of held rows: twice what its experts take under even
# routing.  Skewed routing reaches into further chunks (dropless).
HELD_SLACK = 2


def _held_chunk(T: int, k: int, E_l: int, E: int) -> int:
    """Rows of the first chunk of :func:`_held_rows`: HELD_SLACK x the rows
    that E_l of E experts take under even routing, in 128-row tiles, at
    most the T x min(k, E_l) rows they can take at all."""
    even = -(-T * k * E_l // E)
    return min(T * min(k, E_l), -(-HELD_SLACK * even // 128) * 128)


def _further_chunks(total: int, rows: int) -> List[int]:
    """Rows of the further chunks, up to ``total``: an eighth of the first
    chunk's (in 128-row tiles), doubling up to the first's, so that a step
    whose held rows just pass the first chunk runs only a small one."""
    sizes, size, left = [], max(128, rows // 8 // 128 * 128), total - rows
    while left > 0:
        sizes.append(min(size, left))
        left -= sizes[-1]
        size = min(2 * size, rows)
    return sizes


@jax.named_scope("moe.dispatch")
def _held_rows(xt, top_phys, top_w, wu_f, wg_f, wd_f, activation: str,
               k: int, first: int, E_l: int, E: int, skip=None):
    """What experts [first, first + E_l) add to each token — one rank's
    local experts, or one chip's share of a layer: (T, d) float32, the
    weighted sum over the (token, k) rows routed to them.

    The rows are sorted by held expert, rows routed elsewhere to a tail.
    Only held rows are gathered, in chunks: the first, of
    :func:`_held_chunk` rows, always runs; each further one
    (:func:`_further_chunks`) only where the held rows reach into it,
    rematerialised so that a chunk not run keeps nothing for the backward
    pass.  A chunk's rows past the held ones are zero padding, so a step's
    work does not follow the routing unless it reaches a further chunk.  A
    chunk's outputs are weighted and added into their tokens, whose
    backward is a gather of the chunk's rows."""
    T, d = xt.shape
    flat_e = top_phys.reshape(-1)
    lid = flat_e - first
    local = (lid >= 0) & (lid < E_l)
    if skip is not None:
        local = local & ~skip  # replica rows: handled by the replica path
    lid = jnp.where(local, lid, E_l).astype(jnp.int32)  # sentinel tail
    order = jnp.argsort(lid)  # stable: held rows first, by expert
    counts = jnp.zeros((E_l + 1,), jnp.int32).at[lid].add(1)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(counts[:E_l]).astype(jnp.int32)]
    )
    total = T * min(k, E_l)
    rows = _held_chunk(T, k, E_l, E)
    tok = order[:total] // k
    w = jnp.take(top_w.reshape(-1), order[:total])

    def chunk(xt, wu, wg, wd, tok_c, w_c, off_c):
        # Rows past off_c[E_l] (routed elsewhere) are padding: zero rows
        # that the last held expert takes, so that every grid step does a
        # held row's work and a chunk costs the same whatever the routing.
        size = tok_c.shape[0]
        pad = jnp.arange(size) >= off_c[E_l]
        xs = jnp.where(pad[:, None], 0, jnp.take(xt, tok_c, axis=0))
        w_c = jnp.where(pad, 0, w_c)
        ys = _ragged_rows_ffn(xs, wu, wg, wd, off_c.at[E_l].set(size),
                              activation)
        with jax.named_scope("moe.combine"):
            return jnp.zeros((T, d), jnp.float32).at[tok_c].add(
                ys.astype(jnp.float32) * w_c[:, None])

    def operands(s, size):
        return (xt, wu_f, wg_f, wd_f, tok[s:s + size], w[s:s + size],
                jnp.clip(offsets - s, 0, size))

    y = chunk(*operands(0, rows))
    more = jax.checkpoint(chunk)
    s = rows
    for size in _further_chunks(total, rows):
        y = y + lax.cond(offsets[E_l] > s, more,
                         lambda *a: jnp.zeros((T, d), jnp.float32),
                         *operands(s, size))
        s += size
    return y


# -- hot-expert replication (migration planner escape hatch) ----------------
#
# A replicated expert's rows never hit the a2a wire: every EP rank
# materializes the replica channels' weights (owner-masked select from its
# ZeRO-gathered shard + psum over "ep" — the psum of a single nonzero
# contribution is exact) and computes its OWN tokens' replica rows locally,
# so the hot expert's load splits across groups by token origin.  The
# weights stay ONE logical param leaf: the psum/gather transposes sum every
# rank's replica grads back into it automatically.  Replication is
# function-preserving — paths that ignore the table (local / pipeline
# interior) remain exact.


@jax.named_scope("moe.dispatch")
def _replica_rows(top_i, replicas, E: int):
    """Per flat (token, k) row: routed-to-a-replica mask and the replica
    channel id (sentinel R for non-replica rows).  ``replicas``: (R,)
    logical expert ids, sentinel E = free channel."""
    R = replicas.shape[0]
    # Size-(E+1) tables so the sentinel E lands on a discarded row.
    is_rep = (
        jnp.zeros((E + 1,), bool).at[replicas].set(True, mode="drop")[:E]
    )
    chan = (
        jnp.full((E + 1,), R, jnp.int32)
        .at[replicas].set(jnp.arange(R, dtype=jnp.int32), mode="drop")[:E]
    )
    flat_i = top_i.reshape(-1)
    rep_row = is_rep[flat_i]
    rchan = jnp.where(rep_row, chan[flat_i], R)
    return rep_row, rchan.astype(jnp.int32)


@jax.named_scope("moe.experts")
def _replica_weights(replicas, assignment, wu_f, wg_f, wd_f, E: int,
                     E_l: int, ep_size: int):
    """Materialize the R replica channels' expert weights on every EP rank.

    Each active channel's weights live in exactly one rank's gathered
    shard (its home physical slot under ``assignment``); an owner-masked
    select + psum("ep") broadcasts them.  AD: the psum transposes to a
    psum of the per-rank replica-weight cotangents, masked back onto the
    owner's shard row — replica grads sum into the one logical leaf.
    """
    R = replicas.shape[0]
    active = replicas < E
    slot = assignment[jnp.clip(replicas, 0, E - 1)]
    g = lax.axis_index("ep") if ep_size > 1 else 0
    owner = slot // E_l
    lrow = slot - owner * E_l
    mine = active & (owner == g)

    def bcast(w):
        sel = jnp.where(mine[:, None, None], w[lrow], jnp.zeros_like(w[lrow]))
        return lax.psum(sel, "ep") if ep_size > 1 else sel

    wu_r = bcast(wu_f)
    wg_r = bcast(wg_f) if wg_f is not None else None
    wd_r = bcast(wd_f)
    return wu_r, wg_r, wd_r


@jax.named_scope("moe.experts")
def _replica_ffn(xt, rchan, top_k: int, wu_r, wg_r, wd_r, R: int,
                 activation: str, wire_bf16: bool):
    """Ragged FFN over the (token, k) rows routed to replica channels.

    Rows carrying the sentinel R sort to the never-computed tail and come
    back zero.  ``wire_bf16`` mirrors ``_transport_bf16``'s double cast so
    replica-local rows match bit-for-bit what the a2a path would have
    computed for them (token-sharded paths only; decode has no wire cast).
    Returns (Tk, d) with zeros in non-replica rows.
    """
    order = jnp.argsort(rchan)
    counts = jnp.zeros((R + 1,), jnp.int32).at[rchan].add(1)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts[:R]).astype(jnp.int32)]
    )
    xs = jnp.take(xt, order // top_k, axis=0)
    if wire_bf16:
        xs = xs.astype(jnp.bfloat16).astype(xt.dtype)
    ys = _ragged_rows_ffn(xs, wu_r, wg_r, wd_r, offsets, activation)
    if wire_bf16:
        ys = ys.astype(jnp.bfloat16).astype(xt.dtype)
    return jnp.zeros((rchan.shape[0], xt.shape[1]), ys.dtype).at[order].set(ys)


@jax.named_scope("moe.exchange")
def _transport_bf16(a2a_fn, x):
    """Run a dispatch/combine collective with a bf16 payload in BOTH
    directions: the forward cast makes the wire payload bf16, and because
    the transpose of `astype` restores the cast, the backward cotangent
    crosses the wire in bf16 too (measured 2x a2a wire on granite —
    EXPERIMENTS.md §Perf)."""
    orig = x.dtype
    y = a2a_fn(x.astype(jnp.bfloat16))
    y = _checkpoint_name(y, "ep_a2a")
    return y.astype(orig)


def _select_a2a(plan: MeshPlan):
    """The ONE place the EP dispatch/combine collective is selected
    (flat vs HALO hierarchical): both the capacity-path and ragged-path
    transports call through here, so ``plan.hierarchical_a2a`` /
    ``plan.a2a_chunks`` cannot half-apply.  Returns the per-chunk
    collective; chunking itself is driven by ``halo.overlapped_a2a``."""
    if plan.hierarchical_a2a:
        return lambda t: halo.hierarchical_all_to_all(t, plan)
    return halo.flat_all_to_all


@jax.named_scope("moe.dispatch")
def _moe_capacity_sharded(buf, wu_f, wg_f, wd_f, activation: str,
                          ep_size: int, E_l: int, capacity: int, d: int,
                          a2a, chunks: int):
    """Capacity-mode EP dispatch -> grouped FFN -> combine, chunked along
    the capacity dim and software-pipelined: chunk k+1's dispatch a2a is
    issued while chunk k's expert GEMM runs (halo.overlapped_a2a), and each
    chunk's combine a2a overlaps the next chunk's compute.  Every chunk is
    a valid per-expert slot range, so per-row results are identical to the
    monolithic transfer (chunks=1 degenerates to exactly it)."""
    bufe = buf.reshape(ep_size, E_l, capacity, d)

    def get_chunk(start, size):
        return bufe[:, :, start:start + size].reshape(ep_size, E_l * size, d)

    def compute(recv, start, size):
        # recv[(i, e, c)] = source i's slot chunk for my expert e.
        expert_in = (
            recv.reshape(ep_size, E_l, size, d)
            .transpose(1, 0, 2, 3)
            .reshape(E_l, ep_size * size, d)
        )
        expert_out = _expert_ffn(expert_in, wu_f, wg_f, wd_f, activation)
        return (
            expert_out.reshape(E_l, ep_size, size, d)
            .transpose(1, 0, 2, 3)
            .reshape(ep_size, E_l * size, d)
        )

    slices = halo.chunk_slices(capacity, chunks)
    outs = halo.overlapped_a2a(
        partial(_transport_bf16, a2a), get_chunk, compute, slices
    )
    y = jnp.concatenate(
        [o.reshape(ep_size, E_l, sz, d) for o, (_, sz) in zip(outs, slices)],
        axis=2,
    )
    return y.reshape(ep_size * E_l, capacity, d)


def moe_ffn_local(
    params: Dict[str, jax.Array],
    x: jax.Array,  # (b, s, d) — the caller's full (replicated) token block
    arch: ArchConfig,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Collective-free single-rank MoE: the exact routing/capacity/expert
    math of :func:`moe_ffn`'s body with EP = 1 and no mesh — the reference
    the sharded paths are tested against.
    """
    moe = arch.moe
    assert moe is not None
    E = moe.num_experts
    b, s, d = x.shape
    T = b * s
    xt = x.reshape(T, d)
    top_w, top_i, probs, logits = _route(xt, params["w_router"], moe,
                                         _router_bias(params, moe))
    aux, z, counts = _aux_losses(probs, logits, top_i, moe, (), rows=b)
    with jax.named_scope("moe.router"):
        top_phys = params["assignment"][top_i]
    wg = params.get("w_gate")
    if moe.ep_share > 1:
        y = _held_rows(
            xt, top_phys, top_w, params["w_up"], wg, params["w_down"],
            arch.ffn_activation, moe.top_k, moe.first_held, moe.experts_held,
            E,
        ).astype(x.dtype)
    elif moe.dispatch == "ragged":
        y = _moe_ragged_local(
            xt, top_phys, top_w, params["w_up"], wg, params["w_down"],
            arch.ffn_activation, E, moe.top_k,
        )
    else:
        capacity = _capacity(T, moe)
        flat_e, pos, keep, flat_w = _dispatch_indices(
            top_phys, top_w, E, capacity
        )
        buf = _scatter_to_buffers(xt, flat_e, pos, keep, E, capacity)
        y_buf = _expert_ffn(
            buf, params["w_up"], wg, params["w_down"], arch.ffn_activation
        )
        vals = y_buf[flat_e, pos]
        y = _combine_expert_outputs(vals, flat_w, keep, T, moe.top_k, d)
    y = y.reshape(b, s, d)
    if moe.num_shared_experts:
        y = y + _shared_experts(params, x, arch)
    metrics = {"moe_aux_loss": aux, "moe_z_loss": z, "expert_load": counts}
    return y, metrics


def _router_bias(params, moe: MoECfg) -> Optional[jax.Array]:
    """The selection bias (E,) float32, or None without one."""
    if "router_bias" not in params:
        return None
    with jax.named_scope("moe.router"):
        return params["router_bias"].astype(jnp.float32) * moe.bias_update_speed


@jax.named_scope("moe.shared")
def _shared_experts(params, x, arch: ArchConfig):
    """The always-active shared experts: one dense FFN over all tokens.
    On a share every chip computes them alike."""
    from repro.models import layers

    return layers.dense_ffn(
        {
            "w_up": params["w_shared_up"],
            "w_gate": params.get("w_shared_gate"),
            "w_down": params["w_shared_down"],
        },
        x,
        arch.ffn_activation,
    )


def moe_ffn(
    params: Dict[str, jax.Array],
    x: jax.Array,  # (b, s, d) global view
    arch: ArchConfig,
    plan: MeshPlan,
    *,
    token_sharded: bool = True,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """MoE FFN sub-layer (global view; explicit shard_map inside).

    token_sharded=True: train/prefill — x sharded (dp, sp, None), dispatch
    via all_to_all over the "ep" axis.
    token_sharded=False: decode — x sharded (dp, None, None); tokens are
    replicated across the ep/tp axes, each ep rank computes its local
    experts, outputs combine via psum("ep") (weight-parallel decode).
    """
    moe = arch.moe
    assert moe is not None
    mesh = plan.mesh
    ep_size = plan.ep
    # A share stands for the other chips of its layer; it runs no exchange.
    assert ep_size == 1 or moe.ep_share == 1, "a share runs without EP"
    E = moe.num_experts
    E_l = moe.experts_held // ep_size
    axes = _all_axes(plan)

    import numpy as _np

    dp_div = int(_np.prod([mesh.shape[a] for a in plan.dp_axes])) if plan.dp_axes else 1
    dp_spec = (
        tuple(plan.dp_axes)
        if plan.dp_axes and dp_div > 1 and x.shape[0] % dp_div == 0
        else None
    )
    sp_spec = tuple(plan.sp_axes)
    x_spec = P(dp_spec, sp_spec, None) if token_sharded else P(dp_spec, None, None)

    wr_spec = P(None, None)
    wu_spec = P("ep", None, ("data", "tp"))
    wd_spec = P("ep", ("data", "tp"), None)

    # In the decode path tokens are replicated over ep/tp — mean metrics
    # over the axes the batch dim is ACTUALLY sharded on.  ``dp_spec`` is
    # None when the batch does not divide the dp axes (e.g. batch-1
    # long-context decode): the tokens are then fully replicated, and
    # psumming over plan.dp_axes anyway multiplies counts and token totals
    # by the replica count — the ep>1 x dp>1 double-count bug the decode
    # tests pin (metrics must be invariant to the mesh factoring).
    metric_axes = axes if token_sharded else (dp_spec or ())

    def body(wr, wu, wg, wd, assignment, replicas, bias, xl):
        b_l, s_l, d = xl.shape
        T = b_l * s_l
        # The tokens as rows for the router and the dispatch.  Its backward
        # closes the fusion that the dispatch's k-sum joins, which a trace
        # then reads under moe.dispatch.
        with jax.named_scope("moe.dispatch"):
            xt = xl.reshape(T, d)
        top_w, top_i, probs, logits = _route(
            xt, wr, moe, bias if bias.shape[0] else None)
        # Metrics/aux use LOGICAL expert ids; dispatch uses PHYSICAL slots
        # via the migration routing table.
        aux, z, counts = _aux_losses(
            probs, logits, top_i, moe, metric_axes, rows=b_l,
            sp_axes=sp_spec if token_sharded else ())
        with jax.named_scope("moe.router"):
            top_phys = assignment[top_i]

        capacity = _capacity(T, moe)

        # Gather ZeRO-3-sharded expert weights (transpose = reduce-scatter).
        gather_axes = ("data", "tp") if "data" in axes else ("tp",)
        with jax.named_scope("moe.experts"):
            wu_f = lax.all_gather(wu, gather_axes, axis=2, tiled=True)
            wg_f = (
                lax.all_gather(wg, gather_axes, axis=2, tiled=True)
                if wg is not None
                else None
            )
            wd_f = lax.all_gather(wd, gather_axes, axis=1, tiled=True)

        # Flat/halo/chunked selection lives in _select_a2a + the plan's
        # a2a_chunks — shared by the capacity and ragged transports.
        a2a = _select_a2a(plan)
        chunks = max(int(getattr(plan, "a2a_chunks", 1) or 1), 1)

        # Hot-expert replication: replica rows leave the main dispatch and
        # compute source-locally.  Only meaningful under EP — with one
        # group there is nothing to split, so the table is ignored.
        R = replicas.shape[0]
        have_rep = R > 0 and ep_size > 1
        rep_row = None
        y_rep = None
        if have_rep:
            rep_row, rchan = _replica_rows(top_i, replicas, E)
            wu_r, wg_r, wd_r = _replica_weights(
                replicas, assignment, wu_f, wg_f, wd_f, E, E_l, ep_size
            )
            if token_sharded:
                vals_rep = _replica_ffn(
                    xt, rchan, moe.top_k, wu_r, wg_r, wd_r, R,
                    arch.ffn_activation, wire_bf16=True,
                )
            else:
                # Decode: tokens are replicated over "ep" — round-robin row
                # ownership so each row is computed exactly once, then psum.
                g = lax.axis_index("ep")
                own = (
                    jnp.arange(rchan.shape[0], dtype=jnp.int32) % ep_size
                ) == g
                rchan_own = jnp.where(own, rchan, R)
                vals_rep = _replica_ffn(
                    xt, rchan_own, moe.top_k, wu_r, wg_r, wd_r, R,
                    arch.ffn_activation, wire_bf16=False,
                )
                vals_rep = lax.psum(vals_rep, "ep")
            # Disjoint supports (rep_row vs keep) make the two combines an
            # exact split of the oracle's single combine.
            y_rep = _combine_expert_outputs(
                vals_rep, top_w.reshape(-1), rep_row, T, moe.top_k, d
            )

        if moe.dispatch == "ragged":
            # Sort-based dropless dispatch.  Train/prefill (token-sharded):
            # with EP the a2a payload is the sorted rows + a counts-exchange
            # pre-pass (rank-level row budget, capacity wire size); without
            # EP the whole block is processed ragged.  Decode (replicated
            # tokens): each rank sorts locally by its own expert ids and
            # partial outputs combine via psum("ep") — no capacity buffers.
            if not token_sharded or moe.ep_share > 1:
                # Each rank (or the share) computes its own experts' part.
                g = lax.axis_index("ep") if ep_size > 1 else 0
                y = _held_rows(
                    xt, top_phys, top_w, wu_f, wg_f, wd_f,
                    arch.ffn_activation, moe.top_k, moe.first_held + g * E_l,
                    E_l, E, skip=rep_row,
                )
                if ep_size > 1:
                    y = lax.psum(y, "ep")
                y = y.astype(xt.dtype)
            elif ep_size > 1:
                y = _moe_ragged_sharded(
                    xt, top_phys, top_w, wu_f, wg_f, wd_f,
                    arch.ffn_activation, moe, ep_size, capacity, a2a,
                    chunks, skip=rep_row,
                )
            else:
                y = _moe_ragged_local(
                    xt, top_phys, top_w, wu_f, wg_f, wd_f,
                    arch.ffn_activation, E, moe.top_k,
                )
            if y_rep is not None:
                y = y + y_rep
            y = y.reshape(b_l, s_l, d)
            metrics = {
                "moe_aux_loss": aux,
                "moe_z_loss": z,
                "expert_load": counts,
            }
            return y, metrics

        # Capacity dispatch (decode default: replicated tokens +
        # psum("ep") combine over the static per-expert slot layout).
        flat_e, pos, keep, flat_w = _dispatch_indices(top_phys, top_w, E, capacity)
        if rep_row is not None:
            # Replica rows leave the buffers (slots stay consumed, so the
            # surviving rows' positions match the unreplicated run).
            keep = keep & ~rep_row
        buf = _scatter_to_buffers(xt, flat_e, pos, keep, E, capacity)

        if token_sharded and ep_size > 1:
            y_buf = _moe_capacity_sharded(
                buf, wu_f, wg_f, wd_f, arch.ffn_activation,
                ep_size, E_l, capacity, d, a2a, chunks,
            )
            vals = y_buf[flat_e, pos]
        else:
            # Decode / EP-disabled: compute only the local expert shard and
            # psum partial outputs over "ep".
            g = lax.axis_index("ep") if ep_size > 1 else 0
            local = lax.dynamic_slice_in_dim(buf, g * E_l, E_l, axis=0)
            expert_out = _expert_ffn(local, wu_f, wg_f, wd_f,
                                     arch.ffn_activation)
            y_local = jnp.zeros((E, capacity, d), expert_out.dtype)
            y_local = lax.dynamic_update_slice_in_dim(
                y_local, expert_out, g * E_l, axis=0
            )
            vals = y_local[flat_e, pos]
            if ep_size > 1:
                vals = lax.psum(vals, "ep")

        y = _combine_expert_outputs(vals, flat_w, keep, T, moe.top_k, d)
        if y_rep is not None:
            y = y + y_rep
        y = y.reshape(b_l, s_l, d)
        metrics = {
            "moe_aux_loss": aux,
            "moe_z_loss": z,
            "expert_load": counts,
        }
        return y, metrics

    wg = params.get("w_gate")
    replicas = params.get("replicas")
    if replicas is None:
        replicas = jnp.zeros((0,), jnp.int32)
    bias = _router_bias(params, moe)
    if bias is None:
        bias = jnp.zeros((0,), jnp.float32)
    in_specs = (
        wr_spec,
        wu_spec,
        wu_spec if wg is not None else P(),
        wd_spec,
        P(None),
        P(None),
        P(None),
        x_spec,
    )
    out_specs = (x_spec, {"moe_aux_loss": P(), "moe_z_loss": P(), "expert_load": P()})

    def wrapped(wr, wu, wg_, wd, assignment, replicas_, bias_, xl):
        return body(
            wr, wu, wg_ if wg is not None else None, wd, assignment,
            replicas_, bias_, xl,
        )

    # Manual over every non-pipeline axis.  When nested inside the pipeline
    # executor's shard_map (manual over pp_axis), the context mesh must be
    # used — passing the concrete mesh would conflict with the outer manual
    # axis types.
    manual = set(a for a in mesh.axis_names if a != plan.pp_axis)
    have_ctx = len(jax.sharding.get_abstract_mesh().axis_names) > 0
    mesh_kw = {} if have_ctx else {"mesh": mesh}

    y, metrics = jax.shard_map(
        wrapped,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
        axis_names=manual,
        **mesh_kw,
    )(
        params["w_router"],
        params["w_up"],
        wg if wg is not None else jnp.zeros((), x.dtype),
        params["w_down"],
        params["assignment"],
        replicas,
        bias,
        x,
    )

    if moe.num_shared_experts:
        y = y + _shared_experts(params, x, arch)
    return y, metrics
