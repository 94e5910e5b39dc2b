"""Grouped per-expert GEMM Pallas TPU kernels: padded (capacity) + ragged.

Computes ``out[e] = x[e] @ w[e]`` for E experts in one launch.  This is the
paper's skinny-GEMM hot spot (§II-A, Fig 4): fine-grained experts make both
M (tokens-per-expert) and N (= d_ffn/TP) small, so a naive per-expert loop
starves the MXU.  The padded kernel:

* tiles (M, N, K) into (128, 128, 512) blocks, clamped to divisors of the
  actual dims so tiny experts still launch well-formed blocks;
* walks the grid (E, M/bm, N/bn, K/bk) with K innermost so each output tile
  is revisited across K steps and accumulated in float32 (bf16 inputs,
  fp32 accumulation — MXU-native).

The **ragged** kernels are the dropless (MegaBlocks-style) path: the input
is one (T, K) matrix of token rows *sorted by expert*, plus a per-expert
prefix-sum ``offsets`` (E+1,).  A work-item list maps each grid step to the
(row-tile, expert) pairs that actually contain tokens, delivered to the
index maps through scalar prefetch, so only occupied tiles are launched —
an expert with c_e rows costs ceil(c_e/bm) tiles instead of a fixed
capacity C.  Tiles straddling an expert boundary are visited once per
overlapping expert with the out-of-range rows masked (blend-store), which
is what bounds the padding waste at < bm rows per expert instead of
``C - c_e`` rows per expert.

Their blocks come from the shapes (:func:`ragged_tiles`), so that a call
reads each operand from HBM about once:

* ``bk`` = the whole K and ``bn`` = the whole N where the blocks fit the
  VMEM budget.  The grid then has one step per work item; consecutive
  items of one expert keep the weight block's index, so Pallas skips its
  copy and each expert's weights are read once per run of its row tiles,
  and each token tile is read once, not once per output strip.  Where the
  double-buffered blocks and the kernel's temporaries exceed the budget,
  ``bn`` shrinks first, then ``bk``, through the block sizes Mosaic
  accepts (the whole dim, or a divisor that is a multiple of 128).
* ``bm`` (:func:`row_tile`): the largest of 512, 256 and 128 rows whose
  at most E straddling revisits and surplus items stay within an eighth of
  the T/bm row tiles.
* Each launch sets ``vmem_limit_bytes`` from its blocks' estimate with
  headroom, at most ``VMEM_CAP`` (sized for v5e's 128 MiB of VMEM); the
  default scoped limit would refuse whole-K/N blocks at Moonlight's widths.

Every program-id-derived value is hoisted out of the ``pl.when`` bodies
below, which keeps the bodies free of grid queries in both the Mosaic and
the interpret-mode lowering.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(x_ref, w_ref, o_ref, *, k_steps: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[0, ...] += jnp.dot(
        x_ref[0], w_ref[0], preferred_element_type=jnp.float32
    )


def _block(dim: int, preferred: int) -> int:
    """Largest divisor of ``dim`` that is <= preferred (MXU-aligned whenever
    the dim allows it), or the whole dim where that divisor is no multiple
    of the 128 lanes (Mosaic tiles a block's minor dims by 128 unless the
    block spans the array: Moonlight's d_ff of 1408 = 11 x 128)."""
    b = min(dim, preferred)
    while dim % b:
        b -= 1
    return b if b % 128 == 0 else dim


# VMEM of one TPU v5e core is 128 MiB.  The picker keeps a ragged kernel's
# estimate within VMEM_BUDGET; its limit adds headroom up to VMEM_CAP.
VMEM_BUDGET = 80 * 2**20
VMEM_CAP = 100 * 2**20


class Tiles(NamedTuple):
    bm: int
    bn: int
    bk: int
    vmem_bytes: int  # the estimate of the blocks and temporaries
    vmem_limit_bytes: int
    grid_steps: int


def row_tile(T: int, E: int) -> int:
    """Rows of a ragged kernel's tile: the largest of 512, 256 and 128 for
    which the at most E straddling revisits and surplus work items stay
    within an eighth of the T/bm row tiles, at most T rounded up to the
    16-row sublane tile (an unaligned second-to-minor block dim would not
    lower under Mosaic; 16 covers fp32 and bf16)."""
    bm = next((b for b in (512, 256) if 8 * E * b <= T), 128)
    return min(bm, max(-(-T // 16) * 16, 16))


def _block_options(dim: int):
    """The blocks Mosaic accepts for ``dim``, largest first: the whole dim,
    then its divisors that are multiples of 128."""
    return [dim] + [b for b in range(128 * ((dim - 1) // 128), 127, -128)
                    if dim % b == 0]


def _vmem_bytes(kernel: str, bm: int, bn: int, bk: int, a_dtype,
                b_dtype) -> int:
    """VMEM a ragged launch needs: its double-buffered blocks, its scratch
    and the kernel body's fp32 temporaries.  ``a_dtype`` is the row
    operand's; ``b_dtype`` the weights' (the cotangent rows' for dw)."""
    a, b = jnp.dtype(a_dtype).itemsize, jnp.dtype(b_dtype).itemsize
    tile = bm * bn * 4  # one fp32 (bm, bn) tile
    if kernel == "ragged_dw_f32":
        blocks = bm * bk * a + bm * bn * b + bk * bn * 4
        # Both operands cast to fp32, and the (bk, bn) product.
        return 2 * blocks + bm * bk * 4 + tile + bk * bn * 4
    w = 2 if kernel == "ragged_gate_up_silu_f32" else 1
    blocks = bm * bk * a + w * bk * bn * b + (2 * w - 1) * tile
    cast = bk * bn * 4 * w if a != b else 0  # weights cast to fp32
    # w accumulators, then w products and the blend-store's value.
    return 2 * blocks + w * tile + (w + 1) * tile + cast


def ragged_tiles(kernel: str, T: int, E: int, K: int, N: int, a_dtype,
                 b_dtype, *, bm=None, bn=None, bk=None,
                 budget: int = VMEM_BUDGET) -> Tiles:
    """Blocks of one ragged launch, from its shapes: ``kernel`` is
    ``ragged_gate_up_silu_f32``, ``ragged_matmul_f32`` or ``ragged_dw_f32``
    over T rows of E experts, contracting K into N outputs (dw: rows of
    (T, K) against (T, N) into (E, K, N)).  A block given is taken as
    ``_block`` makes it; one left None is picked (module docstring)."""
    bm = row_tile(T, E) if bm is None else bm
    bks = _block_options(K) if bk is None else [_block(K, bk)]
    bns = _block_options(N) if bn is None else [_block(N, bn)]
    for k in bks:
        for n in bns:
            est = _vmem_bytes(kernel, bm, n, k, a_dtype, b_dtype)
            if est <= budget:
                limit = min(VMEM_CAP, est + est // 4 + 4 * 2**20)
                steps = (K // k) * (N // n) * (-(-T // bm) + E)
                return Tiles(bm, n, k, est, limit, steps)
    raise ValueError(f"{kernel}: no blocks of ({bm}, {N}, {K}) fit "
                     f"{budget} bytes of VMEM")


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def grouped_matmul_f32(
    x: jax.Array,  # (E, M, K)
    w: jax.Array,  # (E, K, N)
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Float32-accumulated grouped matmul; cast at the call site."""
    E, M, K = x.shape
    E2, K2, N = w.shape
    assert E == E2 and K == K2, (x.shape, w.shape)

    bm = _block(M, bm)
    bn = _block(N, bn)
    bk = _block(K, bk)
    k_steps = K // bk
    grid = (E, M // bm, N // bn, k_steps)

    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda e, m, n, k: (e, m, k)),
            pl.BlockSpec((1, bk, bn), lambda e, m, n, k: (e, k, n)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda e, m, n, k: (e, m, n)),
        out_shape=jax.ShapeDtypeStruct((E, M, N), jnp.float32),
        interpret=interpret,
    )(x, w)


# ---------------------------------------------------------------------------
# Ragged (dropless) grouped GEMM
# ---------------------------------------------------------------------------
#
# Work-item list: expert e with rows [offsets[e], offsets[e+1]) overlaps
# row-tiles floor(offsets[e]/bm) .. ceil(offsets[e+1]/bm)-1.  The total
# number of (tile, expert) work items is at most ceil(T/bm) + E (each expert
# boundary adds at most one straddling revisit), which is the static grid
# bound; surplus grid steps repeat the last valid item with an all-false row
# mask so they are harmless no-ops.


def num_work_items(T_pad: int, bm: int, E: int) -> int:
    """Static work-item bound for a (T_pad, bm, E) ragged launch."""
    return T_pad // bm + E


def ragged_metadata(offsets: jax.Array, bm: int, E: int, G: int):
    """Work-item tables for the ragged kernels.

    offsets: (E+1,) int32 row prefix sums (offsets[E] = occupied rows).
    Returns int32 arrays of length G: ``tile_m`` (row-tile index),
    ``grp`` (expert id), ``valid`` (1 for real work items), ``is_first``
    (1 on the first work item of each expert — tgmm accumulator init).
    """
    o = offsets.astype(jnp.int32)
    counts = o[1:] - o[:-1]
    first = o[:-1] // bm
    last = jnp.where(counts > 0, (o[1:] - 1) // bm, first - 1)
    ntiles = jnp.maximum(last - first + 1, 0)
    seg_end = jnp.cumsum(ntiles)
    seg_start = seg_end - ntiles
    nvalid = seg_end[-1]
    g = jnp.arange(G, dtype=jnp.int32)
    valid = (g < nvalid).astype(jnp.int32)
    # Clamp surplus items onto the last valid one: their masks are forced
    # all-false via `valid`, but every ref index stays in range.
    gg = jnp.minimum(g, jnp.maximum(nvalid - 1, 0))
    grp = jnp.searchsorted(seg_end, gg, side="right").astype(jnp.int32)
    grp = jnp.minimum(grp, E - 1)
    tile_m = (first[grp] + (gg - seg_start[grp])).astype(jnp.int32)
    tile_m = jnp.clip(tile_m, 0, None)
    prev = jnp.concatenate([jnp.array([-1], jnp.int32), grp[:-1]])
    is_first = ((grp != prev) & (valid == 1)).astype(jnp.int32)
    return tile_m, grp, valid, is_first


def _row_mask(tile_m, grp, valid, offs, g, bm):
    """(bm, 1) bool: rows of work item g that belong to its expert."""
    e = grp[g]
    rows = tile_m[g] * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    return (rows >= offs[e]) & (rows < offs[e + 1]) & (valid[g] == 1)


def _ragged_mm_kernel(tile_m, grp, valid, offs, x_ref, w_ref, o_ref, acc,
                      *, bm: int, k_steps: int):
    k = pl.program_id(2)
    mask = _row_mask(tile_m, grp, valid, offs, pl.program_id(1), bm)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(x_ref[...], w_ref[0],
                        preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _store():
        # Blend-store: straddling tiles are visited once per expert; each
        # visit owns a disjoint row range of the tile.  The work-item axis
        # runs INSIDE the n axis so every revisit of an output block is
        # grid-consecutive — the block stays resident in VMEM between the
        # visits, which is the only revisit pattern Pallas TPU guarantees
        # (non-consecutive revisits would read an unreloaded window).
        o_ref[...] = jnp.where(mask, acc[...], o_ref[...])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def ragged_matmul_f32(
    x: jax.Array,  # (T, K) rows sorted by expert; T % bm == 0
    w: jax.Array,  # (E, K, N)
    offsets: jax.Array,  # (E+1,) int32; offsets[E] <= T
    *,
    bm: int = 128,
    bn=None,
    bk=None,
    interpret: bool = False,
) -> jax.Array:
    """out[t] = x[t] @ w[expert_of(t)] for the occupied rows t <
    offsets[E]; rows beyond are zeroed.  fp32 accumulation.  Blocks left
    None are picked by :func:`ragged_tiles`."""
    T, K = x.shape
    E, K2, N = w.shape
    assert K == K2 and T % bm == 0, (x.shape, w.shape, bm)
    t = ragged_tiles("ragged_matmul_f32", T, E, K, N, x.dtype, w.dtype,
                     bm=bm, bn=bn, bk=bk)
    bn, bk = t.bn, t.bk
    k_steps = K // bk
    G = num_work_items(T, bm, E)
    tile_m, grp, valid, _ = ragged_metadata(offsets, bm, E, G)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(N // bn, G, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda n, g, k, tm, gr, vl, of: (tm[g], k)),
            pl.BlockSpec(
                (1, bk, bn), lambda n, g, k, tm, gr, vl, of: (gr[g], k, n)
            ),
        ],
        out_specs=pl.BlockSpec(
            (bm, bn), lambda n, g, k, tm, gr, vl, of: (tm[g], n)
        ),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_mm_kernel, bm=bm, k_steps=k_steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=t.vmem_limit_bytes),
        interpret=interpret,
    )(tile_m, grp, valid, offsets.astype(jnp.int32), x, w)
    # Rows no expert owns (padding tail) are uninitialized VMEM — zero them
    # so downstream elementwise math is deterministic and NaN-free.
    rows = jnp.arange(T, dtype=jnp.int32)[:, None]
    return jnp.where(rows < offsets[-1], out, 0.0)


def _ragged_gate_up_kernel(tile_m, grp, valid, offs, x_ref, wg_ref, wu_ref,
                           h_ref, ag_ref, au_ref, accg, accu,
                           *, bm: int, k_steps: int):
    k = pl.program_id(2)
    mask = _row_mask(tile_m, grp, valid, offs, pl.program_id(1), bm)

    @pl.when(k == 0)
    def _init():
        accg[...] = jnp.zeros_like(accg)
        accu[...] = jnp.zeros_like(accu)

    xb = x_ref[...]
    accg[...] += jnp.dot(xb, wg_ref[0], preferred_element_type=jnp.float32)
    accu[...] += jnp.dot(xb, wu_ref[0], preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _store():
        # Blend-store; work items run inside the n axis so output-block
        # revisits are grid-consecutive (see _ragged_mm_kernel).
        g_act = accg[...]
        u = accu[...]
        h = jax.nn.silu(g_act) * u
        h_ref[...] = jnp.where(mask, h, h_ref[...])
        ag_ref[...] = jnp.where(mask, g_act, ag_ref[...])
        au_ref[...] = jnp.where(mask, u, au_ref[...])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def ragged_gate_up_silu_f32(
    x: jax.Array,  # (T, K) sorted rows; T % bm == 0
    w_gate: jax.Array,  # (E, K, F)
    w_up: jax.Array,  # (E, K, F)
    offsets: jax.Array,  # (E+1,)
    *,
    bm: int = 128,
    bn=None,
    bk=None,
    interpret: bool = False,
):
    """Fused ragged gate·up·SiLU: one launch computes h = silu(x@wg)·(x@wu)
    and also emits the fp32 pre-activations (custom-VJP residuals)."""
    T, K = x.shape
    E, K2, F = w_gate.shape
    assert K == K2 and T % bm == 0, (x.shape, w_gate.shape, bm)
    t = ragged_tiles("ragged_gate_up_silu_f32", T, E, K, F, x.dtype,
                     w_gate.dtype, bm=bm, bn=bn, bk=bk)
    bn, bk = t.bn, t.bk
    k_steps = K // bk
    G = num_work_items(T, bm, E)
    tile_m, grp, valid, _ = ragged_metadata(offsets, bm, E, G)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(F // bn, G, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda n, g, k, tm, gr, vl, of: (tm[g], k)),
            pl.BlockSpec(
                (1, bk, bn), lambda n, g, k, tm, gr, vl, of: (gr[g], k, n)
            ),
            pl.BlockSpec(
                (1, bk, bn), lambda n, g, k, tm, gr, vl, of: (gr[g], k, n)
            ),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda n, g, k, tm, gr, vl, of: (tm[g], n)),
            pl.BlockSpec((bm, bn), lambda n, g, k, tm, gr, vl, of: (tm[g], n)),
            pl.BlockSpec((bm, bn), lambda n, g, k, tm, gr, vl, of: (tm[g], n)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
    )
    sh = jax.ShapeDtypeStruct((T, F), jnp.float32)
    h, ag, au = pl.pallas_call(
        functools.partial(_ragged_gate_up_kernel, bm=bm, k_steps=k_steps),
        grid_spec=grid_spec,
        out_shape=[sh, sh, sh],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=t.vmem_limit_bytes),
        interpret=interpret,
    )(tile_m, grp, valid, offsets.astype(jnp.int32), x, w_gate, w_up)
    rows = jnp.arange(T, dtype=jnp.int32)[:, None]
    own = rows < offsets[-1]
    return (jnp.where(own, h, 0.0), jnp.where(own, ag, 0.0),
            jnp.where(own, au, 0.0))


def _ragged_dw_kernel(tile_m, grp, valid, is_first, offs, x_ref, g_ref,
                      o_ref, *, bm: int):
    g = pl.program_id(2)
    mask = _row_mask(tile_m, grp, valid, offs, g, bm)
    # Mask BOTH operands: un-owned rows may hold garbage (even NaN) and the
    # contraction here is over rows, so 0·NaN must never be formed.
    xm = jnp.where(mask, x_ref[...].astype(jnp.float32), 0.0)
    gm = jnp.where(mask, g_ref[...].astype(jnp.float32), 0.0)
    contrib = jax.lax.dot_general(
        xm, gm, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    first = is_first[g] == 1

    @pl.when(first)
    def _init():
        o_ref[0] = contrib

    @pl.when(jnp.logical_not(first))
    def _accum():
        o_ref[0] += contrib


@functools.partial(
    jax.jit, static_argnames=("num_groups", "bm", "bn", "bk", "interpret")
)
def ragged_dw_f32(
    x: jax.Array,  # (T, K) sorted rows; T % bm == 0
    g: jax.Array,  # (T, N) cotangent rows, same ordering
    offsets: jax.Array,  # (E+1,)
    num_groups: int,
    *,
    bm: int = 128,
    bn=None,
    bk=None,
    interpret: bool = False,
) -> jax.Array:
    """Ragged dgrad (transposed grouped GEMM): dW[e] = x_e^T @ g_e, the
    expert-weight gradient of a ragged GEMM.  Work items run innermost so
    each expert's (K, N) accumulator tile stays resident across its
    row-tiles; with the whole K and N (the picker's choice where it fits)
    the grid is (1, 1, G) and x and g are each read once."""
    T, K = x.shape
    T2, N = g.shape
    E = num_groups
    assert T == T2 and T % bm == 0, (x.shape, g.shape, bm)
    t = ragged_tiles("ragged_dw_f32", T, E, K, N, x.dtype, g.dtype,
                     bm=bm, bn=bn, bk=bk)
    bn, bk = t.bn, t.bk
    G = num_work_items(T, bm, E)
    tile_m, grp, valid, is_first = ragged_metadata(offsets, bm, E, G)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(K // bk, N // bn, G),
        in_specs=[
            pl.BlockSpec(
                (bm, bk), lambda k, n, g, tm, gr, vl, isf, of: (tm[g], k)
            ),
            pl.BlockSpec(
                (bm, bn), lambda k, n, g, tm, gr, vl, isf, of: (tm[g], n)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, bk, bn), lambda k, n, g, tm, gr, vl, isf, of: (gr[g], k, n)
        ),
    )
    out = pl.pallas_call(
        functools.partial(_ragged_dw_kernel, bm=bm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, K, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=t.vmem_limit_bytes),
        interpret=interpret,
    )(tile_m, grp, valid, is_first, offsets.astype(jnp.int32), x, g)
    # Experts with zero rows get no work item: their tiles are uninitialized.
    counts = offsets[1:] - offsets[:-1]
    return jnp.where((counts > 0)[:, None, None], out, 0.0)
