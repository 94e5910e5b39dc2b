"""Per-kernel and per-path shape/dtype sweeps vs the pure-jnp oracles
(deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.moe_gemm import ops as mm_ops
from repro.kernels.moe_gemm import ref as mm_ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "E,M,K,N",
    [(2, 16, 32, 16), (4, 128, 64, 512), (3, 100, 96, 56), (8, 256, 128, 128),
     (1, 64, 512, 64)],
)
def test_ragged_matmul_full_groups(E, M, K, N, dtype):
    """Evenly full groups (M rows per expert, as capacity buffers hold
    them) through the ragged kernel, against the grouped oracle; K 96 and
    N 56 are no multiples of 128."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (E, M, K), dtype)
    w = jax.random.normal(k2, (E, K, N), dtype)
    offs = jnp.arange(E + 1, dtype=jnp.int32) * M
    out = mm_ops.ragged_matmul(x.reshape(E * M, K), w, offs, interpret=True)
    out = out.reshape(E, M, N)
    ref = mm_ref.grouped_matmul(x, w).astype(dtype)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=TOL[dtype], atol=TOL[dtype] * 8,
    )


@pytest.mark.parametrize("activation", ["swiglu", "gelu"])
def test_expert_ffn_matches_oracle(activation):
    """Capacity dispatch's expert FFN (XLA einsums) against the grouped
    oracle."""
    from repro.models import moe

    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 4)
    E, C, d, f = 4, 64, 48, 96
    toks = jax.random.normal(ks[0], (E, C, d))
    wu = jax.random.normal(ks[1], (E, d, f)) * 0.1
    wg = jax.random.normal(ks[2], (E, d, f)) * 0.1
    wd = jax.random.normal(ks[3], (E, f, d)) * 0.1
    out = moe._expert_ffn(toks, wu, wg, wd, activation)
    ref = mm_ref.grouped_ffn(toks, wu, wg, wd, activation)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_expert_ffn_keeps_fp32_intermediate():
    """Precision regression: the hidden activation must stay fp32 between
    the up/gate and down projections.  A bf16 round-trip's mean error vs
    an fp64 reference is ~2.2e-3 at f=1024; keeping fp32 gives ~1.4e-3 —
    the 1.8e-3 gate fails the truncating version on both widths."""
    from repro.models import moe

    for f in (512, 1024):
        E, C, d = 2, 32, 64
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        toks = jax.random.normal(ks[0], (E, C, d)).astype(jnp.bfloat16)
        wu = (jax.random.normal(ks[1], (E, d, f)) * 0.1).astype(jnp.bfloat16)
        wg = (jax.random.normal(ks[2], (E, d, f)) * 0.1).astype(jnp.bfloat16)
        wd = (jax.random.normal(ks[3], (E, f, d)) * 0.1).astype(jnp.bfloat16)
        t64, u64, g64, d64 = (
            np.asarray(a, np.float64) for a in (toks, wu, wg, wd)
        )
        gate = np.einsum("ecd,edf->ecf", t64, g64)
        up = np.einsum("ecd,edf->ecf", t64, u64)
        h64 = gate / (1 + np.exp(-gate)) * up
        ref = np.einsum("ecf,efd->ecd", h64, d64)
        out = np.asarray(
            moe._expert_ffn(toks, wu, wg, wd, "swiglu"), np.float64
        )
        mean_rel = np.abs(out - ref).mean() / np.abs(ref).mean()
        assert mean_rel < 1.8e-3, (f, mean_rel)


RAGGED_COUNTS = [
    [7, 0, 83, 1, 9],  # skewed + empty expert
    [0, 0, 0, 100],  # all tokens to one expert
    [25, 25, 25, 25],  # uniform
    [100],  # E = 1
    [1, 1, 1, 1, 1, 96, 1, 1],  # near-degenerate skew
]

# Row tiles of 16 (many tiles and straddles), and the blocks the picker
# takes from the shapes: the whole K and N, and a row tile larger than
# most experts' row counts.
RAGGED_BLOCKS = {"bm16": dict(bm=16), "picked": {}}
BLOCK_IDS = list(RAGGED_BLOCKS)


def _ragged_case(counts, K, N, dtype, seed=0):
    counts = np.asarray(counts)
    E, T = len(counts), int(counts.sum())
    offs = jnp.asarray(np.concatenate([[0], np.cumsum(counts)]), jnp.int32)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (T, K), dtype)
    w = jax.random.normal(k2, (E, K, N), dtype) * 0.2
    return x, w, offs, E, T


@pytest.mark.parametrize("blocks", BLOCK_IDS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("counts", RAGGED_COUNTS)
def test_ragged_matmul(counts, dtype, blocks):
    x, w, offs, E, T = _ragged_case(counts, K=48, N=64, dtype=dtype)
    out = mm_ops.ragged_matmul(x, w, offs, interpret=True,
                               **RAGGED_BLOCKS[blocks])
    ref = mm_ref.ragged_matmul(x, w, offs)
    tol = TOL[dtype]
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol * 8,
    )


@pytest.mark.parametrize("blocks", BLOCK_IDS)
@pytest.mark.parametrize("activation", ["swiglu", "gelu"])
@pytest.mark.parametrize("counts", RAGGED_COUNTS)
def test_ragged_ffn_matches_oracle(counts, activation, blocks):
    x, _, offs, E, T = _ragged_case(counts, K=32, N=32, dtype=jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    d, f = 32, 48
    wu = jax.random.normal(ks[0], (E, d, f)) * 0.2
    wg = jax.random.normal(ks[1], (E, d, f)) * 0.2 if activation == "swiglu" else None
    wd = jax.random.normal(ks[2], (E, f, d)) * 0.2
    out = mm_ops.ragged_ffn(x, wu, wg, wd, offs, activation,
                            interpret=True, **RAGGED_BLOCKS[blocks])
    ref = mm_ref.ragged_ffn(x, wu, wg, wd, offs, activation)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
    )


@pytest.mark.parametrize("blocks", BLOCK_IDS)
@pytest.mark.parametrize("activation", ["swiglu", "gelu"])
def test_ragged_ffn_custom_vjp_matches_jax_grad(activation, blocks):
    """The hand-written backward (two ragged GEMMs + ragged dgrads) must
    equal jax.grad through the differentiable XLA reference."""
    counts = [7, 0, 83, 1, 9]
    x, _, offs, E, T = _ragged_case(counts, K=32, N=32, dtype=jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    d, f = 32, 48
    wu = jax.random.normal(ks[0], (E, d, f)) * 0.2
    wg = jax.random.normal(ks[1], (E, d, f)) * 0.2
    wd = jax.random.normal(ks[2], (E, f, d)) * 0.2
    cot = jnp.cos(jnp.arange(T * d, dtype=jnp.float32)).reshape(T, d)

    def kernel_loss(x, wu, wg, wd):
        wg_ = wg if activation == "swiglu" else None
        y = mm_ops.ragged_ffn(x, wu, wg_, wd, offs, activation,
                              interpret=True, **RAGGED_BLOCKS[blocks])
        return (y * cot).sum()

    def ref_loss(x, wu, wg, wd):
        wg_ = wg if activation == "swiglu" else None
        y = mm_ref.ragged_ffn(x, wu, wg_, wd, offs, activation)
        return (y * cot).sum()

    gk = jax.grad(kernel_loss, argnums=(0, 1, 2, 3))(x, wu, wg, wd)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2, 3))(x, wu, wg, wd)
    for name, a, b in zip(("dx", "dwu", "dwg", "dwd"), gk, gr):
        if activation != "swiglu" and name == "dwg":
            continue  # w_gate unused: both grads are zero/absent
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5,
            err_msg=name,
        )


@pytest.mark.parametrize("blocks", BLOCK_IDS)
def test_ragged_ffn_ignores_a_long_unowned_tail(blocks):
    """Rows no expert owns fill most of x (one chip's share of a layer,
    whose rows for absent experts sort to a tail): the static grid's
    surplus items leave them zero with zero gradient, and the owned rows'
    outputs and gradients equal those of the owned rows alone."""
    counts = [7, 0, 23, 1]
    x, _, offs, E, T = _ragged_case(counts, K=32, N=32, dtype=jnp.float32)
    x_tail = jnp.concatenate([x, jnp.ones((93, 32))])
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    wu, wg = (jax.random.normal(k, (E, 32, 48)) * 0.2 for k in ks[:2])
    wd = jax.random.normal(ks[2], (E, 48, 32)) * 0.2
    # The tail must not change the row tile, or the sums run otherwise.
    bm = RAGGED_BLOCKS[blocks].get("bm", 16 * -(-T // 16))

    def grads(x):
        def f(x, wu, wg, wd):
            y = mm_ops.ragged_ffn(x, wu, wg, wd, offs, interpret=True, bm=bm)
            return jnp.sum(jnp.sin(y)), y
        return jax.grad(f, argnums=(0, 1, 2, 3), has_aux=True)(x, wu, wg, wd)

    (g_tail, y_tail), (g_own, y_own) = grads(x_tail), grads(x)
    np.testing.assert_array_equal(y_tail[:T], y_own)
    assert not np.asarray(y_tail[T:]).any()
    assert not np.asarray(g_tail[0][T:]).any()
    np.testing.assert_array_equal(g_tail[0][:T], g_own[0])
    for a, b in zip(g_tail[1:], g_own[1:]):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("bm", [8, None])
def test_ragged_matmul_empty_tail_rows_zero(bm):
    """Rows beyond offsets[-1] (padding) must come back exactly zero."""
    offs = jnp.asarray([0, 5, 8], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 32))  # 8 pad rows
    w = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 16))
    out = np.asarray(mm_ops.ragged_matmul(x, w, offs, interpret=True, bm=bm))
    assert (out[8:] == 0).all()


# Widths with several 128-multiples, so that split blocks (several output
# strips and K steps) and the picker's whole-K/N blocks both run.
SPLIT = dict(bn=128, bk=128)
WIDE_BLOCKS = {"bm16": dict(bm=16), "picked": {}, "split": dict(bm=16, **SPLIT),
               "split_bm32": dict(bm=32, **SPLIT)}


@pytest.mark.parametrize("blocks", list(WIDE_BLOCKS))
@pytest.mark.parametrize("counts", [[7, 0, 83, 1, 9], [40, 0, 0, 2]])
def test_ragged_dw_matches_oracle(counts, blocks):
    """The ragged dgrad against its einsum oracle, bf16 token rows and fp32
    cotangents as the FFN's backward passes them; an expert with no rows
    gets a zero gradient."""
    from repro.kernels.moe_gemm import moe_gemm

    x, _, offs, E, T = _ragged_case(counts, K=256, N=8, dtype=jnp.bfloat16)
    g = jax.random.normal(jax.random.PRNGKey(4), (T, 384), jnp.float32)
    kw = {"bm": moe_gemm.row_tile(T, E), **WIDE_BLOCKS[blocks]}
    pad = -T % kw["bm"]
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    gp = jnp.pad(g, ((0, pad), (0, 0)))
    out = moe_gemm.ragged_dw_f32(xp, gp, offs, E, interpret=True, **kw)
    ref = mm_ref.ragged_dw(x, g, offs, E)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)
    empty = np.asarray(counts) == 0
    assert not np.asarray(out)[empty].any()


@pytest.mark.parametrize("blocks", list(WIDE_BLOCKS))
def test_ragged_ffn_wide_blocks_match_oracle(blocks):
    """The FFN and its custom VJP at widths of 256 and 384, where split
    blocks take several K steps and output strips and the picker takes
    whole K and N, against the XLA reference."""
    counts = [7, 0, 83, 1, 9]
    x, _, offs, E, T = _ragged_case(counts, K=256, N=8, dtype=jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    d, f = 256, 384
    wu, wg = (jax.random.normal(k, (E, d, f)) * 0.05 for k in ks[:2])
    wd = jax.random.normal(ks[2], (E, f, d)) * 0.05

    def loss(fn, **kw):
        def f(x, wu, wg, wd):
            y = fn(x, wu, wg, wd, offs, **kw)
            return jnp.sum(jnp.sin(y)), y
        return jax.grad(f, argnums=(0, 1, 2, 3), has_aux=True)(x, wu, wg, wd)

    gk, yk = loss(mm_ops.ragged_ffn, interpret=True, **WIDE_BLOCKS[blocks])
    gr, yr = loss(mm_ref.ragged_ffn)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)
    for name, a, b in zip(("dx", "dwu", "dwg", "dwd"), gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def _ffn_launches(d, f):
    """(kernel, K, N, row dtype, other dtype) of the ragged FFN's launches,
    forward and backward, as ``ops._make_ragged_ffn`` makes them."""
    bf, f32 = jnp.bfloat16, jnp.float32
    mm, dw = "ragged_matmul_f32", "ragged_dw_f32"
    return [("ragged_gate_up_silu_f32", d, f, bf, bf),  # gate, up
            (mm, f, d, f32, bf),  # down
            (mm, d, f, f32, bf),  # dh
            (mm, f, d, f32, bf),  # dx_gate, dx_up
            (dw, f, d, f32, f32),  # dW_down
            (dw, d, f, bf, f32)]  # dW_gate, dW_up


@pytest.mark.parametrize("T,E,d,f", [(131_072, 40, 1536, 512),
                                     (24_576, 8, 2048, 1408)],
                         ids=["granite", "moonlight_share"])
def test_tiles_take_whole_k_and_n_at_the_cells_widths(T, E, d, f):
    """At granite's widths (16,384 tokens x top-8) and at the first chunk
    of one chip's share of a Moonlight layer, every launch of the FFN takes
    the whole K and N, 256-row tiles, one grid step per work item, and a
    VMEM limit above its estimate and within the cap."""
    from repro.kernels.moe_gemm import moe_gemm

    for kernel, K, N, a, b in _ffn_launches(d, f):
        t = moe_gemm.ragged_tiles(kernel, T, E, K, N, a, b)
        assert (t.bm, t.bk, t.bn) == (256, K, N), (kernel, t)
        assert t.grid_steps == T // 256 + E
        assert t.vmem_bytes < t.vmem_limit_bytes <= moe_gemm.VMEM_CAP
        assert t.vmem_bytes <= moe_gemm.VMEM_BUDGET


def test_row_tile_follows_rows_and_experts():
    from repro.kernels.moe_gemm import moe_gemm

    assert moe_gemm.row_tile(131_072, 40) == 256  # 512 would waste 15.6 %
    assert moe_gemm.row_tile(49_152, 8) == 512
    assert moe_gemm.row_tile(3_072, 8) == 128
    assert moe_gemm.row_tile(100, 5) == 112  # 100 rows to the 16-row tile
    assert moe_gemm.row_tile(1, 1) == 16


def test_tiles_shrink_n_then_k_to_fit_the_budget():
    """Where the whole K and N cannot fit, the picker shrinks N first, then
    K, through the blocks Mosaic accepts, and never passes the budget; with
    no block that fits it refuses."""
    from repro.kernels.moe_gemm import moe_gemm

    f32, mm = jnp.float32, "ragged_matmul_f32"
    t = moe_gemm.ragged_tiles(mm, 65_536, 8, 4096, 8192, f32, f32)
    assert (t.bm, t.bk, t.bn) == (512, 4096, 1024)  # bn 2048 does not fit
    assert t.vmem_bytes <= moe_gemm.VMEM_BUDGET
    assert t.vmem_bytes < t.vmem_limit_bytes <= moe_gemm.VMEM_CAP
    # 8320 = 65 x 128: no bn of 8320, 1664 or 640 fits beside a whole K of
    # 16,384, so K halves; 640 then fits.
    wider = moe_gemm.ragged_tiles(mm, 65_536, 8, 16_384, 8320, f32, f32)
    assert (wider.bk, wider.bn) == (8192, 640)
    assert wider.vmem_bytes < wider.vmem_limit_bytes <= moe_gemm.VMEM_CAP
    small = moe_gemm.ragged_tiles("ragged_dw_f32", 65_536, 8, 8192, 8192,
                                  f32, f32, budget=8 * 2**20)
    assert (small.bk, small.bn) == (512, 256)
    assert small.vmem_bytes <= 8 * 2**20
    with pytest.raises(ValueError, match="fit"):
        moe_gemm.ragged_tiles("ragged_dw_f32", 65_536, 8, 9000, 9000,
                              f32, f32)


def test_ffn_counts_each_launch_with_its_tiles():
    """``moe_gemm.tiles`` is counted once per traced kernel launch: two in
    the forward, six more in the backward, each with the blocks it ran
    (at full widths, traced without running, every launch takes one grid
    step per work item)."""
    from repro import obs

    bf = jnp.bfloat16
    T, E, d, f = 24_576, 8, 2048, 1408
    args = (jax.ShapeDtypeStruct((T, d), bf),
            jax.ShapeDtypeStruct((E, d, f), bf),
            jax.ShapeDtypeStruct((E, d, f), bf),
            jax.ShapeDtypeStruct((E, f, d), bf),
            jax.ShapeDtypeStruct((E + 1,), jnp.int32))

    def fwd(x, wu, wg, wd, offs):
        return mm_ops.ragged_ffn(x, wu, wg, wd, offs, interpret=False)

    def loss(*a):
        return jnp.sum(fwd(*a).astype(jnp.float32))

    ring = obs.RingBufferSink()

    def tiles():
        return [e["attrs"] for e in ring.events()
                if e["kind"] == "counter" and e["name"] == "moe_gemm.tiles"]

    prev = obs.set_telemetry(obs.Telemetry(sinks=[ring]))
    try:
        jax.eval_shape(fwd, *args)
        n_fwd = len(tiles())
        jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2, 3)), *args)
    finally:
        obs.set_telemetry(prev)
    tiles = tiles()
    assert n_fwd == 2 and len(tiles) == 2 + 8
    assert [t["kernel"] for t in tiles[:2]] == ["ragged_gate_up_silu_f32",
                                                "ragged_matmul_f32"]
    kinds = sorted(t["kernel"] for t in tiles[4:])
    assert kinds == ["ragged_dw_f32"] * 3 + ["ragged_matmul_f32"] * 3
    for t in tiles:
        assert t["bm"] == 256 and t["grid_steps"] == T // 256 + E, t
        assert {t["bk"], t["bn"]} == {d, f}, t


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,hq,hkv,s,d,window,cap",
    [
        (2, 4, 2, 128, 32, None, None),
        (1, 8, 8, 256, 64, 64, None),
        (2, 4, 1, 96, 16, None, 50.0),
        (1, 2, 2, 64, 128, 32, 30.0),
    ],
)
def test_xla_causal_matches_oracle(b, hq, hkv, s, d, window, cap, dtype):
    """The XLA causal path, which serves windows and softcap on every
    backend, against the attention oracle."""
    from repro.models import layers as L

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    out = L._xla_causal(q, k, v, window, cap, None)
    ref = fa_ref.attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), window=window, softcap=cap,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=TOL[dtype], atol=TOL[dtype] * 4,
    )


@pytest.mark.parametrize(
    "b,hq,hkv,s,d,cap,dtype",
    [
        (1, 6, 2, 512, 64, None, jnp.bfloat16),  # GQA
        (2, 4, 4, 256, 128, None, jnp.float32),  # MHA
        (1, 4, 2, 256, 64, 30.0, jnp.bfloat16),  # softcap
    ],
)
def test_causal_attention_matches_xla(b, hq, hkv, s, d, cap, dtype):
    """The trainable splash entry point against ``layers.attention`` (one q
    chunk): the output and the gradients w.r.t. q, k and v."""
    from repro.models import layers as L

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, s, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    g = jax.random.normal(ks[3], (b, s, hq, d), dtype)

    def run(attn):
        def loss(q, k, v):
            out = attn(q, k, v)
            return jnp.sum((out * g).astype(jnp.float32)), out

        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))(
            q, k, v
        )

    (_, out), grads = run(lambda q, k, v: fa_ops.causal_attention(
        q, k, v, logit_softcap=cap, interpret=True))
    (_, ref), ref_grads = run(lambda q, k, v: L.attention(
        q, k, v, logit_softcap=cap))
    for got, want in [(out, ref), *zip(grads, ref_grads)]:
        want = np.asarray(want, np.float32)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), want,
            rtol=TOL[dtype], atol=TOL[dtype] * np.abs(want).max(),
        )


@pytest.mark.parametrize("s,block", [(4096, 1024), (1536, 512), (256, 256),
                                     (384, 128), (640, 128)])
def test_causal_attention_block_divides_s(s, block):
    assert fa_ops.block_size(s) == block


FLASH_OK = dict(backend="tpu", s=4096, window=None, cached=False,
                mesh_size=1)


@pytest.mark.parametrize(
    "change,path",
    [
        ({}, "flash"),
        ({"s": 128}, "flash"),
        ({"window": 4096}, "xla"),
        ({"cached": True}, "xla"),
        ({"s": 4000}, "xla"),
        ({"mesh_size": 4}, "xla"),
        ({"backend": "cpu"}, "xla"),
    ],
)
def test_attention_path(change, path):
    """The routing is a function of what the code observes: backend,
    sequence length, window, cache and mesh size."""
    from repro.models import layers as L

    kw = {**FLASH_OK, **change}
    backend, s = kw.pop("backend"), kw.pop("s")
    assert L.attention_path(backend, s, **kw) == path


def test_attention_proj_on_cpu_takes_xla_path():
    """On the CPU backend ``attention_proj`` runs the XLA formula, bit for
    bit, and counts one ``attention.path`` of ``xla`` per traced call."""
    import types

    from repro import obs
    from repro.models import layers as L

    cfg = types.SimpleNamespace(num_heads=4, num_kv_heads=2, head_dim=16,
                                rope_type="rope", rope_theta=1e4,
                                attn_logit_softcap=None)
    b, s, dm = 2, 256, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    params = {
        "wq": jax.random.normal(ks[0], (dm, 64)) * 0.2,
        "wk": jax.random.normal(ks[1], (dm, 32)) * 0.2,
        "wv": jax.random.normal(ks[2], (dm, 32)) * 0.2,
        "wo": jax.random.normal(ks[3], (64, dm)) * 0.2,
    }
    x = jax.random.normal(ks[4], (b, s, dm))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    ring = obs.RingBufferSink()
    prev = obs.set_telemetry(obs.Telemetry(sinks=[ring]))
    try:
        out, _ = L.attention_proj(params, x, cfg, pos)
    finally:
        obs.set_telemetry(prev)
    paths = [e["attrs"]["path"] for e in ring.events()
             if e["kind"] == "counter" and e["name"] == "attention.path"]
    assert paths == ["xla"]

    def heads(w):
        y = jnp.einsum("bsd,dk->bsk", x, w)
        return y.reshape(b, s, -1, 16)

    q = L.apply_rope(heads(params["wq"]), pos, 1e4)
    k = L.apply_rope(heads(params["wk"]), pos, 1e4)
    ref = L.attention(q, k, heads(params["wv"]))
    ref = jnp.einsum("bsk,kd->bsd", ref.reshape(b, s, 64), params["wo"])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
