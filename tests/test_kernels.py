"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.moe_gemm import ops as mm_ops
from repro.kernels.moe_gemm import ref as mm_ref
from repro.kernels.ssd import ops as ssd_ops
from repro.kernels.ssd import ref as ssd_ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "E,M,K,N",
    [(2, 16, 32, 16), (4, 128, 64, 512), (3, 100, 96, 56), (8, 256, 128, 128),
     (1, 64, 512, 64)],
)
def test_grouped_matmul(E, M, K, N, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (E, M, K), dtype)
    w = jax.random.normal(k2, (E, K, N), dtype)
    out = mm_ops.grouped_matmul(x, w, interpret=True)
    ref = mm_ref.grouped_matmul(x, w).astype(dtype)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=TOL[dtype], atol=TOL[dtype] * 8,
    )


@pytest.mark.parametrize("activation", ["swiglu", "gelu"])
def test_grouped_ffn(activation):
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 4)
    E, C, d, f = 4, 64, 48, 96
    toks = jax.random.normal(ks[0], (E, C, d))
    wu = jax.random.normal(ks[1], (E, d, f)) * 0.1
    wg = jax.random.normal(ks[2], (E, d, f)) * 0.1
    wd = jax.random.normal(ks[3], (E, f, d)) * 0.1
    out = mm_ops.grouped_ffn(toks, wu, wg, wd, activation, interpret=True)
    ref = mm_ref.grouped_ffn(toks, wu, wg, wd, activation)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_grouped_ffn_keeps_fp32_intermediate():
    """Precision regression: the hidden activation must stay fp32 between
    the up/gate and down launches.  The old bf16 round-trip's mean error vs
    an fp64 reference is ~2.2e-3 at f=1024; keeping fp32 gives ~1.4e-3 —
    the 1.8e-3 gate fails the truncating version on both widths."""
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
            mm_ops.grouped_ffn(toks, wu, wg, wd, "swiglu", interpret=True),
            np.float64,
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


def _ragged_case(counts, K, N, dtype, seed=0):
    counts = np.asarray(counts)
    E, T = len(counts), int(counts.sum())
    offs = jnp.asarray(np.concatenate([[0], np.cumsum(counts)]), jnp.int32)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (T, K), dtype)
    w = jax.random.normal(k2, (E, K, N), dtype) * 0.2
    return x, w, offs, E, T


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("counts", RAGGED_COUNTS)
def test_ragged_matmul(counts, dtype):
    x, w, offs, E, T = _ragged_case(counts, K=48, N=64, dtype=dtype)
    out = mm_ops.ragged_matmul(x, w, offs, interpret=True, bm=16)
    ref = mm_ref.ragged_matmul(x, w, offs)
    tol = TOL[dtype]
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol * 8,
    )


@pytest.mark.parametrize("activation", ["swiglu", "gelu"])
@pytest.mark.parametrize("counts", RAGGED_COUNTS)
def test_ragged_ffn_matches_oracle(counts, activation):
    x, _, offs, E, T = _ragged_case(counts, K=32, N=32, dtype=jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    d, f = 32, 48
    wu = jax.random.normal(ks[0], (E, d, f)) * 0.2
    wg = jax.random.normal(ks[1], (E, d, f)) * 0.2 if activation == "swiglu" else None
    wd = jax.random.normal(ks[2], (E, f, d)) * 0.2
    out = mm_ops.ragged_ffn(x, wu, wg, wd, offs, activation,
                            interpret=True, bm=16)
    ref = mm_ref.ragged_ffn(x, wu, wg, wd, offs, activation)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
    )


@pytest.mark.parametrize("activation", ["swiglu", "gelu"])
def test_ragged_ffn_custom_vjp_matches_jax_grad(activation):
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
                              interpret=True, bm=16)
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


def test_ragged_ffn_ignores_a_long_unowned_tail():
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

    def grads(x):
        def f(x, wu, wg, wd):
            y = mm_ops.ragged_ffn(x, wu, wg, wd, offs, interpret=True, bm=16)
            return jnp.sum(jnp.sin(y)), y
        return jax.grad(f, argnums=(0, 1, 2, 3), has_aux=True)(x, wu, wg, wd)

    (g_tail, y_tail), (g_own, y_own) = grads(x_tail), grads(x)
    np.testing.assert_array_equal(y_tail[:T], y_own)
    assert not np.asarray(y_tail[T:]).any()
    assert not np.asarray(g_tail[0][T:]).any()
    np.testing.assert_array_equal(g_tail[0][:T], g_own[0])
    for a, b in zip(g_tail[1:], g_own[1:]):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_ragged_matmul_empty_tail_rows_zero():
    """Rows beyond offsets[-1] (padding) must come back exactly zero."""
    counts = [5, 3]
    offs = jnp.asarray([0, 5, 8], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 32))  # 8 pad rows
    w = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 16))
    out = np.asarray(mm_ops.ragged_matmul(x, w, offs, interpret=True, bm=8))
    assert (out[8:] == 0).all()


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
def test_flash_attention(b, hq, hkv, s, d, window, cap, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    out = fa_ops.flash_attention(
        q, k, v, window=window, logit_softcap=cap, interpret=True,
        bq=64, bk=64,
    )
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


@pytest.mark.parametrize(
    "b,nc,cl,h,p,n", [(1, 2, 32, 4, 16, 8), (2, 2, 64, 8, 32, 16)]
)
def test_ssd_intra_chunk(b, nc, cl, h, p, n):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (b, nc, cl, h, p))
    dA = -jnp.abs(jax.random.normal(ks[1], (b, nc, cl, h))) * 0.1
    B = jax.random.normal(ks[2], (b, nc, cl, h, n))
    C = jax.random.normal(ks[3], (b, nc, cl, h, n))
    y = ssd_ops.ssd_intra_chunk(x, dA, B, C, interpret=True)
    fold = lambda t: t.reshape((b * nc,) + t.shape[2:])
    ref = ssd_ref.ssd_intra_chunk(fold(x), fold(dA), fold(B), fold(C))
    np.testing.assert_allclose(
        np.asarray(y).reshape(ref.shape), np.asarray(ref), atol=3e-5
    )


def test_full_model_pallas_matches_xla():
    from repro.configs import get_arch
    from repro.models.model import LanguageModel, init_params
    from repro.sharding import single_device_plan

    for name in ["granite-moe-3b-a800m", "mamba2-370m", "gemma2-9b"]:
        arch = get_arch(name).reduced()
        plan = single_device_plan(arch)
        with plan.mesh:
            params = init_params(arch, jax.random.PRNGKey(0))
            toks = jax.random.randint(
                jax.random.PRNGKey(5), (2, 64), 0, arch.vocab_size
            )
            lx, _, _ = jax.jit(
                LanguageModel(arch, plan, impl="xla").forward
            )(params, {"tokens": toks})
            lp, _, _ = jax.jit(
                LanguageModel(arch, plan, impl="pallas").forward
            )(params, {"tokens": toks})
            np.testing.assert_allclose(
                np.asarray(lx), np.asarray(lp), atol=5e-5
            )
