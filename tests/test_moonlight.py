"""moonlight-16b-a3b: the DeepSeek-V3 block on the program's paths that
do not run it (serving, the pipeline executor, expert migration and
replication), one chip's share of an expert layer, and the arch's cut."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MoECfg, get_arch
from repro.models.model import LanguageModel, init_params
from repro.sharding import single_device_plan

TINY = get_arch("moonlight-16b-a3b-ep8").reduced()


def test_published_and_share_presets():
    full = get_arch("moonlight-16b-a3b")
    assert [b for b in full.layers[:2]] == [("mla", "dense"), ("mla", "moe")]
    assert full.num_moe_layers == 26 and full.num_attn_layers == 27
    assert 15.9e9 < full.total_params() < 16.1e9
    # A3B: 2.91 B with the input embedding table, a gather.
    assert 2.5e9 < full.active_params() < 2.7e9
    share = get_arch("moonlight-16b-a3b-ep8")
    m = share.moe
    assert (m.num_experts, m.experts_held, m.first_held, m.top_k) == (
        64, 8, 0, 6)
    assert share.vocab_size == 163840 // 8
    assert share.replace(num_layers=5).layers == (
        (("mla", "dense"),) + (("mla", "moe"),) * 4)
    assert full.share(8, 3).moe.first_held == 24
    with pytest.raises(AssertionError):
        share.replace(num_layers=1)  # no layer after the dense one


@pytest.mark.parametrize("kw", [dict(max_replicas=2),
                                dict(dispatch="capacity"),
                                dict(ep_rank=8)])
def test_a_share_refuses_replicas_capacity_and_foreign_ranks(kw):
    base = dict(num_experts=64, top_k=6, d_ff=1408, dispatch="ragged",
                ep_share=8)
    MoECfg(**base)
    with pytest.raises(AssertionError):
        MoECfg(**{**base, **kw})


def _lm(arch=TINY):
    lm = LanguageModel(arch, single_device_plan(arch))
    return lm, init_params(arch, jax.random.key(0))


@pytest.mark.parametrize("path", ["init_cache", "init_paged_cache",
                                  "decode_step", "prefill", "prefill_paged",
                                  "decode_step_paged"])
def test_serving_paths_refuse_latent_attention(path):
    """A cache is refused where it is made; the decode paths and the paged
    prefill get theirs from there, so none runs on a wrong cache."""
    from repro.serving import kv_cache as kv_lib

    lm, params = _lm(get_arch("moonlight-16b-a3b").reduced())
    tok = {"tokens": jnp.zeros((2, 1), jnp.int32)}
    lengths = jnp.ones((2,), jnp.int32)
    layout = kv_lib.PagedLayout(num_blocks=4, block_size=8, max_seqs=2,
                                max_blocks_per_seq=2)
    call = {
        "init_cache": lambda: lm.init_cache(2, 16),
        "init_paged_cache": lambda: lm.init_paged_cache(layout),
        "decode_step": lambda: lm.decode_step(
            params, lm.init_cache(2, 16), tok, 0),
        "prefill": lambda: lm.prefill(
            params, {"tokens": jnp.zeros((2, 8), jnp.int32)}),
        "prefill_paged": lambda: lm.prefill_paged(
            params, {"tokens": jnp.zeros((2, 8), jnp.int32)},
            lm.init_paged_cache(layout), None, lengths),
        "decode_step_paged": lambda: lm.decode_step_paged(
            params, lm.init_paged_cache(layout), None, lengths, tok),
    }[path]
    with pytest.raises(NotImplementedError, match="latent"):
        call()


def test_serve_cli_refuses_latent_attention(monkeypatch):
    from repro.launch import serve

    monkeypatch.setattr("sys.argv", [
        "serve", "--arch", "moonlight-16b-a3b", "--reduced", "--requests",
        "1", "--max-new", "1"])
    with pytest.raises(NotImplementedError, match="latent"):
        serve.main()


def test_migration_is_a_no_op_on_a_share():
    """One chip's share runs without EP: the migration controller leaves
    the state (and its routing tables) as they are."""
    from repro.optim import OptimizerConfig
    from repro.runtime import Trainer, TrainerConfig

    lm, params = _lm()
    tr = Trainer(lm, OptimizerConfig(total_steps=10),
                 TrainerConfig(total_steps=10, migrate_every=1))
    tr.load_stats.update(np.tile(np.arange(8.0) ** 3, (2, 1)))
    state = {"params": params}
    assert tr._maybe_migrate(state, 1) is state


def test_the_pipeline_refuses_a_dense_prefix():
    from repro.optim import OptimizerConfig
    from repro import training

    lm, _ = _lm()
    object.__setattr__(lm.plan, "pp_axis", "pod")
    with pytest.raises(NotImplementedError, match="leading dense"):
        lm._stack_out({}, {"tokens": jnp.zeros((2, 8), jnp.int32)})
    object.__setattr__(lm.plan, "pp", 2)
    with pytest.raises(NotImplementedError, match="bias"):
        training.make_train_step(lm, OptimizerConfig())


def test_router_bias_update_moves_towards_the_mean_load():
    from repro.models import moe as moe_lib

    _, params = _lm()
    loads = jnp.asarray([[[3.0, 1.0, 2.0, 2.0, 0.0, 4.0, 2.0, 2.0]],
                         [[2.0] * 8]])  # (reps, moe positions, E)
    blocks = moe_lib.update_router_bias(params["blocks"], loads, TINY)
    rb = np.asarray(blocks[0]["ffn"]["router_bias"])
    np.testing.assert_array_equal(rb, [[-1, 1, 0, 0, 1, -1, 0, 0], [0] * 8])
    assert rb.dtype == np.int32


def test_sigmoid_router_chooses_by_bias_and_weighs_by_score():
    from repro.models import moe as moe_lib

    moe = TINY.moe
    x = jnp.eye(8, dtype=jnp.float32)[:1] * 2.0  # one token
    w = jnp.diag(jnp.linspace(1.0, 0.3, 8))  # scores fall with the id
    top_w, top_i, scores, _ = moe_lib._route(x, w, moe)
    assert list(np.asarray(top_i[0])) == [0, 1]
    bias = jnp.zeros(8).at[7].set(1.0)
    top_w, top_i, _, _ = moe_lib._route(x, w, moe, bias)
    assert sorted(np.asarray(top_i[0])) == [0, 7]
    s = np.asarray(scores[0])[[0, 7]]
    np.testing.assert_allclose(
        sorted(np.asarray(top_w[0])), sorted(s / s.sum() * moe.routed_scale),
        rtol=1e-6)


def _held_rows_against_dense(ids, first, E_l, E):
    """``moe._held_rows`` against each token's weighted sum over its held
    experts taken densely: output and gradients."""
    from repro.models import moe as moe_lib

    T, k = ids.shape
    d, f = 16, 24
    ks = jax.random.split(jax.random.key(1), 6)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    wg, wu = (jax.random.normal(kk, (E_l, d, f), jnp.float32) / 4
              for kk in ks[1:3])
    wd = jax.random.normal(ks[3], (E_l, f, d), jnp.float32) / 4
    top_w = jax.random.uniform(ks[5], (T, k), jnp.float32)

    def got(x, wg, wu, wd, top_w):
        return moe_lib._held_rows(x, ids, top_w, wu, wg, wd, "swiglu", k,
                                  first, E_l, E)

    def want(x, wg, wu, wd, top_w):
        h = jax.nn.silu(jnp.einsum("td,edf->tef", x, wg)) * jnp.einsum(
            "td,edf->tef", x, wu)
        y = jnp.einsum("tef,efd->ted", h, wd)  # (T, E_l, d)
        lid = ids - first
        on = (lid >= 0) & (lid < E_l)
        pick = jnp.take_along_axis(y, jnp.clip(lid, 0, E_l - 1)[..., None],
                                   axis=1)
        return jnp.sum(jnp.where(on[..., None], pick, 0.0)
                       * top_w[..., None], axis=1)

    r = jax.random.normal(jax.random.key(9), (T, d), jnp.float32)
    args = (x, wg, wu, wd, top_w)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(got(*args), want(*args), rtol=1e-5,
                                   atol=1e-5)
        g1 = jax.grad(lambda *a: jnp.sum(got(*a) * r), argnums=range(5))(
            *args)
        g2 = jax.grad(lambda *a: jnp.sum(want(*a) * r), argnums=range(5))(
            *args)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("skew", [False, True])
def test_held_rows_chunks_are_dropless(skew):
    """The share gathers only its held rows, in chunks of twice their even
    share; routing skewed onto the held experts runs the further chunks.
    Output and gradients equal each token's weighted sum over its held
    experts, taken densely."""
    from repro.models import moe as moe_lib

    T, k, E, E_l, first = 256, 2, 8, 2, 2
    ids = jnp.stack([jax.random.permutation(kk, E)[:k]
                     for kk in jax.random.split(jax.random.key(4), T)])
    if skew:  # every row on a held expert: the rows fill T x k
        ids = first + jnp.tile(jnp.arange(k), (T, 1))
    rows = moe_lib._held_chunk(T, k, E_l, E)
    held = ((ids >= first) & (ids < first + E_l)).sum()
    assert rows == 256 and (held > rows) == skew
    _held_rows_against_dense(ids, first, E_l, E)


def test_held_rows_reaching_into_the_first_further_chunk():
    """Held rows just past the first chunk run the first further chunk
    (an eighth of the first, in 128-row tiles) and not the next: output
    and gradients still equal the dense sum."""
    from repro.models import moe as moe_lib

    T, k, E, E_l, first = 1024, 2, 16, 2, 0
    rows = moe_lib._held_chunk(T, k, E_l, E)
    assert rows == 512
    assert moe_lib._further_chunks(T * k, rows) == [128, 256, 512, 512, 128]
    ids = jnp.stack([jax.random.permutation(kk, jnp.arange(2, E))[:k]
                     for kk in jax.random.split(jax.random.key(5), T)])
    ids = ids.at[:300, 0].set(0).at[:300, 1].set(1)  # 600 held rows
    _held_rows_against_dense(ids, first, E_l, E)


def test_further_chunks_cover_the_share_in_doubling_steps():
    """Moonlight's share at 2 x 8192 tokens: a first chunk of 24,576 rows,
    then 3,072 doubling up to 24,576, together every (token, k) row."""
    from repro.models import moe as moe_lib

    rows = moe_lib._held_chunk(16384, 6, 8, 64)
    sizes = moe_lib._further_chunks(16384 * 6, rows)
    assert rows == 24576 and sizes[:4] == [3072, 6144, 12288, 24576]
    assert rows + sum(sizes) == 16384 * 6 and max(sizes) <= rows


def test_train_cli_runs_the_share():
    from repro.launch import train

    run = train.setup(train.parse_args(
        ["--arch", "moonlight-16b-a3b-ep8", "--reduced", "--steps", "2",
         "--batch", "2", "--seq", "32"]))
    assert run["arch"].moe.dispatch == "ragged"
    out = run["trainer"].fit(run["state"], run["data"])
    run["data"].close()
    assert np.isfinite(float(out["metrics"]["loss"]))
    rb = out["state"]["params"]["blocks"][0]["ffn"]["router_bias"]
    assert np.abs(np.asarray(rb)).max() <= 2


def test_one_flash_kernel_serves_two_traces_of_a_step():
    """The dense first layer and the pattern run in two scans, traced
    apart: the cached splash kernel holds no tracer of the first."""
    from jax import lax

    from repro.kernels.flash_attention import ops as fa_ops

    q = jnp.ones((1, 256, 2, 24))
    v = jnp.ones((1, 256, 2, 16))

    def f(q, v):
        def first(c, _):
            return c + fa_ops.causal_attention(q, q, v, interpret=True), None

        def then(c, _):
            return 2 * c + fa_ops.causal_attention(q, q, v, interpret=True), None

        a, _ = lax.scan(jax.checkpoint(first), jnp.zeros_like(v), None, length=1)
        b, _ = lax.scan(jax.checkpoint(then), a, None, length=1)
        return jnp.sum(b)

    g = jax.jit(jax.grad(f))(q, v)
    assert g.shape == q.shape and bool(jnp.all(jnp.isfinite(g)))
