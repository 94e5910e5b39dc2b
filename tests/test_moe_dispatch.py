"""Ragged (dropless) vs capacity dispatch parity — deterministic suite.

These are the dispatch-mode guarantees that must hold in every container
(no hypothesis dependency; the randomized sweeps over the same checks live
in tests/test_moe_properties.py):

* where capacity mode drops nothing, ragged (the Pallas kernels and their
  custom VJP) == capacity (XLA einsums) on outputs AND grads;
* where capacity mode drops, ragged still equals the no-drop oracle;
* degenerate skews: E=1 and all-tokens-to-one-expert.
"""

import dataclasses
import re
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import moe as moe_lib


def _moe_variant(base, capacity_factor, dispatch):
    return base.replace(
        moe=dataclasses.replace(
            base.moe, capacity_factor=capacity_factor, dispatch=dispatch
        )
    )


@lru_cache(maxsize=1)
def moe_setup():
    from repro.configs import get_arch
    from repro.models.model import init_params
    from repro.sharding import single_device_plan

    arch = get_arch("granite-moe-3b-a800m").reduced()
    plan = single_device_plan(arch)
    with plan.mesh:
        params = init_params(arch, jax.random.PRNGKey(0))
    ffn = jax.tree.map(lambda p: p[0], params["blocks"][0]["ffn"])
    return arch, plan, ffn


def check_parity_no_drops(arch, plan, ffn, seed):
    """Shared body for the deterministic and hypothesis parity tests."""
    E, k = arch.moe.num_experts, arch.moe.top_k
    # C >= T*k: capacity mode provably keeps everything.
    cap = _moe_variant(arch, float(E) / k + 1.0, "capacity")
    rag = _moe_variant(arch, float(E) / k + 1.0, "ragged")
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 16, arch.d_model))
    x = x * 0.5
    with plan.mesh:
        yc, _ = moe_lib.moe_ffn_local(ffn, x, cap)
        yr, _ = moe_lib.moe_ffn_local(ffn, x, rag)
        np.testing.assert_allclose(np.asarray(yc), np.asarray(yr), atol=1e-5)

        # grads: ragged (the kernels' custom VJP) vs capacity XLA autodiff
        def grads(mode):
            return jax.grad(
                lambda p, h: (moe_lib.moe_ffn_local(p, h, mode)[0] ** 2).sum(),
                argnums=(0, 1), allow_int=True,
            )(ffn, x)

        errs = jax.tree.map(
            lambda a, b: float(
                np.abs(np.asarray(a, np.float32)
                       - np.asarray(b, np.float32)).max()
            )
            if np.issubdtype(np.asarray(a).dtype, np.floating)
            else 0.0,
            grads(cap), grads(rag),
        )
        assert max(jax.tree.leaves(errs)) < 2e-4


@pytest.mark.parametrize("seed", [0, 3, 17])
def test_ragged_equals_capacity_when_nothing_drops(seed):
    arch, plan, ffn = moe_setup()
    check_parity_no_drops(arch, plan, ffn, seed)


def test_ragged_keeps_tokens_capacity_drops():
    """On skewed loads where capacity mode demonstrably drops tokens,
    ragged output still equals the no-drop oracle (high-capacity run)."""
    arch, plan, ffn = moe_setup()
    E, k = arch.moe.num_experts, arch.moe.top_k
    lo_cap = _moe_variant(arch, 1.0, "capacity")
    lo_rag = _moe_variant(arch, 1.0, "ragged")
    oracle = _moe_variant(arch, float(E) / k + 1.0, "capacity")
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, arch.d_model)) * 0.5
    with plan.mesh:
        y_oracle, _ = moe_lib.moe_ffn_local(ffn, x, oracle)
        y_cap, _ = moe_lib.moe_ffn_local(ffn, x, lo_cap)
        y_rag, _ = moe_lib.moe_ffn_local(ffn, x, lo_rag)
    # capacity at cf=1 provably drops on this skewed routing...
    assert np.abs(np.asarray(y_cap) - np.asarray(y_oracle)).max() > 1e-3
    # ...ragged at the same cf keeps every token
    np.testing.assert_allclose(
        np.asarray(y_rag), np.asarray(y_oracle), atol=1e-5
    )


def test_ragged_degenerate_skews():
    """E=1 (single expert) and all-tokens-to-one-expert: the ragged path
    must match a dense FFN over all tokens."""
    from repro.kernels.moe_gemm import ref as mm_ref

    arch, plan, ffn = moe_setup()

    # E=1, k=1: MoE collapses to a dense FFN with router weight 1.
    moe1 = dataclasses.replace(
        arch.moe, num_experts=1, top_k=1, dispatch="ragged"
    )
    arch1 = arch.replace(moe=moe1)
    ffn1 = dict(ffn)
    ffn1["w_router"] = ffn["w_router"][:, :1]
    ffn1["assignment"] = jnp.zeros((1,), jnp.int32)
    for kname in ("w_up", "w_gate", "w_down"):
        ffn1[kname] = ffn[kname][:1]
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 8, arch.d_model)) * 0.5
    with plan.mesh:
        y, _ = moe_lib.moe_ffn_local(ffn1, x, arch1)
    xt = x.reshape(-1, arch.d_model)
    dense = mm_ref.ragged_ffn(
        xt, ffn1["w_up"], ffn1["w_gate"], ffn1["w_down"],
        jnp.asarray([0, xt.shape[0]], jnp.int32), arch.ffn_activation,
    )
    np.testing.assert_allclose(
        np.asarray(y).reshape(-1, arch.d_model), np.asarray(dense),
        atol=1e-5,
    )

    # All tokens routed to one expert: force it through the assignment
    # table (every logical expert maps to physical slot 3).
    arch_all = _moe_variant(arch, arch.moe.capacity_factor, "ragged")
    ffn_all = dict(ffn)
    ffn_all["assignment"] = jnp.full_like(ffn["assignment"], 3)
    with plan.mesh:
        y_all, _ = moe_lib.moe_ffn_local(ffn_all, x, arch_all)
    assert np.isfinite(np.asarray(y_all)).all()
    # oracle: dense FFN through expert 3 (router weights sum to 1 per token)
    dense3 = mm_ref.ragged_ffn(
        xt, ffn["w_up"][3:4], ffn["w_gate"][3:4], ffn["w_down"][3:4],
        jnp.asarray([0, xt.shape[0]], jnp.int32), arch.ffn_activation,
    )
    np.testing.assert_allclose(
        np.asarray(y_all).reshape(-1, arch.d_model), np.asarray(dense3),
        atol=1e-5,
    )


# -- the ragged path's permutations ------------------------------------------

# (T, E, k, routing): uniform routing, every row to one expert, k = 1,
# k = E, and T no multiple of 128.
PERMUTE_CASES = {
    "uniform": (256, 8, 2, "uniform"),
    "one-expert": (256, 8, 2, "one"),
    "k1": (256, 8, 1, "uniform"),
    "kE": (64, 8, 8, "all"),
    "t100": (100, 8, 2, "uniform"),
}
PERMUTE_TOL = {jnp.float32: 1e-6, jnp.bfloat16: 2.0 ** -6}


def _routing(T, E, k, routing, key):
    if routing == "one":
        return jnp.full((T, k), 3, jnp.int32)
    if routing == "all":
        keys = jax.random.split(key, T)
        return jax.vmap(lambda kk: jax.random.permutation(kk, E))(keys)
    # k distinct experts per token, as top-k gives them
    scores = jax.random.uniform(key, (T, E))
    return jax.lax.top_k(scores, k)[1].astype(jnp.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(PERMUTE_CASES))
def test_permutation_helpers_match_the_take_formula(case, dtype):
    """``_to_expert_order`` and ``_from_expert_order``: values and
    gradients (to the rows and to the weights) equal ``jax.vjp`` of the
    plain ``jnp.take`` formula, which autodiff transposes into
    scatter-adds.  The formula runs in float32 on the same inputs; a
    bfloat16 helper lands within bfloat16's rounding of it, and no further
    from it than the formula run in bfloat16."""
    T, E, k, routing = PERMUTE_CASES[case]
    d = 32
    kx, ke, kw, kg, ky = jax.random.split(jax.random.PRNGKey(T + k), 5)
    ids = _routing(T, E, k, routing, ke)
    order, inv, _ = moe_lib._sort_dispatch(ids.reshape(-1), E)
    xt = jax.random.normal(kx, (T, d)).astype(dtype)
    ys = jax.random.normal(ky, (T * k, d)).astype(dtype)
    w = jax.nn.softmax(jax.random.normal(kw, (T, k)), axis=-1).reshape(-1)
    g_xs = jax.random.normal(kg, (T * k, d)).astype(dtype)
    g_y = jax.random.normal(kg, (T, d)).astype(dtype)
    tol = PERMUTE_TOL[dtype]

    def to_plain(xt):
        return jnp.take(xt, order // k, axis=0)

    def from_plain(ys, w):
        vals = jnp.take(ys, inv, axis=0)
        vals = vals * w[:, None].astype(vals.dtype)
        return vals.reshape(T, k, d).sum(axis=1)

    def to_help(xt):
        return moe_lib._to_expert_order(xt, order, inv, k)

    def from_help(ys, w):
        return moe_lib._from_expert_order(ys, inv, order, w, k)

    def run(to, frm, cast):
        xs, to_vjp = jax.vjp(to, cast(xt))
        y, from_vjp = jax.vjp(frm, cast(ys), w)
        out = (xs, to_vjp(cast(g_xs))[0], y, *from_vjp(cast(g_y)))
        return [np.asarray(a, np.float32) for a in out]

    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    got = run(to_help, from_help, lambda a: a)
    want = run(to_plain, from_plain, f32)
    plain = run(to_plain, from_plain, lambda a: a)
    for name, g, r, p in zip(("xs", "g_xt", "y", "g_ys", "g_w"),
                             got, want, plain):
        scale = max(float(np.abs(r).max()), 1.0)
        np.testing.assert_allclose(g, r, rtol=0, atol=tol * scale,
                                   err_msg=name)
        assert np.abs(g - r).max() <= np.abs(p - r).max() + tol * scale / 4, \
            name


def test_permutation_vjps_gather_and_never_scatter_add():
    """Both helpers' backward passes are gathers: their VJP jaxprs hold no
    ``scatter-add``, where the plain ``jnp.take`` formula's do."""
    T, E, k, d = 64, 8, 2, 16
    ids = _routing(T, E, k, "uniform", jax.random.PRNGKey(0))
    order, inv, _ = moe_lib._sort_dispatch(ids.reshape(-1), E)
    xt = jnp.ones((T, d))
    ys = jnp.ones((T * k, d))
    w = jnp.ones((T * k,))

    def vjp_jaxpr(f, *primals):
        def pull(*p):
            out, back = jax.vjp(f, *p)
            return back(jnp.ones_like(out))
        return str(jax.make_jaxpr(pull)(*primals))

    to_help = vjp_jaxpr(lambda x: moe_lib._to_expert_order(x, order, inv, k),
                        xt)
    from_help = vjp_jaxpr(
        lambda y, w: moe_lib._from_expert_order(y, inv, order, w, k), ys, w)
    to_take = vjp_jaxpr(lambda x: jnp.take(x, order // k, axis=0), xt)
    from_take = vjp_jaxpr(lambda y: jnp.take(y, inv, axis=0), ys)
    assert "scatter-add" in to_take and "scatter-add" in from_take
    assert "scatter-add" not in to_help and "gather" in to_help
    assert "scatter-add" not in from_help and "gather" in from_help


_SCATTER = re.compile(
    r'"?stablehlo\.scatter"?\(.*?\}\) : \(tensor<([^>]*)>, tensor<([^>]*)>, '
    r'tensor<([^>]*)>\)', re.S)


@lru_cache(maxsize=None)
def lowered_step(arch: str):
    """The jitted train step of ``arch``, reduced and ragged at batch
    2 x 256, built through ``launch.train.setup`` and lowered with
    telemetry on: (StableHLO text, the ``moe.permute`` counts, T·k, d)."""
    from repro import obs
    from repro.launch import train

    args = train.parse_args(
        ["--arch", arch, "--reduced", "--steps", "2", "--batch", "2",
         "--seq", "256", "--dispatch", "ragged"])
    run = train.setup(args)
    batch = next(run["data"])
    run["data"].close()
    ring = obs.RingBufferSink()
    prev = obs.set_telemetry(obs.Telemetry(sinks=[ring]))
    try:
        with run["plan"].mesh:
            text = run["trainer"].train_step.lower(run["state"],
                                                   batch).as_text()
    finally:
        obs.set_telemetry(prev)
    permute = [e["attrs"] for e in ring.events()
               if e["kind"] == "counter" and e["name"] == "moe.permute"]
    arch_ = run["arch"]
    return text, permute, 2 * 256 * arch_.moe.top_k, arch_.d_model


def test_train_step_scatters_no_expert_rows():
    """The ragged train step of granite holds no scatter whose updates are
    (T·k, d) rows: the dispatch and the combine move rows by gathers in both
    passes.  The expert-count bincounts may stay."""
    text, _, Tk, d = lowered_step("granite-moe-3b-a800m")
    updates = [m.group(3) for m in _SCATTER.finditer(text)]
    assert updates, "no scatter found: the pattern no longer parses"
    rows = f"{Tk}x{d}x"
    assert not [u for u in updates if u.startswith(rows)], updates


@pytest.mark.parametrize("arch,counted", [
    ("granite-moe-3b-a800m", True),
    ("moonlight-16b-a3b-ep8", False),
])
def test_permute_counter_counts_the_ragged_path_only(arch, counted):
    """``moe.permute`` counts each traced helper call: a granite step both
    ``op`` values with ``rows`` = T·k; a Moonlight share step, whose held
    rows bypass the helpers, none."""
    _, permute, Tk, _ = lowered_step(arch)
    if not counted:
        assert permute == []
        return
    assert {p["op"] for p in permute} == {"to_expert", "from_expert"}
    assert {p["rows"] for p in permute} == {Tk}
