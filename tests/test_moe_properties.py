"""Property-based tests (hypothesis) on MoE dispatch & routing invariants."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests need the dev extra
from hypothesis import given, settings, strategies as st

from repro.models.moe import _dispatch_indices, _route, _sort_dispatch
from repro.configs.base import MoECfg


@settings(deadline=None, max_examples=30)
@given(
    T=st.integers(4, 64),
    E=st.integers(2, 16),
    k=st.integers(1, 4),
    cap=st.integers(1, 64),
    seed=st.integers(0, 2**16),
)
def test_dispatch_slots_unique_and_bounded(T, E, k, cap, seed):
    k = min(k, E)
    key = jax.random.PRNGKey(seed)
    top_i = jax.random.randint(key, (T, k), 0, E)
    top_w = jax.nn.softmax(jax.random.normal(key, (T, k)))
    flat_e, pos, keep, flat_w = _dispatch_indices(top_i, top_w, E, cap)
    flat_e, pos, keep = map(np.asarray, (flat_e, pos, keep))
    # kept slots are within capacity
    assert (pos[keep] < cap).all() and (pos[keep] >= 0).all()
    # (expert, slot) pairs are unique among kept entries
    pairs = set()
    for e, p, kp in zip(flat_e, pos, keep):
        if kp:
            assert (e, p) not in pairs
            pairs.add((e, p))
    # per-expert kept count never exceeds capacity
    for e in range(E):
        assert ((flat_e == e) & keep).sum() <= cap


@settings(deadline=None, max_examples=20)
@given(
    T=st.integers(4, 32),
    E=st.integers(2, 8),
    k=st.integers(1, 3),
    seed=st.integers(0, 2**16),
)
def test_route_weights_normalized(T, E, k, seed):
    k = min(k, E)
    moe = MoECfg(num_experts=E, top_k=k, d_ff=8)
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (T, 16))
    wr = jax.random.normal(key, (16, E))
    top_w, top_i, probs, logits = _route(x, wr, moe)
    top_w = np.asarray(top_w)
    np.testing.assert_allclose(top_w.sum(-1), 1.0, atol=1e-5)
    assert (top_w >= 0).all()
    # top-k ids index the largest probabilities
    probs = np.asarray(probs)
    for t in range(T):
        chosen = set(np.asarray(top_i)[t].tolist())
        topk_true = set(np.argsort(-probs[t])[:k].tolist())
        assert chosen == topk_true


def test_high_capacity_drops_nothing():
    """With cf >= E/k coverage every assignment is kept."""
    T, E, k = 32, 4, 2
    key = jax.random.PRNGKey(0)
    top_i = jax.random.randint(key, (T, k), 0, E)
    top_w = jnp.ones((T, k)) / k
    flat_e, pos, keep, _ = _dispatch_indices(top_i, top_w, E, capacity=T * k)
    assert bool(jnp.all(keep))


@settings(deadline=None, max_examples=25)
@given(
    T=st.integers(4, 64),
    E=st.integers(1, 16),
    k=st.integers(1, 4),
    seed=st.integers(0, 2**16),
)
def test_sort_dispatch_is_inverse_consistent(T, E, k, seed):
    """Sort-based dispatch invariants: offsets are prefix sums of the true
    per-expert counts, the sorted layout is nondecreasing in expert id, and
    order/inv are mutually inverse permutations."""
    k = min(k, E)
    top_i = jax.random.randint(jax.random.PRNGKey(seed), (T, k), 0, E)
    flat_e = top_i.reshape(-1)
    order, inv, offsets = _sort_dispatch(flat_e, E)
    order, inv, offsets = map(np.asarray, (order, inv, offsets))
    fe = np.asarray(flat_e)
    assert offsets[0] == 0 and offsets[-1] == T * k
    assert (np.diff(offsets) == np.bincount(fe, minlength=E)).all()
    sorted_e = fe[order]
    assert (np.diff(sorted_e) >= 0).all()
    assert (order[inv] == np.arange(T * k)).all()
    assert (inv[order] == np.arange(T * k)).all()
    # every expert's segment holds exactly its rows
    for e in range(E):
        assert (sorted_e[offsets[e]:offsets[e + 1]] == e).all()


@settings(deadline=None, max_examples=8)
@given(seed=st.integers(0, 2**16))
def test_ragged_equals_capacity_when_nothing_drops(seed):
    """On loads where capacity mode drops nothing (cf sized to worst case),
    ragged dispatch must reproduce its outputs AND grads exactly — same
    math, different data layout.  (Deterministic-seed variants of this and
    the drop/degenerate-skew properties run unconditionally in
    tests/test_moe_dispatch.py; this is the randomized sweep.)"""
    from test_moe_dispatch import check_parity_no_drops, moe_setup

    arch, plan, ffn = moe_setup()
    check_parity_no_drops(arch, plan, ffn, seed)


def test_moe_output_matches_dense_oracle():
    """MoE layer output == direct per-token expert evaluation (no drops)."""
    from repro.configs import get_arch
    from repro.models.model import LanguageModel, init_params
    from repro.models import moe as moe_lib
    from repro.sharding import single_device_plan

    arch = get_arch("granite-moe-3b-a800m").reduced()
    arch = arch.replace(
        moe=dataclasses.replace(arch.moe, capacity_factor=16.0)
    )
    plan = single_device_plan(arch)
    with plan.mesh:
        params = init_params(arch, jax.random.PRNGKey(0))
        ffn = params["blocks"][0]["ffn"]
        layer0 = jax.tree.map(lambda p: p[0], ffn)
        key = jax.random.PRNGKey(3)
        x = jax.random.normal(key, (2, 16, arch.d_model)) * 0.5

        y, _ = jax.jit(
            lambda p, h: moe_lib.moe_ffn(p, h, arch, plan)
        )(layer0, x)

        # oracle: softmax-topk routing, dense expert evaluation
        xt = np.asarray(x).reshape(-1, arch.d_model)
        wr = np.asarray(layer0["w_router"], np.float32)
        probs = jax.nn.softmax(jnp.asarray(xt, jnp.float32) @ wr, axis=-1)
        top_w, top_i = jax.lax.top_k(probs, arch.moe.top_k)
        top_w = np.asarray(top_w / top_w.sum(-1, keepdims=True))
        top_i = np.asarray(top_i)
        wu = np.asarray(layer0["w_up"])
        wg = np.asarray(layer0["w_gate"])
        wd = np.asarray(layer0["w_down"])
        expect = np.zeros_like(xt)
        for t in range(xt.shape[0]):
            for j in range(arch.moe.top_k):
                e = top_i[t, j]
                h = jax.nn.silu(xt[t] @ wg[e]) * (xt[t] @ wu[e])
                expect[t] += top_w[t, j] * np.asarray(h @ wd[e])
        np.testing.assert_allclose(
            np.asarray(y).reshape(-1, arch.d_model), expect, atol=2e-3
        )
