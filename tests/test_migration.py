"""Expert migration: Algorithm 2 properties + function preservation."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Only the property tests need the hypothesis dev extra — everything else
# in this file must still run without it.
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import migration as mig

if HAVE_HYPOTHESIS:
    _property = lambda f: settings(deadline=None, max_examples=40)(
        given(
            E=st.integers(4, 32),
            ep=st.sampled_from([2, 4]),
            seed=st.integers(0, 2**16),
        )(f)
    )
else:
    _property = pytest.mark.skip(reason="hypothesis not installed")


@_property
def test_hill_climb_reduces_imbalance(E, ep, seed):
    E = (E // ep) * ep
    if E < ep:
        return
    rng = np.random.default_rng(seed)
    loads = rng.exponential(1.0, E)
    assignment = np.arange(E, dtype=np.int32)

    def gap(assign):
        e_l = E // ep
        sums = np.zeros(ep)
        np.add.at(sums, assign // e_l, loads)
        return sums.max() - sums.min()

    new_assign, swaps = mig.rebalance_assignment(loads, assignment, ep)
    assert gap(new_assign) <= gap(assignment) + 1e-9
    # group sizes preserved
    e_l = E // ep
    for g in range(ep):
        assert (new_assign // e_l == g).sum() == e_l
    # it is a permutation
    assert sorted(new_assign.tolist()) == list(range(E))


def test_hill_climb_terminates_and_counts():
    loads = np.array([10.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 10.0])
    groups = [[(0, 10.0), (7, 10.0)], [(1, 1.0), (2, 1.0)],
              [(3, 1.0), (4, 1.0)], [(5, 1.0), (6, 1.0)]]
    new_groups, swaps = mig.hill_climb_rebalance(groups, max_iters=100)
    sums = [sum(l for _, l in g) for g in new_groups]
    assert max(sums) - min(sums) < 20.0
    assert 0 < swaps <= 100


def test_permutation_roundtrip():
    rng = np.random.default_rng(0)
    E = 12
    old = np.arange(E, dtype=np.int32)
    new = rng.permutation(E).astype(np.int32)
    perm = mig.permutation_for(old, new)
    # W_new[s] = W_old[perm[s]]; logical expert e must end at new[e]
    W_old = rng.normal(size=(E, 3))
    W_new = W_old[perm]
    for e in range(E):
        np.testing.assert_allclose(W_new[new[e]], W_old[old[e]])


def test_migration_preserves_model_function():
    from repro.configs import get_arch
    from repro.models.model import LanguageModel, init_params
    from repro.sharding import single_device_plan

    arch = get_arch("granite-moe-3b-a800m").reduced()
    arch = arch.replace(
        moe=dataclasses.replace(arch.moe, capacity_factor=16.0)
    )
    plan = single_device_plan(arch)
    with plan.mesh:
        lm = LanguageModel(arch, plan)
        params = init_params(arch, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                  arch.vocab_size)
        batch = {"tokens": toks}
        logits0, _, _ = jax.jit(lm.forward)(params, batch)

        rng = np.random.default_rng(0)
        E = arch.moe.num_experts
        ffn = params["blocks"][0]["ffn"]
        old = np.asarray(ffn["assignment"])
        reps = old.shape[0]
        new = np.stack([rng.permutation(E) for _ in range(reps)]).astype(np.int32)
        perms = np.stack(
            [mig.permutation_for(old[r], new[r]) for r in range(reps)]
        )
        new_ffn = mig.apply_migration_to_tree(ffn, perms)
        new_ffn["assignment"] = jnp.asarray(new)
        blocks = list(params["blocks"])
        blk = dict(blocks[0])
        blk["ffn"] = new_ffn
        blocks[0] = blk
        params2 = {**params, "blocks": tuple(blocks)}
        logits1, _, _ = jax.jit(lm.forward)(params2, batch)
        np.testing.assert_allclose(
            np.asarray(logits0), np.asarray(logits1), atol=1e-4
        )


def test_load_stats_and_trigger():
    stats = mig.LoadStats(num_layers=2, num_experts=8, decay=0.5)
    skewed = np.zeros((2, 8))
    skewed[:, 0] = 100.0
    skewed[:, 1:] = 1.0
    for _ in range(5):
        stats.update(skewed)
    assign = np.tile(np.arange(8, dtype=np.int32), (2, 1))
    imb = stats.imbalance(assign, ep=4)
    assert imb > 1.5
    balanced = np.ones((2, 8))
    stats2 = mig.LoadStats(2, 8)
    stats2.update(balanced)
    assert stats2.imbalance(assign, ep=4) == pytest.approx(1.0, abs=1e-6)


def test_migration_cost_matches_paper_table4():
    """Table IV rows: Mixtral 8x7B -> 2.63 GB, 52.6 ms; DeepSeek-V3 ->
    21 GB, 420 ms.  (The paper's 'GB' column is GiB — 48*8*4096*14336/8
    = 2.818e9 B = 2.625 GiB — and its latency divides that GiB number by
    50, so we compare in the paper's own convention.)"""
    GIB = 2**30
    size, _ = mig.migration_cost(E=8, d_model=4096, d_ffn=14336)
    assert abs(size / GIB - 2.63) < 0.05
    assert abs(size / GIB / 50 * 1e3 - 52.6) < 1.0
    size, _ = mig.migration_cost(E=256, d_model=7168, d_ffn=2048)
    assert abs(size / GIB - 21.0) < 0.1
    assert abs(size / GIB / 50 * 1e3 - 420.0) < 2.0


# ---------------------------------------------------------------------------
# Swap-only blind spot, replication planner, and LoadStats persistence
# ---------------------------------------------------------------------------


def _layer_imbalance(loads, assignment, ep, replicas=None):
    ls = mig.LoadStats(1, len(loads))
    ls.ema[0] = np.asarray(loads, dtype=np.float64)
    reps = None if replicas is None else np.asarray(replicas)[None, :]
    return ls.imbalance(np.asarray(assignment)[None, :], ep, reps)


def test_plan_layer_noop_on_balanced():
    E, ep = 8, 4
    loads = np.full(E, 10.0)
    assign = np.arange(E, dtype=np.int32)
    reps = np.full(2, E, dtype=np.int32)  # all channels free
    new_a, new_r, perm, swaps = mig.plan_layer(loads, assign, reps, ep)
    assert swaps == 0
    assert np.array_equal(new_a, assign)
    assert np.array_equal(perm, np.arange(E))
    assert np.array_equal(new_r, reps)  # no channel engages on balance


def test_plan_layer_converges_on_mild_skew():
    """No expert exceeds fair share -> swaps alone reach near-perfect
    balance (the regime Algorithm 2 is built for)."""
    ep = 4
    loads = np.array([30, 25, 10, 15, 22, 18, 28, 12.0])
    assign = np.arange(8, dtype=np.int32)
    pre = _layer_imbalance(loads, assign, ep)
    new_a, new_r, _, swaps = mig.plan_layer(loads, assign, None, ep)
    post = _layer_imbalance(loads, new_a, ep)
    assert new_r is None
    assert swaps > 0
    assert post < pre
    assert post <= 1.15  # near the floor of 1.0
    assert mig.swap_floor(loads, ep) == 1.0


def test_swap_only_cannot_beat_dominant_expert_floor():
    """One expert above a group's fair share: swap-only bottoms out at
    max(load_e)/fair_share (the tentpole's motivating bug), while one
    replica channel splits the hot expert's load and beats that floor."""
    ep = 4
    loads = np.array([100, 5, 5, 5, 5, 5, 5, 5.0])
    assign = np.arange(8, dtype=np.int32)
    floor = mig.swap_floor(loads, ep)
    assert floor > 2.5  # 100 / (135/4)

    new_a, _, _, _ = mig.plan_layer(loads, assign, None, ep)
    assert _layer_imbalance(loads, new_a, ep) >= floor - 1e-9

    reps = np.full(2, 8, dtype=np.int32)
    rep_a, rep_r, _, _ = mig.plan_layer(loads, assign, reps, ep)
    assert (rep_r < 8).sum() >= 1  # hot expert got a channel
    assert _layer_imbalance(loads, rep_a, ep, rep_r) < floor


def test_plan_replication_hysteresis():
    """Channels engage above fair share, are HELD in the cool-down band
    (no flapping), and release only below release_factor * fair."""
    E, ep = 8, 4
    free = np.full(2, E, dtype=np.int32)
    hot = np.array([100, 5, 5, 5, 5, 5, 5, 5.0])
    held = mig.plan_replication(hot, free, ep)
    assert 0 in held

    # 8.75 < 10 < 11.67: below the acquire threshold, above release.
    warm = np.array([10, 5, 5, 5, 5, 5, 5, 5.0])
    assert 0 in mig.plan_replication(warm, held, ep)  # held channel stays
    assert 0 not in mig.plan_replication(warm, free, ep)  # no new acquire

    cold = np.full(E, 5.0)
    released = mig.plan_replication(cold, held, ep)
    assert np.all(released == E)


def test_load_stats_state_roundtrip_bit_exact():
    rng = np.random.default_rng(0)
    ls = mig.LoadStats(3, 8, decay=0.85)
    for _ in range(5):
        ls.update(rng.integers(0, 100, size=(3, 8)))
    state = ls.to_state()
    ls2 = mig.LoadStats.from_state(state)
    assert ls.ema.tobytes() == ls2.ema.tobytes()  # bit-exact, not approx
    assert (ls2.steps, ls2.decay) == (ls.steps, ls.decay)

    with pytest.raises(ValueError):
        mig.LoadStats(2, 8).load_state(state)
