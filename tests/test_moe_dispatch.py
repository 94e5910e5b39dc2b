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
