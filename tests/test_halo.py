"""HALO hierarchical all-to-all == flat oracle, as a property.

The halo module's contract is that ``hierarchical_all_to_all`` is
bit-for-bit interchangeable with ``lax.all_to_all`` (flat) for EVERY
factorization ep = g1 x M — values AND gradients (the collective is linear;
its transpose must be the same collective reversed).  This module sweeps
ep in {2, 4, 8} x all proper g1 divisors on real host-device meshes in a
re-exec'd child (8 forced host devices, like test_multidevice), and
property-tests the pure chunk geometry helpers directly (with randomized
hypothesis sweeps when the dev extra is installed).
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core import halo

# ---------------------------------------------------------------------------
# Pure chunk geometry (no devices needed)
# ---------------------------------------------------------------------------


def _check_slices(total, K):
    slices = halo.chunk_slices(total, K)
    assert len(slices) <= max(K, 1)
    if total == 0:
        assert slices == [(0, 0)]
        return slices
    # exact disjoint cover in order
    pos = 0
    for start, size in slices:
        assert start == pos and size > 0
        pos += size
    assert pos == total
    # only the tail chunk may be short
    sizes = [s for _, s in slices]
    assert all(s == sizes[0] for s in sizes[:-1])
    assert sizes[-1] <= sizes[0]
    return slices


def test_chunk_slices_deterministic_sweep():
    for total in (0, 1, 2, 3, 7, 8, 16, 17, 64, 100):
        for K in (1, 2, 3, 4, 8, 200):
            _check_slices(total, K)


def test_chunk_slices_k1_is_monolithic():
    assert halo.chunk_slices(37, 1) == [(0, 37)]


def test_chunk_slices_tail():
    # K=3 over 16 rows: ceil -> 6,6,4 (only the tail is short)
    assert halo.chunk_slices(16, 3) == [(0, 6), (6, 6), (12, 4)]


def test_chunk_slices_degenerates_to_single_rows():
    assert halo.chunk_slices(3, 8) == [(0, 1), (1, 1), (2, 1)]


def test_pick_inner_divides():
    for ep in (2, 4, 8, 16, 64):
        g1 = halo._pick_inner(ep)
        assert ep % g1 == 0 and 1 <= g1 <= 4


def test_group_partitions():
    for ep, g1 in ((4, 2), (8, 2), (8, 4)):
        lanes = halo.lane_groups(ep, g1)
        nodes = halo.node_groups(ep, g1)
        for groups, size in ((lanes, g1), (nodes, ep // g1)):
            flat = sorted(r for g in groups for r in g)
            assert flat == list(range(ep))
            assert all(len(g) == size for g in groups)


try:
    from hypothesis import given, settings, strategies as st

    @settings(deadline=None, max_examples=200)
    @given(st.integers(0, 4096), st.integers(1, 64))
    def test_chunk_slices_property(total, K):
        _check_slices(total, K)
except ImportError:  # hypothesis is a dev extra; deterministic sweep above
    pass


# ---------------------------------------------------------------------------
# Device-mesh parity (child re-exec with 8 forced host devices)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def child_results():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).resolve())],
        capture_output=True, text=True, env=env, timeout=1200,
    )
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULTS ")][-1]
    return json.loads(line[len("RESULTS "):])


def test_halo_value_parity_all_factorizations(child_results):
    keys = [k for k in child_results if k.startswith("val_")]
    assert keys, child_results
    for k in keys:
        assert child_results[k], k


def test_halo_gradient_parity_all_factorizations(child_results):
    keys = [k for k in child_results if k.startswith("grad_")]
    assert keys, child_results
    for k in keys:
        assert child_results[k], k


def _child_main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.sharding import MeshPlan, host_mesh

    assert len(jax.devices()) == 8, jax.devices()
    results = {}
    R, d = 3, 5
    for ep in (2, 4, 8):
        mesh = host_mesh((ep, 8 // ep), ("ep", "other"))
        plan = MeshPlan(mesh=mesh, ep=ep, tp=1, dp_axes=("other",))
        xg = jax.random.normal(jax.random.PRNGKey(ep), (ep * ep, R, d))

        def run(fn):
            return jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=P("ep", None, None),
                out_specs=P("ep", None, None), check_vma=False,
            ))(xg)

        def grad_of(fn):
            def loss(x):
                y = jax.shard_map(
                    fn, mesh=mesh, in_specs=P("ep", None, None),
                    out_specs=P("ep", None, None), check_vma=False,
                )(x)
                return jnp.sum(jnp.sin(y) * jnp.arange(y.size).reshape(y.shape))

            return jax.jit(jax.grad(loss))(xg)

        flat_v = run(halo.flat_all_to_all)
        flat_g = grad_of(halo.flat_all_to_all)
        # g1=None exercises the auto _pick_inner path; proper divisors the
        # explicit factorizations (ep=2 has none -> auto falls back to flat).
        g1s = [None] + [g for g in range(2, ep) if ep % g == 0]
        for g1 in g1s:
            fn = lambda xl, g=g1: halo.hierarchical_all_to_all(xl, plan, g1=g)
            tag = f"ep{ep}_g1{'auto' if g1 is None else g1}"
            results[f"val_{tag}"] = bool(np.allclose(
                np.asarray(flat_v), np.asarray(run(fn)), atol=1e-6))
            results[f"grad_{tag}"] = bool(np.allclose(
                np.asarray(flat_g), np.asarray(grad_of(fn)), atol=1e-6))
    print("RESULTS " + json.dumps(results))


if __name__ == "__main__":
    _child_main()
