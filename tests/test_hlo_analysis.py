"""Loop-aware HLO cost estimator tests (roofline inputs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch import hlo_analysis as H


def test_scan_matmul_flops_trip_aware():
    def f(w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        return jax.lax.scan(body, jnp.ones((64, 64)), w)[0]

    c = jax.jit(f).lower(
        jax.ShapeDtypeStruct((12, 64, 64), jnp.float32)
    ).compile()
    cost = H.analyze_hlo(c.as_text(), 1)
    assert cost.flops == pytest.approx(12 * 2 * 64**3, rel=0.02)


def test_nested_scan_multiplies():
    def f(w):
        def outer(c, wo):
            def inner(ci, wi):
                return ci @ wi, None
            return jax.lax.scan(inner, c, wo)[0], None
        return jax.lax.scan(outer, jnp.ones((32, 32)), w)[0]

    c = jax.jit(f).lower(
        jax.ShapeDtypeStruct((3, 4, 32, 32), jnp.float32)
    ).compile()
    cost = H.analyze_hlo(c.as_text(), 1)
    assert cost.flops == pytest.approx(12 * 2 * 32**3, rel=0.05)


def test_loop_free_matches_xla_cost_analysis():
    f = jax.jit(lambda a, b: jnp.tanh(a @ b))
    c = f.lower(
        jax.ShapeDtypeStruct((256, 128), jnp.float32),
        jax.ShapeDtypeStruct((128, 512), jnp.float32),
    ).compile()
    cost = H.analyze_hlo(c.as_text(), 1)
    xla = c.cost_analysis()["flops"]
    assert cost.flops == pytest.approx(xla, rel=0.05)


def test_collectives_in_scan(tmp_path):
    import subprocess, sys, os, textwrap

    # needs >1 devices; run in a child
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.sharding import host_mesh
        from repro.launch import hlo_analysis as H
        mesh = host_mesh((8,), ('x',))
        def f(xs):
            def body(c, x):
                return c + jax.lax.psum(x, 'x'), None
            return jax.lax.scan(body, jnp.zeros(1024), xs)[0]
        g = jax.shard_map(f, mesh=mesh, in_specs=P(None, None),
                          out_specs=P(), check_vma=False)
        c = jax.jit(g).lower(
            jax.ShapeDtypeStruct((10, 1024), jnp.float32)).compile()
        s = H.analyze_hlo(c.as_text(), 8)
        assert s.coll_counts['all-reduce'] == 10.0, s.coll_counts
        assert s.coll_result_bytes['all-reduce'] == 40960.0
        print('OK')
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=str(
                           __import__('pathlib').Path(__file__).parents[1]))
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr


def test_wire_models():
    assert H._wire_estimate("all-reduce", 100, 4) == pytest.approx(150.0)
    assert H._wire_estimate("all-gather", 100, 4) == pytest.approx(75.0)
    assert H._wire_estimate("all-to-all", 100, 4) == pytest.approx(75.0)
    assert H._wire_estimate("reduce-scatter", 100, 4) == pytest.approx(300.0)
    assert H._wire_estimate("collective-permute", 100, 1) == 100.0
    assert H._wire_estimate("all-reduce", 100, 1) == 0.0
