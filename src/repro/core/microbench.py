"""Micro-benchmarking suite (paper §IV): measure the platform, feed the
resource model.

On Frontier the paper measures attention kernels (Fig 3), expert GEMMs
(Fig 4) and all-to-all bandwidth (Fig 5).  On this container the measurable
platform is the host CPU + XLA host devices; the POINT of these functions is
the mechanism (measured curves parameterize the performance estimator), and
the CPU measurements genuinely exhibit the paper's qualitative phenomena —
most importantly the tall-and-skinny GEMM efficiency collapse of Fig 4.
"""

from __future__ import annotations

import time
import types
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs


def _time_fn(fn, *args, iters: int = 5, warmup: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def gemm_throughput(m: int, k: int, n: int, dtype=jnp.float32) -> Tuple[float, float]:
    """Returns (seconds, GFLOP/s) for an (m,k)x(k,n) matmul."""
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (m, k), dtype)
    b = jax.random.normal(key, (k, n), dtype)
    f = jax.jit(lambda x, y: x @ y)
    sec = _time_fn(f, a, b)
    return sec, 2.0 * m * k * n / sec / 1e9


def expert_gemm_curve(
    d_model: int = 512, tokens: int = 4096,
    ffn_dims: Tuple[int, ...] = (32, 64, 128, 256, 512, 1024, 2048),
) -> List[Dict]:
    """Fig 4 analog: throughput of the expert GEMM as d_ffn shrinks
    (fine-grained experts) at a fixed token budget."""
    rows = []
    peak = max(
        gemm_throughput(2048, 2048, 2048)[1], 1e-9
    )
    for f in ffn_dims:
        sec, gflops = gemm_throughput(tokens, d_model, f)
        rows.append(
            {"d_ffn": f, "seconds": sec, "gflops": gflops,
             "efficiency": gflops / peak}
        )
    return rows


def attention_curve(
    d_model: int = 512, heads: int = 8,
    seq_lens: Tuple[int, ...] = (128, 256, 512, 1024),
) -> List[Dict]:
    """Fig 3 analog: attention throughput vs sequence length."""
    from repro.models.layers import attention

    rows = []
    hd = d_model // heads
    key = jax.random.PRNGKey(0)
    for s in seq_lens:
        q = jax.random.normal(key, (1, s, heads, hd), jnp.float32)
        f = jax.jit(lambda q_: attention(q_, q_, q_))
        sec = _time_fn(f, q)
        flops = 4.0 * s * s * d_model  # QK^T + AV
        rows.append({"seq": s, "seconds": sec, "gflops": flops / sec / 1e9})
    return rows


def a2a_bandwidth_curve(msg_sizes: Tuple[int, ...] = (2**14, 2**17, 2**20)) -> List[Dict]:
    """Fig 5 analog: all-to-all wall time vs message size on however many
    host devices exist (mechanism demo; 1 device => local copy baseline)."""
    from jax.sharding import PartitionSpec as P

    n = len(jax.devices())
    rows = []
    if n == 1:
        for m in msg_sizes:
            x = jnp.zeros((1, m // 4), jnp.float32)
            f = jax.jit(lambda t: t + 1)
            sec = _time_fn(f, x)
            rows.append({"ranks": 1, "msg_bytes": m, "seconds": sec,
                         "gbps": m / sec / 1e9})
        return rows
    from repro.sharding import host_mesh

    mesh = host_mesh((n,), ("x",))

    def f(x):
        return jax.lax.all_to_all(x, "x", 0, 0, tiled=True)

    g = jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                      check_vma=False)
    )
    for m in msg_sizes:
        rows_per = max(m // 4 // n, 1)
        x = jnp.zeros((n * n, rows_per), jnp.float32)
        sec = _time_fn(g, x)
        bytes_moved = x.size * 4 * (n - 1) / n
        rows.append({"ranks": n, "msg_bytes": m, "seconds": sec,
                     "gbps": bytes_moved / sec / 1e9})
    return rows


def a2a_overlap_layer(
    ep: int, rows: int, d: int, d_ff: int,
    algo: str = "flat", chunks: int = 1, g1: int = None,
    part: str = "layer",
):
    """Build one capacity-layout MoE layer pass over ``ep`` host devices:
    dispatch a2a -> expert FFN -> combine a2a, software-pipelined through
    ``halo.overlapped_a2a`` exactly like models.moe's chunked path (same
    transport, same unrolled double-buffered loop) but with a synthetic
    one-expert FFN so the probe isolates the transport/compute pipeline.

    ``part`` selects what the jitted function runs — "layer" (the full
    chunked pipeline), "a2a" (one monolithic dispatch transfer only) or
    "ffn" (the expert GEMMs only) — the latter two are the calibration
    points benchmarks/a2a_overlap_bench.py fits its analytical model from.

    Returns ``(jitted_fn, mesh, args)``; time with ``_time_fn(f, *args)``
    under ``with mesh:``.
    """
    from jax.sharding import PartitionSpec as P

    from repro.core import halo
    from repro.sharding import host_mesh

    assert algo in ("flat", "halo"), algo
    assert ep <= len(jax.devices()), (ep, len(jax.devices()))
    mesh = host_mesh((ep,), ("ep",))
    # hierarchical_all_to_all only reads plan.mesh; a full MeshPlan would
    # drag in an arch, so hand it a one-field stand-in.
    shim = types.SimpleNamespace(mesh=mesh)
    if algo == "halo":
        a2a = lambda t: halo.hierarchical_all_to_all(t, shim, g1=g1)
    else:
        a2a = halo.flat_all_to_all
    slices = halo.chunk_slices(rows, chunks)

    def layer(x, wu, wd):
        def ffn(rx):
            h = rx.reshape(ep * rx.shape[1], d)
            h = jnp.maximum(h @ wu, 0.0) @ wd
            return h.reshape(ep, rx.shape[1], d)

        if part == "a2a":
            return a2a(x)
        if part == "ffn":
            return ffn(x)

        def get_chunk(start, size):
            return x[:, start:start + size]

        def compute(rx, start, size):
            return ffn(rx)

        outs = halo.overlapped_a2a(a2a, get_chunk, compute, slices)
        return jnp.concatenate(outs, axis=1)

    f = jax.jit(jax.shard_map(
        layer, mesh=mesh, in_specs=(P("ep"), P(), P()), out_specs=P("ep"),
        check_vma=False,
    ))
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (ep * ep * rows, d), jnp.float32)
    x = x.reshape(ep * ep, rows, d)
    wu = jax.random.normal(key, (d, d_ff), jnp.float32) * 0.01
    wd = jax.random.normal(key, (d_ff, d), jnp.float32) * 0.01
    return f, mesh, (x, wu, wd)


def measure_a2a_overlap(
    ep: int, rows: int, d: int, d_ff: int,
    algo: str = "flat", chunks: int = 1, g1: int = None,
    part: str = "layer", iters: int = 3, warmup: int = 1,
) -> float:
    """Seconds per call of one ``a2a_overlap_layer`` configuration."""
    f, mesh, args = a2a_overlap_layer(
        ep, rows, d, d_ff, algo=algo, chunks=chunks, g1=g1, part=part
    )
    with mesh:
        t = _time_fn(f, *args, iters=iters, warmup=warmup)
    # One span per measurement (not per iter): duration = steady-state
    # seconds/call, the number the drift tracker compares against the comm
    # model.  Recorded post-hoc so the timed loop itself stays unobserved.
    obs.get_telemetry().record_span(
        "a2a.layer", t, ep=ep, rows=rows, d=d, d_ff=d_ff, algo=algo,
        chunks=chunks, part=part,
    )
    return t
