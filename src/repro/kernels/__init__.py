"""Pallas TPU kernels for the compute hot-spots the paper identifies:

* ``moe_gemm``        -- grouped (per-expert) GEMM; the tall-and-skinny
                         regime of fine-grained MoE (paper Fig 4)
* ``flash_attention`` -- block-tiled attention (paper SSIV-A benchmarks it);
                         ``ops.causal_attention`` trains causal attention
                         through JAX's block-sparse TPU splash kernel
* ``ssd``             -- Mamba2 SSD intra-chunk kernel (mamba2/jamba archs)

Each kernel ships with ``ops.py`` (the jit'd public wrapper with an
``interpret`` switch) and ``ref.py`` (pure-jnp oracle, called only by tests
and benchmarks) and is swept against the oracle over shapes/dtypes in
tests/.
"""

import jax


def interpret_mode() -> bool:
    """Default of every ``ops.py`` ``interpret`` switch.

    Interpret mode exists for the CPU backend, where the tests run the
    kernel bodies.  On a TPU the kernels always compile through Mosaic, and
    a backend with neither raises instead of quietly interpreting.
    """
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel path for backend {backend!r}")
