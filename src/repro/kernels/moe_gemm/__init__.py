"""Grouped expert GEMM kernels: ``ops`` (public wrappers) and ``ref``
(pure-jnp oracle for tests)."""
