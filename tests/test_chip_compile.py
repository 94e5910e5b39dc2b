"""Ahead-of-time TPU v5e compiles of the main path's kernels at the published
widths of granite-moe-3b-a800m (d_model 1536, 40 experts of d_ff 512,
24/8 heads of 64, seq 4096).

Nothing runs: each test lowers a kernel for a described (not attached) v5e
chip and checks that Mosaic accepted it, which catches tiling, alignment
and VMEM faults that interpret mode cannot see.  The topology is described
inside a fixture, never at import, so that only the worker that runs this
file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.moe_gemm import ops as mm_ops

T_ROWS = 131_072  # 16,384 tokens x top-8: one 4 x 4096 batch's expert rows
E, D, F = 40, 1536, 512
HQ, HKV, DH, S = 24, 8, 64, 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: a cache
    entry for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _ragged_args(one_chip):
    bf = jnp.bfloat16
    return (
        _spec((T_ROWS, D), bf, one_chip),
        _spec((E, D, F), bf, one_chip),
        _spec((E, D, F), bf, one_chip),
        _spec((E, F, D), bf, one_chip),
        _spec((E + 1,), jnp.int32, one_chip),
    )


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_ragged_ffn_forward_compiles(one_chip):
    def fwd(x, wu, wg, wd, offsets):
        return mm_ops.ragged_ffn(x, wu, wg, wd, offsets, interpret=False)

    _assert_mosaic(fwd, *_ragged_args(one_chip))


def test_ragged_ffn_grad_compiles(one_chip):
    def loss(x, wu, wg, wd, offsets):
        y = mm_ops.ragged_ffn(x, wu, wg, wd, offsets, interpret=False)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    _assert_mosaic(jax.grad(loss, argnums=(0, 1, 2, 3)),
                   *_ragged_args(one_chip))


def test_grouped_ffn_forward_compiles(one_chip):
    # Capacity buffers of that batch: C = ceil(16384 * 8 / 40 * 1.25).
    bf = jnp.bfloat16
    args = (
        _spec((E, 4096, D), bf, one_chip),
        _spec((E, D, F), bf, one_chip),
        _spec((E, D, F), bf, one_chip),
        _spec((E, F, D), bf, one_chip),
    )

    def fwd(tokens, wu, wg, wd):
        return mm_ops.grouped_ffn(tokens, wu, wg, wd, interpret=False)

    _assert_mosaic(fwd, *args)


def test_flash_attention_forward_compiles(one_chip):
    bf = jnp.bfloat16
    q = _spec((1, S, HQ, DH), bf, one_chip)
    kv = _spec((1, S, HKV, DH), bf, one_chip)

    def fwd(q, k, v):
        return fa_ops.flash_attention(q, k, v, causal=True, interpret=False)

    _assert_mosaic(fwd, q, kv, kv)


def test_causal_attention_grad_compiles(one_chip):
    """The trainable splash path at the cell's shapes: the forward and the
    fused dq/dkv kernel both lower through Mosaic."""
    bf = jnp.bfloat16
    q = _spec((4, S, HQ, DH), bf, one_chip)
    kv = _spec((4, S, HKV, DH), bf, one_chip)

    def loss(q, k, v):
        out = fa_ops.causal_attention(q, k, v, interpret=False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") >= 2


def test_share_kernels_compile_at_moonlight_widths(one_chip):
    """One chip's share of a Moonlight layer (8 of 64 experts, d_model 2048,
    d_ff 1408 = 11 x 128, 8192 tokens x top-6 rows with a sentinel tail):
    the ragged FFN, forward and backward; and the splash kernel at MLA's
    q/k head of 192 and v head of 128."""
    bf = jnp.bfloat16
    rows, e, d, f = 8192 * 6, 8, 2048, 1408
    args = (_spec((rows, d), bf, one_chip), _spec((e, d, f), bf, one_chip),
            _spec((e, d, f), bf, one_chip), _spec((e, f, d), bf, one_chip),
            _spec((e + 1,), jnp.int32, one_chip))

    def loss(x, wu, wg, wd, offsets):
        y = mm_ops.ragged_ffn(x, wu, wg, wd, offsets, interpret=False)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    _assert_mosaic(jax.grad(loss, argnums=(0, 1, 2, 3)), *args)

    def attn(q, k, v):
        out = fa_ops.causal_attention(q, k, v, interpret=False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    qk = _spec((1, 8192, 16, 192), bf, one_chip)
    v = _spec((1, 8192, 16, 128), bf, one_chip)
    _assert_mosaic(jax.grad(attn, argnums=(0, 1, 2)), qk, qk, v)


def test_share_grad_compiles_at_moonlights_first_chunk(one_chip):
    """The ragged FFN's grad at the first chunk of one chip's share of a
    Moonlight layer as the cell runs it (``moe._held_chunk``: 24,576 rows
    of 8 held experts): the picker's whole-K/N blocks need more VMEM than
    the default scoped limit, so this compiles only with the limit each
    launch sets from its blocks."""
    from repro.models.moe import _held_chunk

    bf = jnp.bfloat16
    rows, e, d, f = _held_chunk(2 * 8192, 6, 8, 64), 8, 2048, 1408
    assert rows == 24_576
    args = (_spec((rows, d), bf, one_chip), _spec((e, d, f), bf, one_chip),
            _spec((e, d, f), bf, one_chip), _spec((e, f, d), bf, one_chip),
            _spec((e + 1,), jnp.int32, one_chip))

    def loss(x, wu, wg, wd, offsets):
        y = mm_ops.ragged_ffn(x, wu, wg, wd, offsets, interpret=False)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") >= 8
