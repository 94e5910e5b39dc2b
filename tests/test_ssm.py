"""Mamba2/SSD properties: chunking invariance, recurrence equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests need the dev extra
from hypothesis import given, settings, strategies as st

from repro.models.ssm import segsum, ssd_chunked


def _rand_inputs(key, b, l, h, p, g, n):
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, l, g, n)) * 0.5
    C = jax.random.normal(ks[0], (b, l, g, n)) * 0.5
    return x, dt, a, B, C


def _ssd_sequential(x, dt, a, B, C):
    """Token-by-token linear recurrence oracle."""
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    Bh = np.repeat(np.asarray(B), rep, axis=2)
    Ch = np.repeat(np.asarray(C), rep, axis=2)
    xn, dtn, an = map(np.asarray, (x, dt, a))
    state = np.zeros((b, h, p, n))
    ys = np.zeros((b, l, h, p))
    for t in range(l):
        decay = np.exp(dtn[:, t] * an[None, :])  # (b, h)
        upd = np.einsum("bh,bhp,bhn->bhpn", dtn[:, t], xn[:, t], Bh[:, t])
        state = state * decay[..., None, None] + upd
        ys[:, t] = np.einsum("bhpn,bhn->bhp", state, Ch[:, t])
    return ys, state


# (b, l, h, p, n) and the chunk: three chunkings of one sequence, then two
# chunks of 32 and 64 rows with wider heads and states.
SEQUENTIAL_CASES = [
    *(pytest.param((2, 32, 4, 8, 8), c, id=str(c)) for c in (4, 8, 16)),
    pytest.param((1, 64, 4, 16, 8), 32, id="l64-cl32"),
    pytest.param((2, 128, 8, 32, 16), 64, id="l128-cl64"),
]


@pytest.mark.parametrize("shape,chunk", SEQUENTIAL_CASES)
def test_ssd_chunked_matches_sequential(shape, chunk):
    b, l, h, p, n = shape
    x, dt, a, B, C = _rand_inputs(jax.random.PRNGKey(0), b, l, h, p, 1, n)
    y, final = ssd_chunked(x, dt, a, B, C, chunk)
    y_ref, state_ref = _ssd_sequential(x, dt, a, B, C)
    np.testing.assert_allclose(np.asarray(y), y_ref, atol=2e-4)
    np.testing.assert_allclose(np.asarray(final), state_ref, atol=2e-4)


@settings(deadline=None, max_examples=10)
@given(seed=st.integers(0, 2**16))
def test_ssd_chunk_size_invariance(seed):
    """The output must not depend on the chunking."""
    x, dt, a, B, C = _rand_inputs(jax.random.PRNGKey(seed), 1, 24, 2, 4, 1, 4)
    y1, f1 = ssd_chunked(x, dt, a, B, C, 4)
    y2, f2 = ssd_chunked(x, dt, a, B, C, 12)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), atol=2e-4)


def test_ssd_initial_state_continuation():
    """Processing [first half] then [second half with carried state] equals
    one pass — the prefill->decode contract."""
    x, dt, a, B, C = _rand_inputs(jax.random.PRNGKey(1), 1, 32, 2, 4, 1, 4)
    y_full, f_full = ssd_chunked(x, dt, a, B, C, 8)
    y1, f1 = ssd_chunked(x[:, :16], dt[:, :16], a, B[:, :16], C[:, :16], 8)
    y2, f2 = ssd_chunked(
        x[:, 16:], dt[:, 16:], a, B[:, 16:], C[:, 16:], 8, initial_state=f1
    )
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([y1, y2], 1)), np.asarray(y_full), atol=2e-4
    )
    np.testing.assert_allclose(np.asarray(f2), np.asarray(f_full), atol=2e-4)


def test_segsum_definition():
    x = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    s = np.asarray(segsum(x))
    assert s[2, 0] == pytest.approx(2 + 3)
    assert s[3, 1] == pytest.approx(3 + 4)
    assert s[1, 1] == 0.0
    assert np.isneginf(s[0, 1])
