"""ops/selective_scan.py: the chunked selective state-space scan against the
step-by-step recurrence (values and all six gradients, chunk sizes that
divide T and that do not, T shorter than a chunk, the state carried across
chunks), the causal depthwise convolution against a shifted sum, and the
shape discipline: no [T, E, N] array in the gradient's jaxpr."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import selective_scan as ss

ROWS, T, E, N = 2, 37, 24, 4


def stepwise(u, delta, A, B, C, D):
    """h_t = exp(delta_t A) h_{t-1} + (delta_t u_t) (x) B_t; y_t = h_t . C_t
    + D u_t, one step at a time, state [rows, E, N]."""
    def step(h, xs):
        d_t, u_t, b_t, c_t = xs
        h = (jnp.exp(d_t[:, :, None] * A[None]) * h
             + (d_t * u_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.einsum("ren,rn->re", h, c_t) + D * u_t

    h0 = jnp.zeros((u.shape[0], u.shape[2], A.shape[1]))
    h, y = jax.lax.scan(step, h0, tuple(jnp.moveaxis(x, 1, 0)
                                        for x in (delta, u, B, C)))
    return jnp.moveaxis(y, 0, 1), h


@pytest.fixture(scope="module")
def case():
    k = jax.random.split(jax.random.PRNGKey(0), 7)
    u = jax.random.normal(k[0], (ROWS, T, E))
    delta = jax.nn.softplus(jax.random.normal(k[1], (ROWS, T, E)))
    A = -jnp.exp(jax.random.normal(k[2], (E, N)))
    B = jax.random.normal(k[3], (ROWS, T, N))
    C = jax.random.normal(k[4], (ROWS, T, N))
    D = jax.random.normal(k[5], (E,))
    weight = jax.random.normal(k[6], (ROWS, T, E))
    return (u, delta, A, B, C, D), weight


@pytest.mark.parametrize("chunk", [1, 8, 10, 37, 64])
def test_chunked_scan_is_the_recurrence(case, chunk, monkeypatch):
    """8 and 10 leave a tail (37 = 4 x 8 + 5 = 3 x 10 + 7), 37 is one whole
    chunk, 64 is longer than the sequence; 1 carries the state across every
    step."""
    monkeypatch.setattr(ss, "SCAN_CHUNK", chunk)
    args, weight = case
    want, grads_want = jax.value_and_grad(
        lambda *a: jnp.sum(stepwise(*a)[0] * weight), argnums=range(6))(*args)
    got, grads = jax.value_and_grad(
        lambda *a: jnp.sum(ss.selective_scan(*a) * weight),
        argnums=range(6))(*args)
    np.testing.assert_allclose(ss.selective_scan(*args),
                               stepwise(*args)[0], rtol=2e-5, atol=2e-5)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name, a, b in zip("u delta A B C D".split(), grads, grads_want):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * scale,
                                   err_msg=name)


def test_the_state_is_carried_across_chunks(case, monkeypatch):
    """The states saved at the chunk boundaries are the recurrence's own:
    the start of chunk c is the state after c x chunk steps."""
    monkeypatch.setattr(ss, "SCAN_CHUNK", 8)
    args, _ = case
    _, starts = ss._forward(*args)
    assert starts.shape == (5, ROWS, N, E)
    assert not np.any(np.asarray(starts[0]))
    for c in (1, 3, 4):
        cut = tuple(x[:, :8 * c] if x.ndim == 3 else x for x in args)
        np.testing.assert_allclose(jnp.swapaxes(starts[c], 1, 2),
                                   stepwise(*cut)[1], rtol=2e-5, atol=2e-5)


def test_low_precision_inputs_run_the_recurrence_in_float32(case):
    args, _ = case
    low = tuple(x.astype(jnp.bfloat16) if x.ndim == 3 else x for x in args)
    y = ss.selective_scan(*low)
    assert y.dtype == jnp.bfloat16
    exact = stepwise(*(x.astype(jnp.float32) for x in low))[0]
    # one rounding of the result, none inside the recurrence
    np.testing.assert_allclose(y.astype(jnp.float32), exact, rtol=1e-2,
                               atol=1e-2)
    grads = jax.grad(lambda *a: jnp.sum(ss.selective_scan(*a).astype(
        jnp.float32)), argnums=(0, 1, 3, 4))(*low)
    assert all(g.dtype == jnp.bfloat16 for g in grads)


def _shapes(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.update(tuple(v.aval.shape) for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _shapes(sub, out)
    return out


def test_no_array_of_the_whole_sequence_times_the_state(case, monkeypatch):
    """T 64 in chunks of 8: the gradient's jaxpr holds [8, rows, N, E]
    arrays (a chunk's) and the 8 boundary states, never T x E x N."""
    monkeypatch.setattr(ss, "SCAN_CHUNK", 8)
    k = jax.random.split(jax.random.PRNGKey(1), 5)
    rows, T_, E_, N_ = 1, 64, 24, 4
    u = jax.random.normal(k[0], (rows, T_, E_))
    delta = jax.nn.softplus(jax.random.normal(k[1], (rows, T_, E_)))
    B = jax.random.normal(k[2], (rows, T_, N_))
    C = jax.random.normal(k[3], (rows, T_, N_))
    A, D = case[0][2], case[0][5]
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ss.selective_scan(*a)), argnums=range(6)))(
            u, delta, A, B, C, D)
    shapes = _shapes(jaxpr.jaxpr, set())
    whole = T_ * E_ * N_
    big = [s for s in shapes if int(np.prod(s)) >= whole]
    assert not big, big
    assert (8, rows, N_, E_) in shapes    # a chunk's states; the boundaries


def shifted_sum(u, w, b):
    T_ = u.shape[1]
    padded = jnp.pad(u, ((0, 0), (w.shape[0] - 1, 0), (0, 0)))
    return b + sum(w[k] * padded[:, k:k + T_] for k in range(w.shape[0]))


@pytest.mark.parametrize("taps", [4, 1])
def test_causal_conv_is_a_shifted_sum(case, taps):
    (u, *_), weight = case
    k = jax.random.split(jax.random.PRNGKey(2), 2)
    w = jax.random.normal(k[0], (taps, E))
    b = jax.random.normal(k[1], (E,))
    np.testing.assert_allclose(ss.causal_conv1d(u, w, b),
                               shifted_sum(u, w, b), rtol=1e-6, atol=1e-6)
    got = jax.grad(lambda *a: jnp.sum(ss.causal_conv1d(*a) * weight),
                   argnums=(0, 1, 2))(u, w, b)
    want = jax.grad(lambda *a: jnp.sum(shifted_sum(*a) * weight),
                    argnums=(0, 1, 2))(u, w, b)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(a, b_, rtol=1e-5, atol=1e-5)
    # causal: the output at t does not move with the input after t
    later = u.at[:, 20:].add(1.0)
    np.testing.assert_array_equal(ss.causal_conv1d(later, w, b)[:, :20],
                                  ss.causal_conv1d(u, w, b)[:, :20])
