"""ops/ssd.py: the chunked state-space duality against the step-by-step
recurrence of ``benchmark/reference/granite_hybrid.ssd`` (which imports
nothing of the program) — values and every input's gradient in float32 to
1e-5, over chunks of 8 and 32, sequences of three chunks and ones that
leave a tail, decays near 0 and near 1, one group of B and C for every head
and groups of two heads — bf16 inputs at a stated band, the states kept at
the segment boundaries, and the shape discipline: nothing of size T x N x P
in the gradient's jaxpr."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import granite_hybrid as reference
from deepspeed_tpu.ops import delta_rule as dr
from deepspeed_tpu.ops import ssd

ROWS, H, P, N = 2, 4, 8, 16
NAMES = "x dt A B C D".split()


def case(T, decay="mixed", seed=0, groups=2):
    """Inputs as the mixer makes them: ``dt`` > 0 (a softplus), ``A`` < 0,
    B and C of ``groups`` groups; and a weight for the output.  ``decay``:
    ``near0`` (``exp(dt A)`` ~ 1e-7: the state is all but wiped each step),
    ``near1`` (~ 0.999: it barely fades), ``mixed``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (ROWS, T, H, P))
    rate = {"near0": 16.0, "near1": 1e-3, "mixed": 0.5}[decay]
    dt = jax.nn.softplus(jax.random.normal(ks[1], (ROWS, T, H)))
    A = -rate * jnp.exp(jax.random.uniform(ks[2], (H,), minval=-0.5,
                                           maxval=0.5))
    B = jax.random.normal(ks[3], (ROWS, T, groups, N)) / np.sqrt(N)
    C = jax.random.normal(ks[4], (ROWS, T, groups, N)) / np.sqrt(N)
    D = jax.random.normal(ks[5], (H,))
    weight = jax.random.normal(ks[6], (ROWS, T, H, P))
    return (x, dt, A, B, C, D), weight


@functools.lru_cache(maxsize=None)
def compiled(chunk):
    """``(value and gradients of the chunked op, of the recurrence)``,
    jitted once per chunk size: cases of one shape share the compile."""
    def both(fn):
        return jax.jit(lambda args, weight: (
            fn(*args), jax.grad(lambda *a: jnp.sum(fn(*a) * weight),
                                argnums=range(6))(*args)))
    return (both(lambda *a: ssd.state_space_duality(*a, chunk)),
            both(reference.ssd))


def check(args, weight, chunk, tol=1e-5):
    chunked, recurrence = compiled(chunk)
    got, grads = chunked(args, weight)
    want, grads_want = recurrence(args, weight)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * scale)
    for name, a, b in zip(NAMES, grads, grads_want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(
            a, b, rtol=0, atol=tol * float(jnp.max(jnp.abs(b))),
            err_msg=name)


@pytest.mark.parametrize("chunk", [8, 32])
@pytest.mark.parametrize("decay", ["mixed", "near0", "near1"])
def test_chunked_op_is_the_recurrence(chunk, decay):
    """Forward and all six gradients to 1e-5 of their largest entry, two
    heads a group, over three chunks."""
    args, weight = case(chunk * 3, decay)
    check(args, weight, chunk)


@pytest.mark.parametrize("T,chunk", [(37, 8), (7, 32)])
def test_a_tail_and_a_short_sequence_are_padded_with_idle_steps(T, chunk):
    """37 = 4 x 8 + 5 leaves a tail, 7 is shorter than a chunk (one chunk
    of 7)."""
    args, weight = case(T, seed=1)
    check(args, weight, chunk)


def test_one_group_serves_every_head():
    """Granite's layout: one B / C group read by all the heads."""
    args, weight = case(48, seed=7, groups=1)
    check(args, weight, 16)


def test_any_chunk_gives_the_same_result():
    """The cell's walk at two other chunks (the published 256 among them,
    at a quarter of the scale): the same outputs to float32's rounding."""
    args, _ = case(64, seed=8, groups=1)
    outs = [ssd.state_space_duality(*args, chunk) for chunk in (4, 16, 64)]
    for out in outs[1:]:
        np.testing.assert_allclose(out, outs[0], rtol=0,
                                   atol=1e-5 * float(jnp.max(outs[0])))


def test_more_chunks_than_a_segment_holds(monkeypatch):
    """12 chunks in segments of 5: three segments of 4, the state carried
    from one to the next, forward and backward."""
    monkeypatch.setattr(dr, "DELTA_SEGMENT", 5)
    args, weight = case(96, seed=2)
    check(args, weight, 8)
    assert ssd.chunk_layout(96, 8) == (8, 12)


def test_the_cells_layout_by_hand():
    """T 8,192: 64 chunks of 128 in 2 segments of 32, nothing padded."""
    assert ssd.SSD_CHUNK == 128
    assert dr._layout(8192, 128) == (128, 32, 2)
    assert ssd.chunk_layout(8192) == (128, 64)
    assert ssd.chunk_layout(100) == (100, 1)


def test_the_states_kept_are_the_recurrences_own(monkeypatch):
    """The start of segment s is the state after s x segment steps: S read
    back through ``y = S^T C`` with unit C, a step that writes nothing."""
    monkeypatch.setattr(dr, "DELTA_SEGMENT", 2)
    (x, dt, A, B, C, D), _ = case(48, seed=3)
    _, starts = ssd._forward(x, dt, A, B, C, D, 8)
    G = B.shape[2]
    assert starts.shape == (3, ROWS, G, N, H // G, P)
    assert starts.dtype == jnp.float32 and not np.any(starts[0])
    after = lambda t, extra: jnp.concatenate([t[:, :32], extra], axis=1)
    for i in (0, N - 1):
        probe = jnp.zeros((ROWS, 1, G, N)).at[..., i].set(1.0)
        y = reference.ssd(after(x, jnp.zeros((ROWS, 1, H, P))),
                          after(dt, jnp.zeros((ROWS, 1, H))), A,
                          after(B, jnp.zeros_like(probe)), after(C, probe),
                          D)
        state = starts[2, :, :, i].reshape(ROWS, H, P)
        np.testing.assert_allclose(state, y[:, -1], rtol=1e-5, atol=1e-6)


def test_low_precision_inputs_run_the_state_in_float32():
    """bf16 x, B, C and D (the engine's policy; dt and A stay float32): the
    output within 2e-2 of the float32 recurrence ON THE SAME rounded inputs,
    the gradients come back in the inputs' dtypes."""
    args, weight = case(128, seed=4)
    low = tuple(t.astype(jnp.bfloat16) if name in "x B C D" else t
                for name, t in zip(NAMES, args))
    y = ssd.state_space_duality(*low)
    assert y.dtype == jnp.bfloat16
    exact = reference.ssd(*(t.astype(jnp.float32) for t in low))
    np.testing.assert_allclose(y.astype(jnp.float32), exact, rtol=0,
                               atol=2e-2 * float(jnp.max(jnp.abs(exact))))
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(
        ssd.state_space_duality(*a).astype(jnp.float32) * weight),
        argnums=range(6)))(*low)
    assert [g.dtype for g in grads] == [t.dtype for t in low]
    want = jax.jit(jax.grad(lambda *a: jnp.sum(reference.ssd(*a) * weight),
                            argnums=range(6)))(
        *(t.astype(jnp.float32) for t in low))
    for name, a, b in zip(NAMES, grads, want):
        off = float(jnp.linalg.norm((a.astype(jnp.float32) - b).ravel()))
        assert off <= 2e-2 * float(jnp.linalg.norm(b.ravel())), name


def test_heads_must_be_whole_groups():
    (x, dt, A, B, C, D), _ = case(16, groups=2)
    with pytest.raises(ValueError, match="whole groups"):
        ssd.state_space_duality(x[:, :, :3], dt[..., :3], A[:3], B, C, D[:3])


def _shapes(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.update(tuple(v.aval.shape) for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _shapes(sub, out)
    return out


def test_no_array_of_the_whole_sequence_times_the_state(monkeypatch):
    """T 256 in chunks of 8, segments of 4: the gradient's jaxpr holds the
    eight segment starts and a segment's chunks, never T x N x P a head."""
    monkeypatch.setattr(dr, "DELTA_SEGMENT", 4)
    args, _ = case(256, seed=5)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ssd.state_space_duality(*a, 8)),
        argnums=range(6)))(*args)
    shapes = _shapes(jaxpr.jaxpr, set())
    whole = ROWS * 256 * H * N * P
    big = [s for s in shapes if int(np.prod(s)) >= whole]
    assert not big, big
    assert (8, ROWS, 2, N, H // 2, P) in shapes       # the segment starts
