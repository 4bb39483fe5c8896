"""ops/delta_rule.py: the chunked gated delta rule against the step-by-step
recurrence of ``benchmark/reference/qwen3_next.delta_rule`` (which imports
nothing of the program) — values and every input's gradient in float32 to
1e-5, over chunks of 16 and 64, sequences of 2 and of 5 chunks and ones that
leave a tail, decays near 0 and near 1, value heads that share a key head —
bf16 inputs at a stated band, the states kept at the chunk boundaries, and
the shape discipline: nothing of size T x dk x dv in the gradient's jaxpr.
The walks as Pallas kernels (interpreted here) against XLA's walks and a
float64 recurrence, and which shapes and backends take them."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as reference
from deepspeed_tpu.ops import delta_rule as dr

ROWS, HK, HV, DK, DV = 2, 2, 4, 8, 8
NAMES = "q k v g beta".split()


def case(T, decay="mixed", seed=0, dims=(ROWS, HK, HV, DK, DV)):
    """Inputs as the mixer makes them: unit keys, queries of norm 1 /
    sqrt(dk), ``g <= 0``, ``beta`` in (0, 1); and a weight for the output.
    ``decay``: ``near0`` (``exp(g)`` ~ 1e-7: the state is all but wiped
    each step), ``near1`` (``exp(g)`` ~ 0.999: it barely fades), ``mixed``."""
    rows, hk, hv, dk, dv = dims
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (rows, T, hk, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (rows, T, hk, dk)))
    v = jax.random.normal(ks[2], (rows, T, hv, dv))
    rate = {"near0": 16.0, "near1": 1e-3, "mixed": 0.5}[decay]
    g = -rate * jax.nn.softplus(jax.random.normal(ks[3], (rows, T, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (rows, T, hv)))
    weight = jax.random.normal(ks[5], (rows, T, hv, dv))
    return (q, k, v, g, beta), weight


def stepwise(q, k, v, g, beta):
    """The reference's recurrence, one key head per value head."""
    q, k = (jnp.repeat(x, HV // HK, axis=2) for x in (q, k))
    return reference.delta_rule(q, k, v, g, beta)


@functools.lru_cache(maxsize=None)
def compiled(chunk):
    """``(value and gradients of the chunked rule, of the recurrence)``,
    jitted once per chunk size: cases of one shape share the compile."""
    def both(fn):
        return jax.jit(lambda args, weight: (
            fn(*args), jax.grad(lambda *a: jnp.sum(fn(*a) * weight),
                                argnums=range(5))(*args)))
    return (both(lambda *a: dr.gated_delta_rule(*a, chunk)), both(stepwise))


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


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("chunks", [2, 5])
@pytest.mark.parametrize("decay", ["mixed", "near0", "near1"])
def test_chunked_rule_is_the_recurrence(chunk, chunks, decay):
    """Forward and all five gradients to 1e-5 of their largest entry."""
    args, weight = case(chunk * chunks, decay)
    check(args, weight, chunk)


@pytest.mark.parametrize("T,chunk", [(37, 16), (7, 64)])
def test_a_tail_and_a_short_sequence_are_padded_with_idle_steps(T, chunk):
    """37 = 2 x 16 + 5 leaves a tail, 7 is shorter than a chunk (one chunk
    of 7)."""
    args, weight = case(T, seed=1)
    check(args, weight, chunk)


def test_a_run_of_identical_keys_is_solved_exactly():
    """One key repeated over every step with ``beta`` near 1 and hardly any
    decay (a run of one token in a document): ``I + L`` is all ones below
    its diagonal, its inverse is 1 / -1 on two diagonals, and a power
    series for it would sum terms of 1e18 that cancel — the chunk's inverse
    is taken exactly (substitution and block products), so the rule stays
    the recurrence: to 1e-4 here, where float32 itself is worth 1e-5 (the
    step-by-step recurrence's gradient to ``g`` is 1.1e-5 off a float64
    one, the chunked rule's 3.6e-5; everything else 1.5e-6)."""
    (q, k, v, g, beta), weight = case(128, "near1", seed=6)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    beta = 0.9 + 0.1 * beta
    check((q, k, v, g, beta), weight, 64, tol=1e-4)
    lower = jnp.tril(jnp.full((64, 64), 0.97), -1)
    inverse = dr._unit_lower_inverse(lower)
    np.testing.assert_allclose(
        inverse @ (lower + jnp.eye(64)), jnp.eye(64), rtol=0, atol=1e-5)


def test_more_chunks_than_a_segment_holds(monkeypatch):
    """12 chunks in segments of 5: three segments of 4, the state carried
    from one to the next, forward and backward."""
    monkeypatch.setattr(dr, "DELTA_SEGMENT", 5)
    assert dr._layout(96, 8) == (8, 4, 3)
    assert dr._layout(88, 8) == (8, 4, 3)          # 11 chunks: one is padding
    args, weight = case(96, seed=2)
    check(args, weight, 8)
    assert dr.chunk_layout(96, 8) == (8, 12)


def test_the_cells_layout_by_hand():
    """T 16,384: 256 chunks of 64 in 8 segments of 32, nothing padded."""
    assert (dr.DELTA_CHUNK, dr.DELTA_SEGMENT) == (64, 32)
    assert dr._layout(16384, 64) == (64, 32, 8)
    assert dr.chunk_layout(16384) == (64, 256)
    assert dr.chunk_layout(100) == (64, 2)


def test_the_states_kept_are_the_recurrences_own():
    """The start of chunk c is the state after c x chunk steps: S read back
    through ``o = S^T q`` with unit queries."""
    (q, k, v, g, beta), _ = case(48, seed=3)
    _, starts = dr._forward(q, k, v, g, beta, 16)
    assert starts.shape == (1, 3, ROWS, HK, HV // HK, DK, DV)
    assert starts.dtype == jnp.float32 and not np.any(starts[0, 0])
    starts = starts.reshape(1, 3, ROWS, HV, DK, DV)
    q, k = (jnp.repeat(x, HV // HK, axis=2) for x in (q, k))
    # probe the state after 32 steps: one more step that writes nothing
    # (beta 0, g 0) and reads with the unit vector e_i
    for i in (0, DK - 1):
        probe = jnp.zeros((ROWS, 1, HV, DK)).at[..., i].set(1.0)
        ext = lambda x, p: jnp.concatenate([x[:, :32], p], axis=1)
        o = reference.delta_rule(
            ext(q, probe), ext(k, jnp.zeros_like(probe)),
            ext(v, jnp.zeros((ROWS, 1, HV, DV))),
            ext(g, jnp.zeros((ROWS, 1, HV))),
            ext(beta, jnp.zeros((ROWS, 1, HV))))
        np.testing.assert_allclose(starts[0, 2, :, :, i], o[:, -1],
                                   rtol=1e-5, atol=1e-6)


def test_low_precision_inputs_run_the_state_in_float32():
    """bf16 q, k, v (the engine's policy; g and beta stay float32): the
    output within 2e-2 of the float32 recurrence ON THE SAME rounded inputs
    (one rounding of the result, bf16 products of q and k, none in the
    state), the gradients come back in the inputs' dtypes."""
    args, weight = case(128, seed=4)
    low = tuple(x.astype(jnp.bfloat16) for x in args[:3]) + args[3:]
    o = dr.gated_delta_rule(*low)
    assert o.dtype == jnp.bfloat16
    exact = stepwise(*(x.astype(jnp.float32) for x in low))
    scale = float(jnp.max(jnp.abs(exact)))
    np.testing.assert_allclose(o.astype(jnp.float32), exact, rtol=0,
                               atol=2e-2 * scale)
    grads = jax.grad(lambda *a: jnp.sum(
        dr.gated_delta_rule(*a).astype(jnp.float32) * weight),
        argnums=range(5))(*low)
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    want = jax.grad(lambda *a: jnp.sum(stepwise(*a) * weight),
                    argnums=range(5))(*(x.astype(jnp.float32) for x in low))
    for name, a, b in zip(NAMES, grads, want):
        off = float(jnp.linalg.norm((a.astype(jnp.float32) - b).ravel()))
        assert off <= 2e-2 * float(jnp.linalg.norm(b.ravel())), name


def test_value_heads_must_be_whole_groups_of_key_heads():
    (q, k, v, g, beta), _ = case(16)
    with pytest.raises(ValueError, match="value heads"):
        dr.gated_delta_rule(q, k, v[:, :, :3], g[..., :3], beta[..., :3])


def _shapes(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.update(tuple(v.aval.shape) for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _shapes(sub, out)
    return out


def test_no_array_of_the_whole_sequence_times_the_state(monkeypatch):
    """T 256 in chunks of 8, segments of 4: the gradient's jaxpr holds the
    32 boundary states and a segment's chunks, never T x dk x dv per head."""
    monkeypatch.setattr(dr, "DELTA_SEGMENT", 4)
    args, _ = case(256, seed=5)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(dr.gated_delta_rule(*a, 8)),
        argnums=range(5)))(*args)
    shapes = _shapes(jaxpr.jaxpr, set())
    whole = ROWS * 256 * HV * DK * DV
    big = [s for s in shapes if int(np.prod(s)) >= whole]
    assert not big, big
    assert (8, 4, ROWS, HK, HV // HK, DK, DV) in shapes   # boundary states


# --------------------------------------------- the walks as Pallas kernels
# (interpreted: no TPU here).  The kernels take a state whose two widths
# are whole lane tiles, so these cases are 128 wide.

WIDE = 128


def recurrence64(args, weight):
    """Value and gradients of the recurrence, one step at a time, in
    float64 (its own few lines: the reference's is float32 by contract)."""
    def rule(q, k, v, g, beta):
        q, k = (jnp.repeat(x, v.shape[2] // x.shape[2], axis=2)
                for x in (q, k))

        def step(S, x):
            q_t, k_t, v_t, g_t, b_t = x
            S = jnp.exp(g_t)[..., None, None] * S
            seen = jnp.einsum("bhkv,bhk->bhv", S, k_t)
            S = S + jnp.einsum("bhk,bhv->bhkv", k_t,
                               b_t[..., None] * (v_t - seen))
            return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

        S0 = jnp.zeros((*v.shape[::2], q.shape[-1], v.shape[-1]), v.dtype)
        _, o = jax.lax.scan(step, S0, tuple(
            jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 1)

    with jax.enable_x64(True):
        args, weight = jax.tree_util.tree_map(
            lambda x: jnp.asarray(np.asarray(x, np.float64)), (args, weight))
        return rule(*args), jax.grad(
            lambda *a: jnp.sum(rule(*a) * weight), argnums=range(5))(*args)


def walked(chunk, interpret):
    """``(output, the states at the chunks' starts, the five gradients)`` of
    the rule: by the kernels where ``interpret``, else by XLA's walk."""
    def run(args, weight):
        out, starts = dr._forward(*args, chunk, interpret)
        return out, starts, jax.grad(lambda *a: jnp.sum(
            dr.gated_delta_rule(*a, chunk, interpret) * weight),
            argnums=range(5))(*args)
    return jax.jit(run)


_walked = functools.lru_cache(maxsize=None)(walked)


def close(got, want, tol, what):
    np.testing.assert_allclose(
        got, want, rtol=0, atol=tol * float(np.max(np.abs(want))),
        err_msg=what)


@pytest.mark.parametrize("tail", [0, 5], ids=["whole", "tail"])
@pytest.mark.parametrize("hv", [2, 4], ids=["r1", "r2"])
@pytest.mark.parametrize("decay", ["mixed", "near0", "near1"])
@pytest.mark.parametrize("chunk", [16, 64])
def test_the_kernels_walk_is_xlas_and_the_recurrence(chunk, decay, hv, tail):
    """Three chunks, or three and a tail padded with idle steps; one or two
    value heads a key head: output, the state at every chunk's start and
    all five gradients of the kernels' walk are XLA's walk's to 1e-6 of
    their largest entry (the same float32 products in another order), and
    output and gradients the float64 recurrence's to 1e-5."""
    args, weight = case(3 * chunk + tail, decay,
                        dims=(1, 2, hv, WIDE, WIDE))
    assert dr.kernel_walks(WIDE, WIDE, 3 * chunk + tail, jnp.float32, chunk,
                           interpret=True)
    out, starts, grads = _walked(chunk, True)(args, weight)
    out_x, starts_x, grads_x = _walked(chunk, False)(args, weight)
    out_64, grads_64 = recurrence64(args, weight)
    assert starts.shape == starts_x.shape == (
        1, 3 + bool(tail), 1, 2, hv // 2, WIDE, WIDE)
    close(out, out_x, 1e-6, "output")
    close(starts, starts_x, 1e-6, "starts")
    close(out, out_64, 1e-5, "output, float64")
    for name, a, b, c in zip(NAMES, grads, grads_x, grads_64):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        close(a, b, 1e-6, name)
        close(a, c, 1e-5, name + ", float64")


def test_the_kernels_hand_the_state_from_one_call_to_the_next(monkeypatch):
    """7 chunks in segments of 3: three kernel calls a direction (the last
    chunk of the third is padding), the state leaving one as an output and
    entering the next — and ``dS`` on the way back."""
    monkeypatch.setattr(dr, "DELTA_SEGMENT", 3)
    assert dr._layout(7 * 16, 16) == (16, 3, 3)
    args, weight = case(7 * 16, seed=7, dims=(1, 2, 4, WIDE, WIDE))
    out, starts, grads = walked(16, True)(args, weight)
    out_x, starts_x, grads_x = walked(16, False)(args, weight)
    assert starts.shape == (3, 3, 1, 2, 2, WIDE, WIDE)
    assert np.any(np.asarray(starts[1, 0]))        # not a fresh zero state
    close(out, out_x, 1e-6, "output")
    close(starts, starts_x, 1e-6, "starts")
    for name, a, b in zip(NAMES, grads, grads_x):
        close(a, b, 1e-6, name)
    out_64, grads_64 = recurrence64(args, weight)
    close(out, out_64, 1e-5, "output, float64")
    for name, a, c in zip(NAMES, grads, grads_64):
        close(a, c, 1e-5, name + ", float64")


def test_which_shapes_and_backends_take_the_kernels(monkeypatch):
    """``supported``: both widths of the state whole lane tiles, the chunk
    whole sublane tiles of the output's dtype.  ``kernel_walks``: that, on
    a TPU (or interpreted); XLA's walk — no ``pallas_call`` in the jaxpr,
    forward or backward — for any other shape or backend."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    assert dr.supported(128, 128, 64, bf16) and dr.supported(256, 128, 8, f32)
    assert not dr.supported(64, 128, 64, f32)
    assert not dr.supported(128, 8, 64, f32)
    assert not dr.supported(128, 128, 12, f32)
    assert not dr.supported(128, 128, 8, bf16)      # half a bf16 tile

    def calls(dims, T, interpret=False):
        args, _ = case(T, dims=dims)
        text = str(jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(dr.gated_delta_rule(*a, 16, interpret)),
            argnums=range(5)))(*args))
        return text.count("pallas_call")

    wide, narrow = (1, 2, 4, WIDE, WIDE), (ROWS, HK, HV, DK, DV)
    assert jax.default_backend() == "cpu"
    assert not dr.kernel_walks(WIDE, WIDE, 32, f32, 16)
    assert calls(wide, 32) == 0                      # no TPU: XLA's walk
    assert calls(wide, 32, interpret=True) == 2      # forward and backward
    assert calls(narrow, 32, interpret=True) == 0    # an 8-wide state
    # a sequence shorter than a chunk is ONE chunk of its length
    assert not dr.kernel_walks(WIDE, WIDE, 7, f32, 16, interpret=True)
    assert calls(wide, 7, interpret=True) == 0
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dr.kernel_walks(128, 128, 16384, bf16)    # the cell's rule
    assert not dr.kernel_walks(DK, DV, 16384, bf16)
