"""Gated delta rule (Gated DeltaNet, arXiv:2412.06464), chunked.

``gated_delta_rule(q, k, v, g, beta)`` computes, per row and value head, with
a MATRIX-valued state ``S`` ``[dk, dv]``,

    S_t = a_t S_{t-1} + k_t (x) [beta_t (v_t - (a_t S_{t-1})^T k_t)]   S_0 = 0
    o_t = S_t^T q_t                                        a_t = exp(g_t)

``q``/``k`` ``[rows, T, Hk, dk]``, ``v`` ``[rows, T, Hv, dv]``, ``g`` (the
log of the decay, never above 0) and ``beta`` ``[rows, T, Hv]``; key head
``i`` serves the value heads ``i Hv/Hk ... (i + 1) Hv/Hk - 1``.  Unlike the
selective scan's, the update is not elementwise: every step first takes away
what the state already predicts for its key, so the steps of a chunk are
tied by a unit-triangular system.  The state and everything that touches it
run in float32 at the highest matmul precision whatever the inputs' dtype;
``o`` comes back in ``v``'s dtype.

The chunked form (exact in exact arithmetic; chunk ``C``, per head): with
``gamma_i = sum_{j<=i} g_j`` and ``D_ij = exp(gamma_i - gamma_j)`` for ``i >=
j``, else 0 (never an exponent above 0),

    L = strictly lower part of (beta k k^T) * D        T = (I + L)^-1
    U = T (beta v)          W = T (beta exp(gamma) k)

(``_prepare``: no state enters, so it runs for many chunks at once), then
over the chunks in order (``_recur``), carrying ``S``:

    V' = U - W S       O = (exp(gamma) q) S + (q k^T * D) V'
    S <- exp(gamma_C) S + (exp(gamma_C - gamma) k)^T V'

Nothing ``[T, dk, dv]`` is ever in HBM, in either direction.  The chunks are
walked in SEGMENTS of ``DELTA_SEGMENT``: a segment prepares its chunks in
one batch, then runs the recurrence over them, so what is held of size ``[T,
H, d]`` in float32 (``U``, ``W``, the scaled q and k and, backward, their
gradients) is a segment's.  The forward keeps its inputs and the state at
every chunk's START (``T / C`` states); the backward walks segments and
chunks in reverse, preparing a segment again and differentiating each
chunk's recurrence from its saved start — ``selective_scan``'s design.  XLA
compiles all of it; a Pallas chunk would take ``_recur``'s place under the
same scope.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from deepspeed_tpu.ops.remat_names import DELTA_OUT, DELTA_STATES

#: steps per chunk (the public implementations' 64): 256 boundary states of
#: 32 x 128 x 128 float32 at T 16,384 (537 MB a layer, held while that layer's
#: backward runs).  PERF.md, PR 37, has the chip's times.
DELTA_CHUNK = 64
#: chunks prepared at once: 32 x 64 = 2,048 steps, so U, W and the scaled q
#: and k of 32 heads of 128 are 4 x 33.5 MB in float32 beside the inputs
DELTA_SEGMENT = 32

_HIGHEST = jax.lax.Precision.HIGHEST


def _pair_decay(g):
    """``D_ij = exp(sum_{j < t <= i} g_t)`` for ``i >= j``, else 0, of ``g``
    ``[..., C]`` float32.  Each span is summed from its own start (a running
    sum down column ``j`` of the steps after ``j``), not taken as the
    difference ``gamma_i - gamma_j`` of two running sums over the whole
    chunk: those grow to hundreds where a head forgets fast, and their
    difference — and, backward, the two sums over a row and a column that
    the difference's gradient subtracts — would be good to an ulp of THEM
    (measured at C 64 in float32 against a float64 recurrence: ``g``'s
    gradient 5e-5 of its largest entry off by differences, 1e-7 by spans)."""
    step = jnp.arange(g.shape[-1])
    spans = jnp.cumsum(jnp.where(step[:, None] > step[None, :],
                                 g[..., :, None], 0.0), axis=-2)
    # masked BEFORE the exponential, so that nothing above the diagonal is
    # ever raised
    return jnp.exp(jnp.where(step[:, None] >= step[None, :], spans,
                             -jnp.inf))


#: rows of the diagonal blocks inverted by substitution; larger blocks are
#: put together from their halves' inverses by two products
_BASE_BLOCK = 16


def _inverse_by_blocks(lower):
    size = lower.shape[-1]
    if size <= _BASE_BLOCK or size % 2:
        # forward substitution, written out: row i of the inverse is e_i
        # less row i of ``lower`` times the rows above it
        eye = jnp.eye(size, dtype=lower.dtype)
        rows = [jnp.broadcast_to(eye[0], lower.shape[:-2] + (size,))]
        for i in range(1, size):
            above = jnp.stack(rows, axis=-2)
            rows.append(eye[i] - jnp.einsum(
                "...j,...jc->...c", lower[..., i, :i], above,
                precision=_HIGHEST))
        return jnp.stack(rows, axis=-2)
    half = size // 2
    top = _inverse_by_blocks(lower[..., :half, :half])
    bottom = _inverse_by_blocks(lower[..., half:, half:])
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    corner = -mm(mm(bottom, lower[..., half:, :half]), top)
    return jnp.concatenate(
        [jnp.concatenate([top, jnp.zeros_like(corner.mT)], axis=-1),
         jnp.concatenate([corner, bottom], axis=-1)], axis=-2)


@jax.custom_vjp
def _unit_lower_inverse(lower):
    """``(I + lower)^-1`` of strictly lower triangular ``lower`` ``[..., C,
    C]`` float32, exactly (no series): the diagonal blocks of
    ``_BASE_BLOCK`` rows by forward substitution, then ``[[A, 0], [B, D]]^-1
    = [[A^-1, 0], [-D^-1 B A^-1, D^-1]]`` level by level — a few batched
    products in place of a triangular-solve call per segment, which on a
    v5e took most of the rule's time (PERF.md, PR 37).  The gradient is the
    inverse's own, ``-T^T dT T^T``, not the substitution's."""
    return _inverse_by_blocks(lower)


def _unit_lower_inverse_fwd(lower):
    inverse = _inverse_by_blocks(lower)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, d_inverse):
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    return (-mm(mm(inverse.mT, d_inverse), inverse.mT),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _prepare(q, k, v, g, beta):
    """What a chunk needs that no state enters, for any number of chunks at
    once: ``q``/``k`` ``[..., Hk, 1, C, dk]``, ``v`` ``[..., Hk, r, C, dv]``,
    ``g``/``beta`` ``[..., Hk, r, C]`` -> ``(U [..., C, dv], W [..., C, dk],
    exp(gamma) q, exp(gamma_C - gamma) k, (q k^T) * D [..., C, C],
    exp(gamma_C) [...])``, all float32 and per value head ``[..., Hk, r]``."""
    f32 = jnp.float32
    dv = v.shape[-1]
    gf = g.astype(f32)
    gamma = jnp.cumsum(gf, axis=-1)
    step = jnp.arange(g.shape[-1])
    decay = _pair_decay(gf)
    pairs = lambda a, b: jnp.einsum("...id,...jd->...ij", a, b,
                                    preferred_element_type=f32)
    beta = beta.astype(f32)[..., None]
    qf, kf = q.astype(f32), k.astype(f32)
    lower = jnp.where(step[:, None] > step[None, :],
                      beta * pairs(k, k) * decay, 0.0)
    grown = jnp.exp(gamma)[..., None]
    solved = jnp.matmul(
        _unit_lower_inverse(lower),
        jnp.concatenate([beta * v.astype(f32), beta * grown * kf], axis=-1),
        precision=_HIGHEST)
    # gamma_C - gamma_j as the sum of the steps AFTER j, as _pair_decay's
    later = jnp.pad(gf[..., 1:], [(0, 0)] * (gf.ndim - 1) + [(0, 1)])
    after = jnp.flip(jnp.cumsum(jnp.flip(later, -1), axis=-1), -1)
    return (solved[..., :dv], solved[..., dv:], grown * qf,
            jnp.exp(after)[..., None] * kf,
            pairs(q, k) * decay, jnp.exp(gamma[..., -1]))


def _recur(S, U, W, q_in, k_out, A, shrink):
    """One chunk of the recurrence on ``_prepare``'s results: ``S`` ``[rows,
    Hk, r, dk, dv]`` float32 -> ``(S at the chunk's end, O [rows, Hk, r, C,
    dv])``."""
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    fresh = U - mm(W, S)
    out = mm(q_in, S) + mm(A, fresh)
    return (shrink[..., None, None] * S
            + mm(jnp.swapaxes(k_out, -1, -2), fresh)), out


def _layout(T, chunk):
    """``(chunk, chunks a segment, segments)`` for ``T`` steps: the tail is
    padded with steps that leave the state alone."""
    chunk = min(chunk, T)
    chunks = -(-T // chunk)
    segments = -(-chunks // DELTA_SEGMENT)
    return chunk, -(-chunks // segments), segments


def _chunks(x, chunk, per_segment, segments):
    """``[rows, T, Hk, r, ...]`` -> ``[segments, chunks, rows, Hk, r, chunk,
    ...]`` (``r``: the value heads of a key head, 1 for q and k), the tail
    padded with zeros (a padded step has ``beta`` 0, ``g`` 0 and a zero
    key: it writes nothing and the state stands still)."""
    rows, T = x.shape[:2]
    steps = segments * per_segment * chunk
    x = jnp.pad(x, ((0, 0), (0, steps - T)) + ((0, 0),) * (x.ndim - 2))
    x = x.reshape(rows, segments, per_segment, chunk, *x.shape[2:])
    return jnp.transpose(x, (1, 2, 0, 4, 5, 3, *range(6, x.ndim)))


def _unchunk(x, T):
    """``[segments, chunks, rows, Hk, r, chunk, ...]`` -> ``[rows, T, Hk, r,
    ...]``."""
    x = jnp.transpose(x, (2, 0, 1, 5, 3, 4, *range(6, x.ndim)))
    return x.reshape(x.shape[0], -1, *x.shape[4:])[:, :T]


def _grouped(q, k, v, g, beta):
    """The value heads as ``[Hk, r]``, q and k as ``[Hk, 1]``: a key head's
    ``r`` value heads read ITS q and k by broadcasting, none is copied."""
    hk = q.shape[2]
    split = lambda x: x.reshape(*x.shape[:2], hk, -1, *x.shape[3:])
    return (q[:, :, :, None], k[:, :, :, None], split(v), split(g),
            split(beta))


def _forward(q, k, v, g, beta, chunk):
    rows, T, hk, dk = q.shape
    layout = _layout(T, chunk)
    xs = tuple(_chunks(x, *layout) for x in _grouped(q, k, v, g, beta))

    def segment(S, x):
        def one(S, prepared):
            S_end, out = _recur(S, *prepared)
            return S_end, (out.astype(v.dtype), S)

        return jax.lax.scan(one, S, _prepare(*x))

    S0 = jnp.zeros((rows, hk, v.shape[2] // hk, dk, v.shape[-1]),
                   jnp.float32)
    _, (out, starts) = jax.lax.scan(segment, S0, xs)
    return _unchunk(out, T).reshape(v.shape), starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, chunk):
    return _forward(q, k, v, g, beta, chunk)[0]


def _rule_fwd(q, k, v, g, beta, chunk):
    out, starts = _forward(q, k, v, g, beta, chunk)
    # named for the recomputation policies: with both saved the replayed
    # forward has no consumer and is dropped, as the selective scan's
    out, starts = (checkpoint_name(out, DELTA_OUT),
                   checkpoint_name(starts, DELTA_STATES))
    return out, (q, k, v, g, beta, starts)


def _rule_bwd(chunk, res, d_out):
    *inputs, starts = res
    T = inputs[0].shape[1]
    layout = _layout(T, chunk)
    xs = tuple(_chunks(x, *layout) for x in (
        *_grouped(*inputs), d_out.reshape(starts.shape[2], T,
                                          *starts.shape[3:5], -1)))

    def segment(dS, x):
        *x, d_o, S_starts = x
        prepared, pull_prepare = jax.vjp(_prepare, *x)

        def one(dS, c):
            S_start, d_o_c, *p = c
            _, pull = jax.vjp(_recur, S_start, *p)
            dS, *d_p = pull((dS, d_o_c.astype(jnp.float32)))
            return dS, tuple(d_p)

        dS, d_prepared = jax.lax.scan(one, dS, (S_starts, d_o, *prepared),
                                      reverse=True)
        return dS, pull_prepare(d_prepared)

    _, grads = jax.lax.scan(segment, jnp.zeros_like(starts[0, 0]),
                            (*xs, starts), reverse=True)
    return tuple(_unchunk(d, T).reshape(x.shape).astype(x.dtype)
                 for d, x in zip(grads, inputs))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk=None):
    """See the module docstring.  ``chunk``: steps per chunk (default
    ``DELTA_CHUNK``; any value gives the same result)."""
    if v.shape[2] % q.shape[2]:
        raise ValueError(f"{v.shape[2]} value heads on {q.shape[2]} key heads")
    return _rule(q, k, v, g, beta, chunk or DELTA_CHUNK)


def chunk_layout(T, chunk=None):
    """``(steps per chunk, chunks per sequence)`` the rule walks ``T`` steps
    in (the padded count): what the model reports as gauges."""
    chunk, per_segment, segments = _layout(T, chunk or DELTA_CHUNK)
    return chunk, per_segment * segments
