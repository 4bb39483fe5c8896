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

(no state enters, so it runs for many chunks at once: ``_gates`` makes what
is no wider than a chunk — ``T``, ``D``, ``beta`` and the decays of a
chunk's steps — and ``_prepare`` applies it to q, k and v), then over the
chunks in order (``_recur``), carrying ``S``:

    V' = U - W S       O = (exp(gamma) q) S + (q k^T * D) V'
    S <- exp(gamma_C) S + (exp(gamma_C - gamma) k)^T V'

Nothing ``[T, dk, dv]`` is ever in HBM, in either direction.  The chunks are
walked in SEGMENTS of ``DELTA_SEGMENT``: a segment's gates are made in one
batch, then the recurrence runs over its chunks.  The forward keeps its
inputs and the state at every chunk's START (``T / C`` states); the backward
walks segments and chunks in reverse, making a segment's gates again and
differentiating each chunk's recurrence from its saved start —
``selective_scan``'s design.

Who walks a segment's chunks follows from what the code can see
(``kernel_walks``).  On a TPU, where the state's widths are whole lane tiles
and the chunk whole sublane tiles (``supported``), ONE Pallas call a segment
and direction (``_walk_pallas``, ``_walk_back_pallas``): the grid's last
axis is the chunk, walked in order, and the state (backward: its gradient)
of a block of ``DELTA_HEADS`` value heads stays in VMEM from the segment's
first chunk to its last; it enters and leaves the call as an operand and a
result, so one segment hands it to the next.  A grid step reads the chunk's
q, k, v and g as they came and the rows of ``_rows`` (``beta`` and the
decays of the chunk's steps, XLA's), and does all of ``_gates``' work past
them in VMEM — ``D`` (``_decay_in_vmem``), k's pair products, ``L``, the
inverse by blocks (``_inverse_in_vmem``) — then ``_prepare``'s and
``_recur``'s: ``D``, ``T``, ``U``, ``W``, the scaled q and k and ``(q k^T) *
D`` never exist in HBM.  The backward kernel forms them and ``V'`` again
from the same operands and the saved start, pulls ``T``'s gradient back
through the inverse, ``L`` and ``D`` in VMEM, and writes the gradients of q,
k and v whole, ``g``'s part through ``D`` and the gradients of ``_rows``'
results, which XLA pulls back to g and beta.  Everywhere else — the CPU, a
narrow state: the tests' oracle — XLA: ``_gates`` and ``_prepare`` for a
segment at once and ``lax.scan`` over ``_recur`` and over its ``jax.vjp``
(``_walk``, ``_walk_back``).  The rows and the copies into the chunked
layout are XLA's on either path, under the same scope.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.remat_names import DELTA_OUT, DELTA_STATES

#: steps per chunk (the public implementations' 64): 256 boundary states of
#: 32 x 128 x 128 float32 at T 16,384 (537 MB a layer, held while that layer's
#: backward runs).  PERF.md, PR 37, has the chip's times.
DELTA_CHUNK = 64
#: chunks whose gates are made at once and which one kernel call walks: 32 x
#: 64 = 2,048 steps, and the state crosses HBM once a segment (backward: 2 x
#: 16.8 MB for q's and k's float32 gradients).  On XLA's walk T and D [64,
#: 64] of 32 heads, 2 x 16.8 MB in float32 (backward: as much again for
#: their gradients), U, W and the scaled q and k, 4 x 33.5 MB, are a
#: segment's too.
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


def _pairs(a, b):
    return jnp.einsum("...id,...jd->...ij", a, b,
                      preferred_element_type=jnp.float32)


def _rows(g, beta):
    """What a chunk needs of its decays and write strengths that is no wider
    than its steps, for any number of chunks at once: ``g``/``beta``
    ``[..., C]`` -> ``(beta [..., C], exp(gamma) [..., C], exp(gamma_C -
    gamma) [..., C], exp(gamma_C) [...])``, float32."""
    gf = g.astype(jnp.float32)
    gamma = jnp.cumsum(gf, axis=-1)
    # gamma_C - gamma_j as the sum of the steps AFTER j, as _pair_decay's
    later = jnp.pad(gf[..., 1:], [(0, 0)] * (gf.ndim - 1) + [(0, 1)])
    after = jnp.flip(jnp.cumsum(jnp.flip(later, -1), axis=-1), -1)
    return (beta.astype(jnp.float32), jnp.exp(gamma), jnp.exp(after),
            jnp.exp(gamma[..., -1]))


def _gates(k, g, beta):
    """What a chunk needs that neither the state nor q or v enters, for any
    number of chunks at once, none of it wider than a chunk: ``k`` ``[...,
    Hk, 1, C, dk]``, ``g``/``beta`` ``[..., Hk, r, C]`` -> ``(T [..., C, C],
    D [..., C, C], *_rows)``, float32 and per value head ``[..., Hk, r]``."""
    decay = _pair_decay(g.astype(jnp.float32))
    beta, *rest = _rows(g, beta)
    step = jnp.arange(g.shape[-1])
    lower = jnp.where(step[:, None] > step[None, :],
                      beta[..., None] * _pairs(k, k) * decay, 0.0)
    return (_unit_lower_inverse(lower), decay, beta, *rest)


def _prepare(q, k, v, g, beta):
    """``_gates`` applied to a chunk's q, k and ``v`` ``[..., Hk, r, C,
    dv]``: ``(U [..., C, dv], W [..., C, dk], exp(gamma) q, exp(gamma_C -
    gamma) k, (q k^T) * D, exp(gamma_C))``, what ``_recur`` takes.  ``beta``
    and ``exp(gamma)`` go on ``T``'s COLUMNS: ``U = (T beta) v``, ``W = (T
    beta exp(gamma)) k``.  XLA's walk; the kernels do this in VMEM."""
    inverse, decay, beta, grown, rest, shrink = _gates(k, g, beta)
    f32 = jnp.float32
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    qf, kf = q.astype(f32), k.astype(f32)
    solve = inverse * beta[..., None, :]
    return (mm(solve, v.astype(f32)), mm(solve * grown[..., None, :], kf),
            grown[..., None] * qf, rest[..., None] * kf,
            _pairs(q, k) * decay, shrink)


def _recur(S, U, W, q_in, k_out, A, shrink):
    """One chunk of the recurrence on ``_prepare``'s results: ``S`` ``[rows,
    Hk, r, dk, dv]`` float32 -> ``(S at the chunk's end, O [rows, Hk, r, C,
    dv])``."""
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    fresh = U - mm(W, S)
    out = mm(q_in, S) + mm(A, fresh)
    return (shrink[..., None, None] * S
            + mm(jnp.swapaxes(k_out, -1, -2), fresh)), out


def _walk(S, x, out_dtype):
    """A segment's chunks in order, by XLA: ``x`` = q, k, v, g, beta
    ``[chunks, rows, Hk, ...]`` prepared in one batch, then ``lax.scan`` of
    ``_recur`` -> ``(S after the last, (O in out_dtype, the state at every
    chunk's START))``."""
    def one(S, chunk):
        S_end, out = _recur(S, *chunk)
        return S_end, (out.astype(out_dtype), S)

    return jax.lax.scan(one, S, _prepare(*x))


def _walk_back(dS, starts, d_out, x):
    """``_walk``'s chunks in reverse, each ``_recur`` pulled back from its
    saved start, then ``_prepare``'s pull: ``(dS, dO [chunks, ...]) -> (dS
    before the first, the gradients of q, k, v, g, beta)``."""
    prepared, pull_prepare = jax.vjp(_prepare, *x)

    def one(dS, chunk):
        S_start, d_o, *p = chunk
        _, pull = jax.vjp(_recur, S_start, *p)
        dS, *d_p = pull((dS, d_o.astype(jnp.float32)))
        return dS, tuple(d_p)

    dS, d_prepared = jax.lax.scan(one, dS, (starts, d_out, *prepared),
                                  reverse=True)
    return dS, pull_prepare(d_prepared)


# ------------------------------------------------- the walks as kernels
# The same two walks as Pallas kernels: grid (blocks of key heads, chunks),
# the chunk axis walked in order ("arbitrary"; the backward's index maps
# turn it round), the state [dk, dv] float32 of each value head of the
# block in VMEM scratch from the first chunk to the last.  A grid step reads
# the chunk's q, k and v as they came (a key head's q and k once for its r
# value heads), g and ``_rows``' rows, and does in VMEM what ``_gates`` does
# past them — ``D``, ``L`` from k's pair products, ``T = (I + L)^-1`` —
# then what ``_prepare`` does and what ``_recur`` does: nothing [T, H, d]
# wide in float32 crosses HBM on its way in, no [C, C] matrix ever does, and
# backward only the gradients of q and k leave at that width.  Every product
# is float32 at HIGHEST, as _prepare's and _recur's.

#: value heads a grid step (PERF.md, PR 38, has the sweep): one head's
#: blocks are smaller than what a grid step costs beside them
DELTA_HEADS = 8

#: rows of the diagonal blocks the kernels invert by substitution before
#: joining them level by level (PERF.md §5 has the sweep); a chunk that is
#: no whole number of them takes the largest power of two that divides it
KERNEL_BLOCK = 16

# products of [heads, a, b] batches, the head in front
_NN = (((2,), (1,)), ((0,), (0,)))      # a b
_NT = (((2,), (2,)), ((0,), (0,)))      # a b^T
_TN = (((1,), (1,)), ((0,), (0,)))      # a^T b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _strictly_lower(x):
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 2)
    return jnp.where(row > col, x, 0.0)


def _decay_in_vmem(g):
    """``D`` of the rows ``g`` ``[heads, 1, C]``, as ``_pair_decay`` takes
    it — each span summed from its own start, masked before the
    exponential — with the sums written as one product: ``spans[i, j] =
    sum_t [t <= i] [j < t] g_t``.  -> ``(D, [t <= i])``, the product's
    left factor, which the backward's pull takes again."""
    shape = g.shape[:1] + (g.shape[-1],) * 2
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    upto = jnp.where(row >= col, 1.0, 0.0)
    spans = _dot(upto, _strictly_lower(jnp.broadcast_to(_column(g), shape)),
                 _NN)
    return jnp.exp(jnp.where(row >= col, spans, -jnp.inf)), upto


def _system(keys, decay, beta):
    """``(beta k k^T, L)`` of one value head of each key head of a block:
    ``keys`` = ``k k^T`` ``[heads, C, C]``, ``decay`` ``D``, ``beta`` a row
    ``[heads, 1, C]``, going on the ROWS — ``_gates``' ``lower``."""
    scaled = _column(beta) * keys
    return scaled, _strictly_lower(scaled * decay)


def _inverse_in_vmem(lower):
    """``(I + lower)^-1`` of strictly lower ``[heads, C, C]``, exactly, as
    ``_inverse_by_blocks`` takes it: the diagonal blocks of ``KERNEL_BLOCK``
    rows (or the largest power of two dividing C) by forward substitution,
    all at once, one column of every block a step; then the blocks joined
    level by level, ``T_2s = T_s - T_s (L on the corners of the 2s blocks)
    T_s`` — ``[[A, 0], [B, D]]^-1`` for every pair of blocks in two
    products."""
    shape, C = lower.shape, lower.shape[-1]
    block = math.gcd(C, KERNEL_BLOCK)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    inverse = jnp.where(row == col, 1.0, 0.0)
    # column j of a row's own diagonal block is the one at offset j
    offset = col - jnp.bitwise_and(row, -block)
    for j in range(block - 1):
        factor = jnp.sum(jnp.where(offset == j, lower, 0.0), axis=-1,
                         keepdims=True)
        # row j of each block, final now, down the rows of its block
        tiles = inverse.reshape(shape[0], C // block, block, C)
        pivot = jnp.broadcast_to(tiles[:, :, j:j + 1], tiles.shape)
        inverse = inverse - factor * pivot.reshape(shape)
    size = block
    while size < C:
        corner = ((jnp.bitwise_and(row, -2 * size)
                   == jnp.bitwise_and(col, -2 * size))
                  & (jnp.bitwise_and(row, size) > jnp.bitwise_and(col, size)))
        joined = _dot(jnp.where(corner, lower, 0.0), inverse, _NN)
        inverse = inverse - _dot(inverse, joined, _NN)
        size *= 2
    return inverse


def supported(dk, dv, chunk, dtype):
    """Whether the kernels take these shapes: the state's two widths whole
    lane tiles, a chunk whole sublane tiles of the output's dtype.  Every
    such chunk suits the inverse in VMEM (its diagonal blocks are the
    largest power of two up to ``KERNEL_BLOCK`` dividing the chunk, 8 rows
    or more): no chunk falls back to XLA for it."""
    return (dk % 128 == 0 and dv % 128 == 0
            and chunk % (32 // jnp.dtype(dtype).itemsize) == 0)


def kernel_walks(dk, dv, T, dtype, chunk=None, interpret=False):
    """Whether ``gated_delta_rule`` walks ``T`` steps of these widths with
    the kernels: on a TPU (or interpreted, for the tests) at a supported
    shape; XLA's walk everywhere else."""
    return ((interpret or jax.default_backend() == "tpu")
            and supported(dk, dv, _layout(T, chunk or DELTA_CHUNK)[0],
                          dtype))


def _column(row):
    """Rows ``[heads, 1, C]`` as columns ``[heads, C, 1]``: a factor for
    the ROWS of a ``[heads, C, d]`` batch, as the row itself is for its
    columns."""
    return row[:, 0, :][:, :, None]


def _walk_kernel(s0_ref, g_ref, beta_ref, grown_ref, rest_ref,
                 shrink_ref, q_ref, k_ref, v_ref,
                 o_ref, starts_ref, last_ref, s_ref):
    f32 = jnp.float32
    chunk = pl.program_id(1)

    @pl.when(chunk == 0)
    def _():
        s_ref[...] = s0_ref[...]

    starts_ref[...] = s_ref[...]
    q, k = q_ref[...].astype(f32), k_ref[...].astype(f32)
    pairs, keys = _dot(q, k, _NT), _dot(k, k, _NT)
    for i in range(s_ref.shape[1]):          # a key head's value heads
        S, grown, beta = s_ref[:, i], grown_ref[:, i], beta_ref[:, i]
        decay, _ = _decay_in_vmem(g_ref[:, i])
        _, lower = _system(keys, decay, beta)
        solve = _inverse_in_vmem(lower) * beta
        fresh = (_dot(solve, v_ref[:, i].astype(f32), _NN)
                 - _dot(_dot(solve * grown, k, _NN), S, _NN))
        o_ref[:, i] = (_column(grown) * _dot(q, S, _NN)
                       + _dot(pairs * decay, fresh, _NN)
                       ).astype(o_ref.dtype)
        s_ref[:, i] = (shrink_ref[:, i] * S
                       + _dot(k, _column(rest_ref[:, i]) * fresh, _TN))

    @pl.when(chunk == pl.num_programs(1) - 1)
    def _():
        last_ref[...] = s_ref[...]


def _walk_back_kernel(ds_in_ref, starts_ref, do_ref, g_ref, beta_ref,
                      grown_ref, rest_ref, shrink_ref, q_ref, k_ref, v_ref,
                      d_g_ref, d_beta_ref, d_grown_ref, d_rest_ref,
                      d_shrink_ref, dq_ref, dk_ref, dv_ref, ds_out_ref,
                      ds_ref):
    f32 = jnp.float32
    chunk = pl.program_id(1)

    @pl.when(chunk == 0)
    def _():
        ds_ref[...] = ds_in_ref[...]

    q, k = q_ref[...].astype(f32), k_ref[...].astype(f32)
    pairs, keys = _dot(q, k, _NT), _dot(k, k, _NT)
    dq, dk, d_pairs, d_keys = jnp.zeros_like(q), jnp.zeros_like(k), 0.0, 0.0
    for i in range(ds_ref.shape[1]):         # a key head's value heads
        S, dS = starts_ref[:, i], ds_ref[:, i]
        beta, grown = beta_ref[:, i], grown_ref[:, i]
        decay, upto = _decay_in_vmem(g_ref[:, i])
        grown_col, rest = _column(grown), _column(rest_ref[:, i])
        v, d_o = v_ref[:, i].astype(f32), do_ref[:, i].astype(f32)
        # the forward's chunk again, from its saved start
        scaled, lower = _system(keys, decay, beta)
        inverse = _inverse_in_vmem(lower)
        solve_v = inverse * beta
        solve_k = solve_v * grown
        W = _dot(solve_k, k, _NN)
        fresh = _dot(solve_v, v, _NN) - _dot(W, S, _NN)
        k_dS, do_S = _dot(k, dS, _NN), _dot(d_o, S, _NT)
        d_fresh = _dot(pairs * decay, d_o, _TN) + rest * k_dS
        d_W = -_dot(d_fresh, S, _NT)
        d_scores = _dot(d_o, fresh, _NT)             # of (q k^T) * D
        d_solve_k = _dot(d_W, k, _NT)
        d_solve_v = _dot(d_fresh, v, _NT) + d_solve_k * grown
        # T's gradient pulled back through the inverse, -T^T dT T^T, then
        # through L: to D, to beta on L's rows and to k k^T
        d_lower = _strictly_lower(-_dot(
            _dot(inverse, d_solve_v * beta, _TN), inverse, _NT))
        d_scaled = d_lower * decay
        # D's gradient, through the exponential and the span sums to g
        d_spans = (d_scores * pairs + d_lower * scaled) * decay
        d_g_ref[:, i] = jnp.sum(_strictly_lower(_dot(upto, d_spans, _TN)),
                                axis=-1)[:, None, :]
        d_pairs += d_scores * decay
        d_keys += _column(beta) * d_scaled
        dv_ref[:, i] = _dot(solve_v, d_fresh, _TN).astype(dv_ref.dtype)
        dq += grown_col * do_S
        dk += _dot(rest * fresh, dS, _NT) + _dot(solve_k, d_W, _TN)
        # of the factors on T's columns: sums down the columns, rows as
        # they are; of those on a batch's rows: sums along the rows, laid
        # out as rows
        d_beta_ref[:, i] = (
            jnp.sum(d_solve_v * inverse, axis=1, keepdims=True)
            + jnp.sum(d_scaled * keys, axis=-1)[:, None, :])
        d_grown_ref[:, i] = (
            jnp.sum(d_solve_k * solve_v, axis=1, keepdims=True)
            + jnp.sum(q * do_S, axis=-1)[:, None, :])
        d_rest_ref[:, i] = jnp.sum(fresh * k_dS, axis=-1)[:, None, :]
        # <S, dS> summed over dk here, over dv outside: a row, not a scalar
        d_shrink_ref[:, i] = jnp.sum(S * dS, axis=1, keepdims=True)
        ds_ref[:, i] = (shrink_ref[:, i] * dS + _dot(q, grown_col * d_o, _TN)
                        - _dot(W, d_fresh, _TN))
    dq = dq + _dot(d_pairs, k, _NN)
    dk = (dk + _dot(d_pairs, q, _TN) + _dot(d_keys, k, _NN)
          + _dot(d_keys, k, _TN))
    dq_ref[...], dk_ref[...] = dq, dk

    @pl.when(chunk == pl.num_programs(1) - 1)
    def _():
        ds_out_ref[...] = ds_ref[...]


_WALK_ORDER = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _kernel_operands(S, x, rows, reverse):
    """What both kernels read of a segment, head-major, with its block
    specs: g, ``_rows``' results and q, k, v ``[chunks, key heads, ...]``,
    a block of key heads (each with its ``r`` value heads) a grid step."""
    q, k, v, g, _ = x
    chunks, r, (C, dv), dk = v.shape[0], v.shape[3], v.shape[-2:], q.shape[-1]
    K = math.prod(S.shape[:2])                       # rows x key heads
    kb = math.gcd(K, max(1, DELTA_HEADS // r))
    at = (lambda c: chunks - 1 - c) if reverse else (lambda c: c)
    per_head = lambda a, b: pl.BlockSpec(
        (None, kb, r, a, b), lambda h, c: (at(c), h, 0, 0, 0))
    per_key_head = pl.BlockSpec((None, kb, C, dk),
                                lambda h, c: (at(c), h, 0, 0))
    state = pl.BlockSpec((kb, r, dk, dv), lambda h, c: (h, 0, 0, 0))
    *steps, shrink = rows
    heads = lambda x, *dims: x.reshape(chunks, K, r, *dims)
    operands = [*(heads(row, 1, C) for row in (g.astype(jnp.float32),
                                               *steps)),
                # a scalar a head rides as a row of the state's width
                heads(jnp.broadcast_to(shrink[..., None], shrink.shape
                                       + (dv,)), 1, dv),
                q.reshape(chunks, K, C, dk), k.reshape(chunks, K, C, dk),
                heads(v, C, dv)]
    specs = [per_head(1, C)] * 4 + [
        per_head(1, dv), per_key_head, per_key_head, per_head(C, dv)]
    return (operands, specs, per_head, per_key_head, state,
            (chunks, K, kb, r, C, dk, dv))


def _walk_pallas(S, x, out_dtype, interpret):
    """``_walk`` with the chunks inside one kernel call."""
    _, _, _, g, beta = x
    operands, specs, per_head, _, state, dims = _kernel_operands(
        S, x, _rows(g, beta), reverse=False)
    chunks, K, kb, r, C, dk, dv = dims
    f32 = jnp.float32
    out, starts, last = pl.pallas_call(
        _walk_kernel,
        out_shape=(jax.ShapeDtypeStruct((chunks, K, r, C, dv), out_dtype),
                   jax.ShapeDtypeStruct((chunks, K, r, dk, dv), f32),
                   jax.ShapeDtypeStruct((K, r, dk, dv), f32)),
        grid=(K // kb, chunks),
        in_specs=[state, *specs],
        out_specs=(per_head(C, dv), per_head(dk, dv), state),
        scratch_shapes=[pltpu.VMEM((kb, r, dk, dv), f32)],
        compiler_params=_WALK_ORDER,
        interpret=interpret,
    )(S.reshape(K, r, dk, dv), *operands)
    return last.reshape(S.shape), (out.reshape(chunks, *S.shape[:3], C, dv),
                                   starts.reshape(chunks, *S.shape))


def _walk_back_pallas(dS, starts, d_out, x, interpret):
    """``_walk_back`` with the chunks inside one kernel call: it forms each
    chunk's ``D`` and ``T`` again in VMEM and pulls their gradients back
    through the inverse, ``L`` and the span sums there, and hands out the
    gradients of q, k and v whole, g's part through ``D``, and those of
    ``_rows``' results, which XLA pulls back to g and beta."""
    q, k, v, g, beta = x
    rows, pull_rows = jax.vjp(_rows, g, beta)
    operands, specs, per_head, per_key_head, state, dims = _kernel_operands(
        dS, x, rows, reverse=True)
    chunks, K, kb, r, C, dk, dv = dims
    f32 = jnp.float32
    shaped = lambda *dims: jax.ShapeDtypeStruct((chunks, K, r, *dims), f32)
    d_g, *d_rows, d_shrink, dq, dk_, dv_, dS = pl.pallas_call(
        _walk_back_kernel,
        out_shape=(*[shaped(1, C)] * 4, shaped(1, dv),
                   jax.ShapeDtypeStruct((chunks, K, C, dk), f32),
                   jax.ShapeDtypeStruct((chunks, K, C, dk), f32),
                   jax.ShapeDtypeStruct((chunks, K, r, C, dv), v.dtype),
                   jax.ShapeDtypeStruct((K, r, dk, dv), f32)),
        grid=(K // kb, chunks),
        in_specs=[state, per_head(dk, dv), per_head(C, dv), *specs],
        out_specs=(*[per_head(1, C)] * 4, per_head(1, dv),
                   per_key_head, per_key_head, per_head(C, dv), state),
        scratch_shapes=[pltpu.VMEM((kb, r, dk, dv), f32)],
        compiler_params=_WALK_ORDER,
        interpret=interpret,
    )(dS.reshape(K, r, dk, dv), starts.reshape(chunks, K, r, dk, dv),
      d_out.reshape(chunks, K, r, C, dv), *operands)
    d_rows = [d.reshape(m.shape) for d, m in zip(d_rows, rows)]
    d_shrink = jnp.sum(d_shrink, axis=(-2, -1)).reshape(rows[-1].shape)
    gg, gbeta = pull_rows((*d_rows, d_shrink))
    return dS.reshape(starts.shape[1:]), (
        dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
        gg + d_g.reshape(g.shape), gbeta)


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


def _walkers(q, v, chunk, interpret):
    """``(walk, walk_back)`` for these inputs: the kernels' or XLA's."""
    if kernel_walks(q.shape[-1], v.shape[-1], q.shape[1], v.dtype, chunk,
                    interpret):
        return (functools.partial(_walk_pallas, interpret=interpret),
                functools.partial(_walk_back_pallas, interpret=interpret))
    return _walk, _walk_back


def _forward(q, k, v, g, beta, chunk, interpret=False):
    rows, T, hk, dk = q.shape
    layout = _layout(T, chunk)
    xs = tuple(_chunks(x, *layout) for x in _grouped(q, k, v, g, beta))
    walk, _ = _walkers(q, v, chunk, interpret)
    S0 = jnp.zeros((rows, hk, v.shape[2] // hk, dk, v.shape[-1]),
                   jnp.float32)
    _, (out, starts) = jax.lax.scan(
        lambda S, x: walk(S, x, v.dtype), S0, xs)
    return _unchunk(out, T).reshape(v.shape), starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, g, beta, chunk, interpret):
    return _forward(q, k, v, g, beta, chunk, interpret)[0]


def _rule_fwd(q, k, v, g, beta, chunk, interpret):
    out, starts = _forward(q, k, v, g, beta, chunk, interpret)
    # named for the recomputation policies: with both saved the replayed
    # forward has no consumer and is dropped, as the selective scan's
    out, starts = (checkpoint_name(out, DELTA_OUT),
                   checkpoint_name(starts, DELTA_STATES))
    return out, (q, k, v, g, beta, starts)


def _rule_bwd(chunk, interpret, res, d_out):
    *inputs, starts = res
    q, v = inputs[0], inputs[2]
    T = q.shape[1]
    layout = _layout(T, chunk)
    xs = tuple(_chunks(x, *layout) for x in (
        *_grouped(*inputs), d_out.reshape(starts.shape[2], T,
                                          *starts.shape[3:5], -1)))
    _, walk_back = _walkers(q, v, chunk, interpret)

    def segment(dS, x):
        *x, d_o, S_starts = x
        return walk_back(dS, S_starts, d_o, x)

    _, grads = jax.lax.scan(segment, jnp.zeros_like(starts[0, 0]),
                            (*xs, starts), reverse=True)
    return tuple(_unchunk(d, T).reshape(x.shape).astype(x.dtype)
                 for d, x in zip(grads, inputs))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk=None, interpret=False):
    """See the module docstring.  ``chunk``: steps per chunk (default
    ``DELTA_CHUNK``; any value gives the same result).  ``interpret``: run
    the kernels in interpret mode, off the TPU (tests)."""
    if v.shape[2] % q.shape[2]:
        raise ValueError(f"{v.shape[2]} value heads on {q.shape[2]} key heads")
    return _rule(q, k, v, g, beta, chunk or DELTA_CHUNK, interpret)


def chunk_layout(T, chunk=None):
    """``(steps per chunk, chunks per sequence)`` the rule walks ``T`` steps
    in (the padded count): what the model reports as gauges."""
    chunk, per_segment, segments = _layout(T, chunk or DELTA_CHUNK)
    return chunk, per_segment * segments
