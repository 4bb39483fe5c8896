"""State-space duality (Mamba-2's SSD, arXiv:2405.21060), chunked.

``state_space_duality(x, dt, A, B, C, D)`` computes, per row and head ``h``,
with a MATRIX-valued state ``S`` ``[N, P]`` and a SCALAR decay a step,

    S_t = exp(dt_t A_h) S_{t-1} + B_t (dt_t x_t)^T      S_0 = 0
    y_t = S_t^T C_t + D_h x_t

``x`` ``[rows, T, H, P]``, ``dt`` ``[rows, T, H]`` (the step sizes, after
their softplus), ``A`` ``[H]`` (negative), ``B``/``C`` ``[rows, T, G, N]``
and ``D`` ``[H]``; group ``i`` of ``G`` serves the heads ``i H/G ... (i + 1)
H/G - 1``.  It is the gated delta rule (``ops/delta_rule.py``) without its
correction: no step takes away what the state already predicts, so a chunk
needs no triangular solve.  The state and everything that touches it run in
float32 at the highest matmul precision whatever the inputs' dtype; ``y``
comes back in ``x``'s dtype.

The chunked form (exact in exact arithmetic; chunk ``C``, per head): with
``g_t = dt_t A``, ``gamma_i = sum_{j<=i} g_j`` and ``D_ij = exp(gamma_i -
gamma_j)`` for ``i >= j``, else 0 (``delta_rule._pair_decay``: each span
summed from its own start),

    y = (C B^T * D) (dt x) + exp(gamma) (C S_start) + D_h x
    S_end = exp(gamma_C) S_start + B^T (exp(gamma_C - gamma) dt x)

A group's heads share ``B`` and ``C``, so each head's decays go on the
VALUE side and the state of a group is one ``[N, (H/G) P]`` matrix: the
chunk's write is one ``[N, C] x [C, (H/G) P]`` product, its read one ``[C,
N] x [N, (H/G) P]`` product (at Granite's one group of 128 and 64 heads of
64: 128 x 4,096), and a 64-wide head needs no lane tile of its own.  Only
``C B^T * D`` is per head.

The chunks are walked in the delta rule's layout (``delta_rule._layout`` /
``_chunks``: SEGMENTS of up to ``DELTA_SEGMENT`` chunks, the tail padded
with steps of ``dt`` 0, which leave the state alone): a segment's chunks
make their within-chunk outputs and their writes into the state in one
batch, a ``lax.scan`` over the chunks carries the state from one to the
next, and the outputs from the state are again one batch.  The forward
keeps its inputs and the state at every SEGMENT's start; the backward walks
the segments in reverse and differentiates each one from its saved start
(``jax.vjp`` of the segment: its chunks' states are the segment's own, for
as long as its backward runs).  Nothing ``[T, N, P]`` is in HBM in either
direction; what a segment holds that is ``[C, C]`` a head (the decays and
the masked scores) is bounded by the segment's length.  An XLA walk: a
Pallas walk would take this module's place under the same scope.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# the delta rule's chunk layout, segments, span-summed pair decays and
# per-step decays, shared so that both walks chunk alike
from deepspeed_tpu.ops.delta_rule import (_chunks, _layout, _pair_decay,
                                          _rows, _unchunk)
from deepspeed_tpu.ops.remat_names import SSD_OUT, SSD_STATES

#: steps per chunk (the published Mamba-2 chunk is 256; any chunk gives the
#: same result): at T 8,192 64 chunks in two segments of 32, 4,096 steps a
#: segment, whose decays and masked scores are 134 MB each at 64 heads
SSD_CHUNK = 128

_HIGHEST = jax.lax.Precision.HIGHEST
_einsum = functools.partial(jnp.einsum, precision=_HIGHEST,
                            preferred_element_type=jnp.float32)


def _grouped(x, dt, B, C):
    """The heads as ``[G, H/G]``, ``B`` and ``C`` as ``[G, 1]``: a group's
    heads read ITS ``B`` and ``C`` by broadcasting, none is copied."""
    groups = B.shape[2]
    split = lambda t: t.reshape(*t.shape[:2], groups, -1, *t.shape[3:])
    return split(x), split(dt), B[:, :, :, None], C[:, :, :, None]


def _segment(S, x, dt, B, C, A, D):
    """One segment's chunks, in float32: ``S`` ``[rows, G, N, H/G, P]`` at
    the segment's start; ``x`` ``[chunks, rows, G, H/G, C, P]``, ``dt``
    ``[chunks, rows, G, H/G, C]``, ``B``/``C`` ``[chunks, rows, G, 1, C,
    N]`` (the ``_chunks`` layout), ``A``/``D`` ``[G, H/G]`` -> ``(S at the
    segment's end, y like x in x's dtype)``."""
    f32 = jnp.float32
    g = dt * A[..., None]                                  # log decays <= 0
    # (``_rows``' first result, a write strength, is the delta rule's own)
    _, grown, after, shrink = _rows(g, dt)
    xf = x.astype(f32)
    written = dt[..., None] * xf
    Bf, Cf = B[:, :, :, 0].astype(f32), C[:, :, :, 0].astype(f32)
    # within the chunk: (C B^T * D) (dt x), the scores shared by a group
    scores = _einsum("cbgim,cbgjm->cbgij", Cf, Bf)[:, :, :, None]
    y = _einsum("cbghij,cbghjp->cbghip", scores * _pair_decay(g), written)
    # each chunk's own write into its group's [N, (H/G) P] state
    writes = _einsum("cbgjm,cbghjp->cbgmhp", Bf, after[..., None] * written)

    def carry(S, chunk):
        write, decay = chunk
        return decay[:, :, None, :, None] * S + write, S

    S, starts = jax.lax.scan(carry, S, (writes, shrink))
    # from the state each chunk starts with
    y = y + grown[..., None] * _einsum("cbgim,cbgmhp->cbghip", Cf, starts)
    return S, (y + D[..., None, None] * xf).astype(x.dtype)


def _forward(x, dt, A, B, C, D, chunk):
    rows, T, H, P = x.shape
    groups, N = B.shape[2:]
    layout = _layout(T, chunk)
    xs = tuple(_chunks(t, *layout) for t in _grouped(x, dt, B, C))
    A, D = (t.astype(jnp.float32).reshape(groups, -1) for t in (A, D))
    S0 = jnp.zeros((rows, groups, N, H // groups, P), jnp.float32)

    def segment(S, xs):
        S_end, y = _segment(S, *xs, A, D)
        return S_end, (y, S)

    _, (y, starts) = jax.lax.scan(segment, S0, xs)
    return _unchunk(y, T).reshape(x.shape), starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd(x, dt, A, B, C, D, chunk):
    return _forward(x, dt, A, B, C, D, chunk)[0]


def _ssd_fwd(x, dt, A, B, C, D, chunk):
    y, starts = _forward(x, dt, A, B, C, D, chunk)
    # named for the recomputation policies, as the delta rule's: with both
    # saved the replayed forward has no consumer and is dropped
    y, starts = checkpoint_name(y, SSD_OUT), checkpoint_name(starts,
                                                             SSD_STATES)
    return y, (x, dt, A, B, C, D, starts)


def _ssd_bwd(chunk, res, dy):
    x, dt, A, B, C, D, starts = res
    T, groups = x.shape[1], B.shape[2]
    layout = _layout(T, chunk)
    grouped = _grouped(x, dt, B, C)
    xs = tuple(_chunks(t, *layout) for t in (*grouped,
                                              dy.reshape(grouped[0].shape)))
    Af, Df = (t.astype(jnp.float32).reshape(groups, -1) for t in (A, D))

    def segment(carry, inputs):
        dS, dA, dD = carry
        *x_seg, dy_seg, S_start = inputs
        _, pull = jax.vjp(_segment, S_start, *x_seg, Af, Df)
        dS, *d_seg, dA_seg, dD_seg = pull((dS, dy_seg))
        return (dS, dA + dA_seg, dD + dD_seg), tuple(d_seg)

    zero = jnp.zeros_like(Af)
    (_, dA, dD), grads = jax.lax.scan(
        segment, (jnp.zeros_like(starts[0]), zero, zero), (*xs, starts),
        reverse=True)
    dx, ddt, dB, dC = (_unchunk(d, T).reshape(t.shape).astype(t.dtype)
                       for d, t in zip(grads, (x, dt, B, C)))
    return (dx, ddt, dA.reshape(A.shape).astype(A.dtype), dB, dC,
            dD.reshape(D.shape).astype(D.dtype))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def state_space_duality(x, dt, A, B, C, D, chunk=None):
    """See the module docstring.  ``chunk``: steps per chunk (default
    ``SSD_CHUNK``; any value gives the same result)."""
    if B.shape != C.shape or x.shape[2] % B.shape[2]:
        raise ValueError(f"B {B.shape} and C {C.shape}: one shape, and "
                         f"{x.shape[2]} heads in whole groups of "
                         f"{B.shape[2]}")
    return _ssd(x, dt, A, B, C, D, chunk or SSD_CHUNK)


def chunk_layout(T, chunk=None):
    """``(steps per chunk, chunks per sequence)`` the op walks ``T`` steps
    in (the padded count): what the model reports as gauges."""
    chunk, per_segment, segments = _layout(T, chunk or SSD_CHUNK)
    return chunk, per_segment * segments
