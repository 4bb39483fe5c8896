"""Grouped matmul over ragged groups: ``rows`` [R, k], sorted by group, times
each group's own matrix ``w`` [e, k, n] -> [R, n]; ``sizes`` [e] (int32) are
the groups' row counts and may sum to less than ``R``.  What a dropless
expert layer's products are (``models/moe.grouped_swiglu``).

On a TPU the three products — the forward, the rows' gradient and the
matrices' gradient — are jax's megablox Pallas kernels
(``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` walks only the row
tiles the groups cover, ``tgmm`` contracts over a group's rows), called from
a ``custom_vjp`` of this module so that each of the three gets tiles of its
own (megablox's own wrapper hands the forward's tiles to problems whose
``k`` and ``n`` are swapped).  Elsewhere ``jax.lax.ragged_dot``.

Why not ``ragged_dot`` on the TPU too: libtpu 0.0.34 does lower it to a
kernel of its own, but under the compiler's label (``op_name =
"ragged-dot-none"``) in place of the jax name stack, so a device trace can
place it under no ``dstpu/`` scope and in no phase (PERF.md, PR 33).

Rows past ``sum(sizes)`` belong to no group: the kernels neither read nor
write them (the output there is whatever the buffer held).  Callers mask.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: rows per tile, the largest that divides the row count (the sorted rows are
#: walked in tiles of this many; a group boundary inside a tile costs one
#: more visit of it)
TILE_ROWS = (512, 256, 128)
#: VMEM a call's tiles may take, of Mosaic's 16 MiB scoped limit on a v5e:
#: double-buffered operand and result tiles + the fp32 accumulator (+ the
#: fp32 copies ``tgmm`` masks its operands in)
VMEM_BUDGET = 10 * 1024 * 1024


def _divisors(size):
    """Tile widths for a dimension: its divisors that are whole 128-lane
    multiples, else the dimension itself."""
    found = [t for t in range(128, size + 1, 128) if size % t == 0]
    return found or [size]


def _tiles(tm, k, n, itemsize, transposed):
    """``(tm, tk, tn)`` for a product of tiles of ``tm`` rows with ``[k, n]``
    matrices: the widest ``tk x tn`` whose tiles fit ``VMEM_BUDGET`` (ties:
    the wider ``tn``, so the rows are read fewer times).  ``transposed``:
    the matrices' gradient (``tgmm``), whose result tile is ``tk x tn`` and
    whose operands are both row tiles."""
    best = None
    for tk in _divisors(k):
        for tn in _divisors(n):
            if transposed:
                need = (2 * itemsize * (tm * tk + tm * tn + tk * tn)
                        + 4 * tk * tn + 4 * (tm * tk + tm * tn))
            else:
                need = (2 * itemsize * (tm * tk + tk * tn + tm * tn)
                        + 4 * tm * tn)
            if need <= VMEM_BUDGET and (best is None
                                        or (tk * tn, tn) > best[0]):
                best = ((tk * tn, tn), (tm, tk, tn))
    if best is None:
        return tm, 128, 128
    return best[1]


def _tile_rows(rows):
    return next((t for t in TILE_ROWS if rows % t == 0), None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(rows, w, sizes, interpret):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    k, n = w.shape[1:]
    return gmm(rows, w, sizes, rows.dtype,
               _tiles(_tile_rows(rows.shape[0]), k, n, rows.dtype.itemsize,
                      False), interpret=interpret)


def _gmm_fwd(rows, w, sizes, interpret):
    return _gmm(rows, w, sizes, interpret), (rows, w, sizes)


def _gmm_bwd(interpret, res, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
    rows, w, sizes = res
    k, n = w.shape[1:]
    tm, item = _tile_rows(rows.shape[0]), rows.dtype.itemsize
    d_rows = gmm(g, w, sizes, rows.dtype, _tiles(tm, n, k, item, False),
                 transpose_rhs=True, interpret=interpret)
    d_w = tgmm(rows.swapaxes(0, 1), g, sizes, w.dtype,
               _tiles(tm, k, n, item, True), num_actual_groups=w.shape[0],
               interpret=interpret)
    return d_rows, d_w, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(rows, w, sizes, interpret=False):
    """``out[i] = rows[i] @ w[group of row i]`` for the rows the groups
    cover (module docstring).  ``w`` is cast to ``rows``' dtype.  The
    kernels take a row count that is a whole number of 128-row tiles and
    refuse any other (``ragged_dot`` there would run under no scope).
    ``interpret``: run the Pallas kernels in interpret mode (tests)."""
    w = w.astype(rows.dtype)
    if not (interpret or jax.default_backend() == "tpu"):
        return jax.lax.ragged_dot(rows, w, sizes)
    if _tile_rows(rows.shape[0]) is None:
        raise ValueError(
            f"grouped_matmul: {rows.shape[0]} rows are no whole number of "
            f"{TILE_ROWS[-1]}-row tiles (tokens x experts per token of the "
            f"micro-batch)")
    return _gmm(rows, w, sizes.astype(jnp.int32), interpret)
