"""Multi-head attention: the streaming Pallas kernel and the XLA path.

``stream_attention`` is flash-attention-style ONLINE-SOFTMAX streaming over
KV tiles (gate: ``stream_supported``): scores and probabilities stay in
VMEM a tile pair at a time, a causal call neither computes nor fetches the
tiles the band hides, and its output and log-sum-exp carry checkpoint
names (``_name_stream_residuals``) so that no recomputation policy runs
the forward kernel twice.  ``xla_attention`` is the plain einsum path (it
materialises the [B, n, T, T] fp32 scores in HBM): short sequences and
other backends run it, and the kernel's tests hold the kernel to it.
``models/layers.py attention_plan`` picks one per call shape; what each
cell runs, and what it costs there, is in the benchmark ledger's rows
(PERF.md §3 and §5).

Numerics: scores and probabilities are fp32 (max-subtracted softmax); the
probability·V contraction runs in the input dtype (bf16 on TPU) with fp32
accumulation — the same contract on both paths.

Both take ``q: [B, T, n, d]``, ``k`` and ``v`` with as many or fewer
heads, and ``attn_mask: [B, T]`` float (1 = attend; pass ones for none).
``interpret=True`` runs the kernel anywhere (CPU tests).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.remat_names import ATTN_LSE, ATTN_OUT

# ==================================================================== stream
# Flash-attention-style ONLINE-SOFTMAX streaming over KV tiles.  Standard
# algebra: the forward keeps a running (row max, denom,
# accumulator) per query tile and emits the logsumexp; the backward
# recomputes probabilities from the logsumexp block-wise.  Default backward
# is a SINGLE fused pass over the (kv tile, query tile) grid producing dQ,
# dK and dV together — the score recompute (QK^T, exp, dP) runs once per
# tile pair instead of once in a dK/dV kernel and again in a dQ kernel,
# and q/k/v/do tiles are DMA'd once instead of twice.  dQ accumulates in a
# full-sequence fp32 VMEM scratch; ``stream_bwd_plan`` sizes that from the
# shape: inside Mosaic's default scoped VMEM the call asks for nothing,
# past it the call asks Mosaic for what it needs (``vmem_limit_bytes``) up
# to the chip generation's cap (``analysis/profiles.py kernel_vmem_mib``),
# and only past the cap does the backward take the classic two-pass split.
# delta = rowsum(dO ∘ O) is precomputed on the XLA side either way.
# Layout: [G, T, d] with G = batch * heads folded on the XLA side.

STREAM_TILE = 512      # preferred tile rows per program
STREAM_TILE_MIN = 256  # fallback when T is not a multiple of 512
_MIB = 1024 * 1024
#: Mosaic's DEFAULT scoped-VMEM limit per kernel on v5e (libtpu 0.0.34
#: names it in its RESOURCE_EXHAUSTED message) — what a call gets that asks
#: for nothing, not the chip's VMEM (128 MiB)
VMEM_SCOPED_LIMIT = 16 * _MIB
#: VMEM the fused backward needs BESIDE its dQ-resident buffers (the
#: double-buffered q/k/v/do/dk/dv tile blocks, the dK/dV scratch, matmul
#: temporaries) at a key head of up to 128 lanes, by input itemsize; half
#: of it (the q, k and dK blocks, the dQ tile) grows with the head's lane
#: tiles.  Upper bounds on the least ``vmem_limit_bytes`` under which
#: Mosaic compiled the call for a v5e, less the resident buffers, at tile
#: 512, gb 2, G 32 (libtpu 0.0.34; PERF.md §6, PR 36, has the table): bf16
#: 7.5 MiB at d=64 and d=128, 10.9 MiB at d=192 (two lane tiles: bound
#: 12); fp32 7.8 MiB at d=64, 11.8 MiB at d=128, 16.0 MiB at d=192 (18).
_FUSED_BWD_WORKING_SET = {2: 8 * _MIB, 4: 12 * _MIB}


def _stream_tile(T: int) -> int:
    return STREAM_TILE if T % STREAM_TILE == 0 else STREAM_TILE_MIN


def stream_supported(seq_len: int, head_dim: int) -> bool:
    return (seq_len % STREAM_TILE_MIN == 0 and seq_len >= STREAM_TILE_MIN
            and head_dim % 8 == 0)


def _tile_mask(s, mask, causal, i, j, qt, kt, window=None):
    """Apply the kv padding mask [gb, kt], the causal band and, under a
    sliding ``window``, its far edge (key ``s`` visible to query ``t`` iff
    ``t - window < s <= t``) to a [gb, qt, kt] score tile at (query tile i,
    kv tile j)."""
    s = jnp.where(mask[:, None, :] != 0, s, -1e9)
    if causal:
        qpos = i * qt + jax.lax.broadcasted_iota(jnp.int32, (qt, kt), 0)
        kpos = j * kt + jax.lax.broadcasted_iota(jnp.int32, (qt, kt), 1)
        s = jnp.where((kpos <= qpos)[None], s, -1e9)
        if window is not None:
            s = jnp.where((kpos > qpos - window)[None], s, -1e9)
    return s


def _tile_visible(i, j, qt, kt, window=None, n_tiles=None):
    """Whether some (query, key) pair of the (query tile i, kv tile j) pair
    of a causal call is visible: not past the diagonal and, under a
    ``window``, not out of it — where an offset grid's index may also name
    no tile of the ``n_tiles`` at all.  Python ints or traced scalars."""
    if window is not None:
        return ((j >= 0) & (i <= n_tiles - 1)
                & (j * kt <= (i + 1) * qt - 1)
                & ((j + 1) * kt - 1 > i * qt - window))
    return j * kt <= (i + 1) * qt - 1


def _window_tiles(window, tile, n_tiles):
    """Length of the kv axis of a windowed call's grid: the kv tiles one
    query tile can see (query and kv tiles of one size), e.g. 2 of the 16 at
    T 8192, window 512, tile 512.  The whole axis without a window."""
    if window is None:
        return n_tiles
    return min(n_tiles, -(-(window - 1) // tile) + 1)


def _when_visible(update, causal, window, i, j, qt, kt, n_tiles):
    """Run ``update`` unless the (query tile i, kv tile j) pair is masked
    whole (``_tile_visible``): GPT-style models pay for only the
    lower-triangular half of the tile grid."""
    if causal:
        pl.when(_tile_visible(i, j, qt, kt, window, n_tiles))(update)
    else:
        update()


def _shared_heads(x, gb):
    """A k or v block [hb, kt, d] for the ``gb`` query heads of the
    program: as it is where every query head has its own, the one head
    broadcast where the ``gb`` query heads share it."""
    if x.shape[0] == gb:
        return x
    return jnp.broadcast_to(x, (gb,) + x.shape[1:])


def _stream_fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                       m_scr, l_scr, acc_scr, *, causal, scale, nk,
                       window=None, n_tiles=None):
    """``nk``: length of the grid's kv axis.  Under a ``window`` that axis
    holds only the tiles a query tile can see and step ``j`` of it is kv
    tile ``i - (nk - 1) + j`` of the ``n_tiles`` (the index maps say the
    same, so an out-of-window tile is not fetched either)."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -1e30, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    i = pl.program_id(1)
    qt = q_ref.shape[1]
    kt = k_ref.shape[1]
    gb = q_ref.shape[0]
    jt = j if window is None else i - (nk - 1) + j      # the kv tile

    def update():
        q = q_ref[...]
        k, v = _shared_heads(k_ref[...], gb), _shared_heads(v_ref[...], gb)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        s = _tile_mask(s, mask_ref[...][:, 0, :], causal, i, jt, qt, kt,
                       window)
        m_old = m_scr[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, :, None])
        alpha = jnp.exp(m_old - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1)
        acc_scr[...] = (alpha[:, :, None] * acc_scr[...]
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v,
                            (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    _when_visible(update, causal, window, i, jt, qt, kt, n_tiles)

    @pl.when(j == nk - 1)
    def _fin():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...]
                      / jnp.maximum(l, 1e-30)[:, :, None]).astype(o_ref.dtype)
        lse_ref[...] = (m_scr[...]
                        + jnp.log(jnp.maximum(l, 1e-30)))[:, None, :]


def _recompute_p_ds(q, k, v, do, lse, delta, mask, causal, i, j, scale,
                    window=None):
    """Shared backward tile math: probabilities from the logsumexp, then
    dS (scale folded in).  Returns (p, ds) fp32 [gb, qt, kt]."""
    qt, kt = q.shape[1], k.shape[1]
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    s = _tile_mask(s, mask, causal, i, j, qt, kt, window)
    p = jnp.exp(s - lse[:, :, None])
    dp = jax.lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, :, None]) * scale
    return p, ds


def _stream_dkv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                       delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                       *, causal, scale, nq, window=None, n_tiles=None):
    """``nq``: length of the grid's query axis; under a ``window`` step
    ``i`` of it is query tile ``j + i`` (the tiles that see kv tile j).
    dK and dV come out per QUERY head: where query heads share a k or v
    head the caller sums them."""
    i = pl.program_id(2)     # query tile (innermost)
    j = pl.program_id(1)     # kv tile

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    qt = q_ref.shape[1]
    kt = k_ref.shape[1]
    gb = q_ref.shape[0]
    it = i if window is None else j + i                  # the query tile

    def update():
        q = q_ref[...]
        k, v = _shared_heads(k_ref[...], gb), _shared_heads(v_ref[...], gb)
        do = do_ref[...]
        p, ds = _recompute_p_ds(q, k, v, do, lse_ref[...][:, 0, :],
                                delta_ref[...][:, 0, :],
                                mask_ref[...][:, 0, :], causal, it, j, scale,
                                window)
        cdt = q.dtype
        bdims = ((0,), (0,))
        # contract the QUERY axis: dK += dS^T q ; dV += P^T dO
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(cdt), q, (((1,), (1,)), bdims),
            preferred_element_type=jnp.float32)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(cdt), do, (((1,), (1,)), bdims),
            preferred_element_type=jnp.float32)

    _when_visible(update, causal, window, it, j, qt, kt, n_tiles)

    @pl.when(i == nq - 1)
    def _fin():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _stream_bwd_fused_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                             delta_ref, dq_ref, dk_ref, dv_ref,
                             dq_scr, dk_scr, dv_scr,
                             *, causal, scale, nq, nk, window=None,
                             n_tiles=None):
    """Single-pass backward: one sweep of the (kv tile j, query tile i)
    grid produces dQ, dK AND dV.  The two-kernel split recomputes the
    score tile (QK^T, exp, dP) once per kernel — 7 T²d matmul passes
    total; fusing drops that to 5 and halves the q/k/v/do tile DMAs.
    dK/dV accumulate per parked kv tile (query innermost, as before);
    dQ accumulates into a full-sequence fp32 scratch sliced at the
    query-tile offset, written out on the final grid step.  ``nq``/``nk``:
    lengths of the grid's axes; under a ``window`` step ``i`` of the query
    axis is query tile ``j + i``, as in ``_stream_dkv_kernel``."""
    i = pl.program_id(2)     # query tile (innermost)
    j = pl.program_id(1)     # kv tile

    @pl.when((j == 0) & (i == 0))
    def _init_dq():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    @pl.when(i == 0)
    def _init_dkv():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    qt = q_ref.shape[1]
    kt = k_ref.shape[1]
    gb = q_ref.shape[0]
    it = i if window is None else j + i                  # the query tile

    def update():
        q = q_ref[...]
        k, v = _shared_heads(k_ref[...], gb), _shared_heads(v_ref[...], gb)
        do = do_ref[...]
        p, ds = _recompute_p_ds(q, k, v, do, lse_ref[...][:, 0, :],
                                delta_ref[...][:, 0, :],
                                mask_ref[...][:, 0, :], causal, it, j, scale,
                                window)
        cdt = q.dtype
        dsc = ds.astype(cdt)
        bdims = ((0,), (0,))
        # contract the QUERY axis: dK += dS^T q ; dV += P^T dO
        dk_scr[...] += jax.lax.dot_general(
            dsc, q, (((1,), (1,)), bdims),
            preferred_element_type=jnp.float32)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(cdt), do, (((1,), (1,)), bdims),
            preferred_element_type=jnp.float32)
        # contract the KV axis: dQ[i] += dS k
        dq_blk = jax.lax.dot_general(
            dsc, k, (((2,), (1,)), bdims),
            preferred_element_type=jnp.float32)
        # Mosaic wants the dynamic sublane offset proven tile-aligned
        rows = pl.ds(pl.multiple_of(it * qt, qt), qt)
        dq_scr[:, rows, :] += dq_blk

    _when_visible(update, causal, window, it, j, qt, kt, n_tiles)

    @pl.when(i == nq - 1)
    def _fin_dkv():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when((j == nk - 1) & (i == nq - 1))
    def _fin_dq():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _stream_dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                      delta_ref, dq_ref, dq_scr, *, causal, scale, nk,
                      window=None, n_tiles=None):
    """``nk``: length of the grid's kv axis, offset under a ``window`` as
    in ``_stream_fwd_kernel``."""
    j = pl.program_id(2)     # kv tile (innermost)
    i = pl.program_id(1)     # query tile

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    qt = q_ref.shape[1]
    kt = k_ref.shape[1]
    gb = q_ref.shape[0]
    jt = j if window is None else i - (nk - 1) + j      # the kv tile

    def update():
        q = q_ref[...]
        k, v = _shared_heads(k_ref[...], gb), _shared_heads(v_ref[...], gb)
        _, ds = _recompute_p_ds(q, k, v, do_ref[...], lse_ref[...][:, 0, :],
                                delta_ref[...][:, 0, :],
                                mask_ref[...][:, 0, :], causal, i, jt,
                                scale, window)
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), k, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    _when_visible(update, causal, window, i, jt, qt, kt, n_tiles)

    @pl.when(j == nk - 1)
    def _fin():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _stream_gb(G: int, *groups: int) -> int:
    """Query heads a program of the streaming kernels takes: 2 where they
    pair up — an even count, and every k / v ``groups`` (query heads a
    shared head) one or even, so that a pair never straddles two shared
    heads (7 query heads a key/value head: 1) — else 1."""
    paired = G % 2 == 0 and all(g == 1 or g % 2 == 0 for g in groups)
    return 2 if paired else 1


def _fold_gtd(x):
    """public [B, T, n, d] -> kernel [B*n, T, d]."""
    B, T, n, d = x.shape
    return jnp.moveaxis(x, 2, 1).reshape(B * n, T, d)


def _unfold_gtd(x, B, n):
    G, T, d = x.shape
    return jnp.moveaxis(x.reshape(B, n, T, d), 1, 2)


def _mask_gtd(attn_mask, B, T, n):
    return jnp.broadcast_to(
        attn_mask.astype(jnp.float32)[:, None, :], (B, n, T)
    ).reshape(B * n, 1, T)


def _head_group(n_q: int, n_kv: int, gb: int) -> int:
    """Query heads per k (or v) head, checked against the ``gb`` query heads
    a program takes: a kv block is those ``gb`` heads' own (group 1) or the
    one head they share."""
    group, rest = divmod(n_q, n_kv)
    if rest or (group > 1 and group % gb):
        raise ValueError(
            f"{n_q} query heads over {n_kv} key/value heads: the streaming "
            f"kernel takes whole groups of query heads, {gb} heads a program")
    return group


def _kv_spec(x, gb, group, kt, tile_of):
    """BlockSpec of a folded k or v operand ``x`` [G / group, T, d]:
    ``tile_of(a, b)`` is the kv tile at the grid's two inner indices; the
    heads are the program's own ``gb`` or, where ``group`` query heads share
    one, that one (found by index map: nothing is repeated in HBM)."""
    d = x.shape[-1]
    if group == 1:
        return pl.BlockSpec((gb, kt, d),
                            lambda g, a, b: (g, tile_of(a, b), 0))
    return pl.BlockSpec((1, kt, d),
                        lambda g, a, b: (g * gb // group, tile_of(a, b), 0))


def _kv_tile(causal, window, nk):
    """``(i, j) -> kv tile`` of a grid whose kv axis (``nk`` long) is
    innermost under query tile i: the forward's and the dQ kernel's."""
    if window is not None:
        # step j of the kv axis is tile i - (nk - 1) + j; before the
        # sequence's start the first tile stays parked and the kernel skips
        return lambda i, j: jnp.maximum(i - (nk - 1) + j, 0)
    if causal:
        # past the diagonal the diagonal tile stays parked: the pipeline
        # fetches no hidden tile, and the kernel skips it
        return lambda i, j: jnp.minimum(j, i)
    return lambda i, j: j


def _q_tile(causal, window, n_tiles):
    """``(j, i) -> query tile`` of a grid whose query axis is innermost
    under kv tile j: the fused and the dK/dV kernel's."""
    if window is not None:
        return lambda j, i: jnp.minimum(j + i, n_tiles - 1)
    if causal:
        # before the diagonal the diagonal tile is fetched and stays
        # parked until it is visited
        return lambda j, i: jnp.maximum(i, j)
    return lambda j, i: i


def _check_window(window, causal):
    if window is not None and not (causal and window >= 1):
        raise ValueError(f"a sliding window (got {window!r}) is a bound on "
                         f"a causal mask: key s is visible to query t iff "
                         f"t - window < s <= t")


def _stream_fwd_impl(q, k, v, attn_mask, causal, interpret, window=None,
                     scale=None):
    _check_window(window, causal)
    B, T, n, d = q.shape
    dv = v.shape[-1]
    G = B * n
    gb = _stream_gb(G, n // k.shape[2], n // v.shape[2])
    k_group = _head_group(n, k.shape[2], gb)
    v_group = _head_group(n, v.shape[2], gb)
    qt = kt = _stream_tile(T)
    nq, n_tiles = T // qt, T // kt
    nk = _window_tiles(window, kt, n_tiles)
    scale = 1.0 / (d ** 0.5) if scale is None else scale
    qg, kg, vg = _fold_gtd(q), _fold_gtd(k), _fold_gtd(v)
    maskg = _mask_gtd(attn_mask, B, T, n)
    kv_tile = _kv_tile(causal, window, nk)
    q_spec = pl.BlockSpec((gb, qt, d), lambda g, i, j: (g, i, 0))
    o_spec = pl.BlockSpec((gb, qt, dv), lambda g, i, j: (g, i, 0))
    k_spec = _kv_spec(kg, gb, k_group, kt, kv_tile)
    v_spec = _kv_spec(vg, gb, v_group, kt, kv_tile)
    # row vectors ride as [G, 1, T]: Mosaic wants the last two block
    # dims (8, 128)-tileable or equal to the array dims
    mask_spec = pl.BlockSpec((gb, 1, kt),
                             lambda g, i, j: (g, 0, kv_tile(i, j)))
    row_spec = pl.BlockSpec((gb, 1, qt), lambda g, i, j: (g, 0, i))
    windowed = ({} if window is None
                else {"window": window, "n_tiles": n_tiles})
    o, lse = pl.pallas_call(
        functools.partial(_stream_fwd_kernel, causal=causal, scale=scale,
                          nk=nk, **windowed),
        out_shape=(jax.ShapeDtypeStruct((G, T, dv), q.dtype),
                   jax.ShapeDtypeStruct((G, 1, T), jnp.float32)),
        grid=(G // gb, nq, nk),
        in_specs=[q_spec, k_spec, v_spec, mask_spec],
        out_specs=(o_spec, row_spec),
        scratch_shapes=[pltpu.VMEM((gb, qt), jnp.float32),
                        pltpu.VMEM((gb, qt), jnp.float32),
                        pltpu.VMEM((gb, qt, dv), jnp.float32)],
        interpret=interpret,
    )(qg, kg, vg, maskg)
    return o, lse, (qg, kg, vg, maskg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def stream_attention(q, k, v, attn_mask, causal: bool = False,
                     interpret: bool = False, window=None, scale=None):
    """Streaming (online-softmax) attention for long sequences.

    q: [B, T, n, d]; k: [B, T, n / gk, d] and v: [B, T, n / gv, dv], where
    ``gk`` (``gv``) consecutive query heads share a key (value) head and a
    value head may be wider than a key head; attn_mask: [B, T] float (1 =
    attend).  ``window``: key s is visible to query t iff ``t - window < s
    <= t`` (needs ``causal``).  A causal call's tiles the band hides — past
    the diagonal, out of the window — are neither computed nor fetched.
    ``scale``: the scores' factor, ``1 / sqrt(d)`` where None.
    Returns [B, T, n, dv] context; callers gate on
    ``stream_supported(T, d)``."""
    B, T, n, d = q.shape
    o, _, _ = _stream_fwd_impl(q, k, v, attn_mask, causal, interpret, window,
                               scale)
    return _unfold_gtd(o, B, n)


def _name_stream_residuals(out, lse):
    """Tag the attention output ``[B, T, n, d]`` and the forward kernel's
    log-sum-exp ``[G, 1, T]`` for the ``selective`` and ``full``
    recomputation policies (``remat_names.FULL_SAVES``).
    A ``custom_vjp``'s residuals are saveable under
    ``save_only_these_names`` only by name; with both saved, the forward
    ``pallas_call`` of the rematerialised computation has no consumer and
    is dropped.  The output is named unfolded, as the projection reads it,
    and the backward kernel's folded operand is re-derived from it by one
    layout copy: saved folded ``[G, T, d]``, d = 64 is padded to the
    128-lane tile and takes twice the HBM, and the copy the other way is
    needed for the projection's weight gradient anyway (measured both ways
    on the chip: PERF.md, PR 27)."""
    return checkpoint_name(out, ATTN_OUT), checkpoint_name(lse, ATTN_LSE)


def _stream_vjp_fwd(q, k, v, attn_mask, causal, interpret, window=None,
                    scale=None):
    B, T, n, d = q.shape
    o, lse, (qg, kg, vg, maskg) = _stream_fwd_impl(q, k, v, attn_mask,
                                                   causal, interpret, window,
                                                   scale)
    out, lse = _name_stream_residuals(_unfold_gtd(o, B, n), lse)
    return out, (qg, kg, vg, maskg, _fold_gtd(out), lse, B, n)


def fused_bwd_vmem(gb: int, T: int, d: int, itemsize: int) -> int:
    """Scoped VMEM, in bytes, the fused backward needs at this shape.
    Resident for the whole grid are the fp32 dQ accumulator and the
    (gb, T, d) dQ out block, which Pallas double-buffers; VMEM tiles pad
    the lane (last) dim to 128, so d=64 costs what d=128 does and d=192
    what d=256 does.  Beside them, the working set's bound."""
    lane_tiles = -(-d // 128)
    resident = gb * T * lane_tiles * 128 * (4 + 2 * itemsize)
    working = _FUSED_BWD_WORKING_SET[itemsize] * (lane_tiles + 1) // 2
    return resident + working


def _kernel_vmem_cap() -> Optional[int]:
    """The most scoped VMEM a kernel may ask for on the backend jax runs
    on, in bytes (the profile's ``kernel_vmem_mib``); None where the
    generation declares none."""
    from deepspeed_tpu.analysis import profiles
    prof = profiles.default_profile()     # an unknown TPU kind raises
    if prof is None or prof.kernel_vmem_mib is None:
        return None
    return prof.kernel_vmem_mib * _MIB


def stream_bwd_plan(gb: int, T: int, d: int, itemsize: int,
                    vmem_cap: Optional[int]) -> Tuple[str, Optional[int]]:
    """Which streaming backward a call shape takes, and the
    ``vmem_limit_bytes`` it asks Mosaic for — all read off the shape:

    - ``("fused", None)``: the need (``fused_bwd_vmem``) is inside
      Mosaic's default; the call carries no compiler parameters at all;
    - ``("fused", limit)``: past the default and within ``vmem_cap`` (the
      chip generation's, in bytes): the need rounded up to a MiB;
    - ``("split", None)``: past the cap, or no cap declared: two kernels,
      each score tile visited twice."""
    need = fused_bwd_vmem(gb, T, d, itemsize)
    if need <= VMEM_SCOPED_LIMIT:
        return "fused", None
    if vmem_cap is not None and need <= vmem_cap:
        return "fused", -(-need // _MIB) * _MIB
    return "split", None


def _sum_shared(dx, like):
    """Per-query-head gradients [G, T, d] of a k or v operand summed over
    the query heads that share a head of ``like`` [G / group, T, d] (fp32
    sum, ``like``'s dtype); as they are where none is shared."""
    group = dx.shape[0] // like.shape[0]
    if group == 1:
        return dx
    return jnp.sum(dx.reshape(like.shape[0], group, *dx.shape[1:])
                   .astype(jnp.float32), axis=1).astype(like.dtype)


def _stream_bwd_impl(qg, kg, vg, maskg, o, lse, dog, causal, interpret,
                     window=None, scale=None):
    """Streaming backward on folded operands (q [G, T, d]; k, v with G /
    group heads, v ``dv`` wide) → (dq, dk, dv), same layouts.  Fused single
    pass, under the VMEM limit ``stream_bwd_plan`` asks for, where its
    dQ-resident buffers fit the chip's cap; the two-kernel split otherwise.
    The kernels give dK and dV per query head; ``_sum_shared`` folds the
    heads that share one."""
    G, T, d = qg.shape
    dv_ = vg.shape[-1]
    k_group, v_group = G // kg.shape[0], G // vg.shape[0]
    gb = _stream_gb(G, k_group, v_group)
    qt = kt = _stream_tile(T)
    n_tiles = T // qt
    nq = nk = n_tiles
    # a window shortens the INNER axis of each grid to the tiles that can
    # see each other; the outer axis walks the whole sequence
    inner = _window_tiles(window, kt, n_tiles)
    scale = 1.0 / (d ** 0.5) if scale is None else scale
    delta = jnp.sum(dog.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]                    # [G, 1, T]
    windowed = ({} if window is None
                else {"window": window, "n_tiles": n_tiles})
    q_tile = _q_tile(causal, window, n_tiles)
    kv_tile = _kv_tile(causal, window, inner)
    # grid (G, kv tile, query tile) — query innermost, kv parked
    kv_spec_o = pl.BlockSpec((gb, kt, d), lambda g_, j, i: (g_, j, 0))
    dv_spec_o = pl.BlockSpec((gb, kt, dv_), lambda g_, j, i: (g_, j, 0))
    k_spec_o = _kv_spec(kg, gb, k_group, kt, lambda j, i: j)
    v_spec_o = _kv_spec(vg, gb, v_group, kt, lambda j, i: j)
    mask_spec_o = pl.BlockSpec((gb, 1, kt), lambda g_, j, i: (g_, 0, j))
    q_spec_o = pl.BlockSpec((gb, qt, d),
                            lambda g_, j, i: (g_, q_tile(j, i), 0))
    do_spec_o = pl.BlockSpec((gb, qt, dv_),
                             lambda g_, j, i: (g_, q_tile(j, i), 0))
    row_spec_o = pl.BlockSpec((gb, 1, qt),
                              lambda g_, j, i: (g_, 0, q_tile(j, i)))
    dkv_shapes = (jax.ShapeDtypeStruct((G, T, d), kg.dtype),
                  jax.ShapeDtypeStruct((G, T, dv_), vg.dtype))
    dkv_scratch = [pltpu.VMEM((gb, kt, d), jnp.float32),
                   pltpu.VMEM((gb, kt, dv_), jnp.float32)]
    kind, vmem_limit = stream_bwd_plan(gb, T, d, qg.dtype.itemsize,
                                       _kernel_vmem_cap())
    if kind == "fused":
        # a shape inside the default asks for nothing: the program it
        # always was
        asked = ({} if vmem_limit is None else {
            "compiler_params": pltpu.CompilerParams(
                vmem_limit_bytes=vmem_limit)})
        dq_spec = pl.BlockSpec((gb, T, d), lambda g_, j, i: (g_, 0, 0))
        dq, dk, dv = pl.pallas_call(
            functools.partial(_stream_bwd_fused_kernel, causal=causal,
                              scale=scale, nq=inner, nk=nk, **windowed),
            out_shape=(jax.ShapeDtypeStruct((G, T, d), qg.dtype),
                       *dkv_shapes),
            grid=(G // gb, nk, inner),
            in_specs=[q_spec_o, k_spec_o, v_spec_o, mask_spec_o,
                      do_spec_o, row_spec_o, row_spec_o],
            out_specs=(dq_spec, kv_spec_o, dv_spec_o),
            scratch_shapes=[pltpu.VMEM((gb, T, d), jnp.float32),
                            *dkv_scratch],
            interpret=interpret,
            **asked,
        )(qg, kg, vg, maskg, dog, lse, delta)
        return dq, _sum_shared(dk, kg), _sum_shared(dv, vg)
    dk, dv = pl.pallas_call(
        functools.partial(_stream_dkv_kernel, causal=causal, scale=scale,
                          nq=inner, **windowed),
        out_shape=dkv_shapes,
        grid=(G // gb, nk, inner),
        in_specs=[q_spec_o, k_spec_o, v_spec_o, mask_spec_o, do_spec_o,
                  row_spec_o, row_spec_o],
        out_specs=(kv_spec_o, dv_spec_o),
        scratch_shapes=dkv_scratch,
        interpret=interpret,
    )(qg, kg, vg, maskg, dog, lse, delta)
    # dQ: grid (G, query tile, kv tile) — kv innermost
    q_spec = pl.BlockSpec((gb, qt, d), lambda g_, i, j: (g_, i, 0))
    do_spec = pl.BlockSpec((gb, qt, dv_), lambda g_, i, j: (g_, i, 0))
    row_spec = pl.BlockSpec((gb, 1, qt), lambda g_, i, j: (g_, 0, i))
    k_spec = _kv_spec(kg, gb, k_group, kt, kv_tile)
    v_spec = _kv_spec(vg, gb, v_group, kt, kv_tile)
    mask_spec = pl.BlockSpec((gb, 1, kt),
                             lambda g_, i, j: (g_, 0, kv_tile(i, j)))
    dq = pl.pallas_call(
        functools.partial(_stream_dq_kernel, causal=causal, scale=scale,
                          nk=inner, **windowed),
        out_shape=jax.ShapeDtypeStruct((G, T, d), qg.dtype),
        grid=(G // gb, nq, inner),
        in_specs=[q_spec, k_spec, v_spec, mask_spec, do_spec,
                  row_spec, row_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((gb, qt, d), jnp.float32)],
        interpret=interpret,
    )(qg, kg, vg, maskg, dog, lse, delta)
    return dq, _sum_shared(dk, kg), _sum_shared(dv, vg)


def _stream_vjp_bwd(causal, interpret, window, scale, res, g):
    qg, kg, vg, maskg, o, lse, B, n = res
    dq, dk, dv = _stream_bwd_impl(qg, kg, vg, maskg, o, lse, _fold_gtd(g),
                                  causal, interpret, window, scale)
    T = qg.shape[1]
    # the mask is a float selector, not a trainable input
    return (_unfold_gtd(dq, B, n), _unfold_gtd(dk, B, kg.shape[0] // B),
            _unfold_gtd(dv, B, vg.shape[0] // B),
            jnp.zeros((B, T), jnp.float32))


stream_attention.defvjp(_stream_vjp_fwd, _stream_vjp_bwd)


# ======================================================================= xla

@jax.custom_vjp
def _qk_scores(q, k):
    """q@k^T scores with fp32 MXU accumulation on low-precision operands.

    The custom backward rounds the fp32 score cotangent to the compute
    dtype BEFORE the dq/dk transpose matmuls (fp32 accumulation kept via
    ``preferred_element_type``) — the same convention every Pallas kernel
    here uses (``ds.astype(cdt)``).  Plain autodiff would feed the fp32
    cotangent straight into the transpose dots, silently running the
    attention backward at fp32 MXU rates on the bf16/fp16 training path
    (graph-lint ``precision.upcast-dot``).  In fp32 the casts are
    identities and the math is unchanged."""
    return jnp.einsum("btnd,bsnd->bnts", q, k,
                      preferred_element_type=jnp.float32)


def _qk_scores_fwd(q, k):
    return _qk_scores(q, k), (q, k)


def _qk_scores_bwd(res, g):
    q, k = res
    gl = g.astype(q.dtype)
    dq = jnp.einsum("bnts,bsnd->btnd", gl, k,
                    preferred_element_type=jnp.float32).astype(q.dtype)
    dk = jnp.einsum("bnts,btnd->bsnd", gl, q,
                    preferred_element_type=jnp.float32).astype(k.dtype)
    return dq, dk


_qk_scores.defvjp(_qk_scores_fwd, _qk_scores_bwd)


def _repeat_heads(x, n):
    """[B, T, n / group, d] -> [B, T, n, d], each head ``group`` times in a
    row (the XLA path materialises what the streaming kernel reads through
    its index maps)."""
    return x if x.shape[2] == n else jnp.repeat(x, n // x.shape[2], axis=2)


def xla_attention(q, k, v, attn_mask, causal, window=None, scale=None):
    """Plain-XLA attention (the models/layers.py einsum path) → [B, T, n,
    dv] context.  Shared k/v heads, a wider value head, ``window`` and
    ``scale`` as in ``stream_attention``."""
    _check_window(window, causal)
    _, T, n, d = q.shape
    k, v = _repeat_heads(k, n), _repeat_heads(v, n)
    scores = _qk_scores(q, k)
    scores = (scores / jnp.sqrt(jnp.asarray(d, jnp.float32)) if scale is None
              else scores * scale)
    if causal:
        cmask = jnp.tril(jnp.ones((T, T), jnp.bool_))
        if window is not None:
            cmask = cmask & ~jnp.tril(jnp.ones((T, T), jnp.bool_), -window)
        scores = jnp.where(cmask[None, None], scores, -1e9)
    scores = jnp.where(attn_mask[:, None, None, :].astype(jnp.bool_),
                       scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bnts,bsnd->btnd", probs, v)
