"""Fused multi-head attention kernels (Pallas, TPU).

The XLA path in ``models/layers.py`` materialises the [B, n, T, T] fp32
score tensor in HBM twice per layer (scores write + softmax read) and again
in the backward replay — at BERT-large/seq128/batch96 that is ~300 MB of HBM
traffic per layer that never needed to leave the chip.  Two kernels:

* ``fused_attention`` — whole-tile: QK^T → mask → softmax → ·V entirely in
  VMEM, one program per (batch row, head block), custom-VJP backward
  recomputing probabilities in VMEM.  For shapes where the full [hb, T, T]
  score tile fits on chip (short sequences).
* ``stream_attention`` — flash-attention-style ONLINE-SOFTMAX streaming
  over KV tiles for long sequences (gate: ``stream_supported``).  Measured
  on a v5e chip END-TO-END (GPT-2 training step, causal bf16;
  bench_attn_sweep.json): 1.14x at seq 512, 1.86x at 1024, 2.44x at 2048
  — under the ``selective`` policy of that time, which ran the forward
  kernel again in the backward pass.  Its output and log-sum-exp now carry
  checkpoint names (``_name_stream_residuals``) and ``selective`` and
  ``full`` keep them, so a layer costs two kernel calls, not three, and
  the ratios above overstate today's.  ``models/layers.py``
  auto-dispatches from ``stream_auto_min(causal)`` tokens (512 causal /
  1024 non-causal on v5e).

Numerics: scores and probabilities are fp32 (max-subtracted softmax); the
probability·V contraction runs in the input dtype (bf16 on TPU) with fp32
accumulation — the same contract as the XLA path.

Use ``fused_attention(q, k, v, attn_mask, causal)`` with
``q/k/v: [B, T, n, d]`` and ``attn_mask: [B, T]`` float (1 = attend; pass
ones for none); callers gate on ``supported(...)``.  ``interpret=True`` runs
anywhere (CPU tests).
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.remat_names import ATTN_LSE, ATTN_OUT

# fp32 score-tile budget per program; several such tiles are live in the
# backward kernel, so keep a healthy margin under the ~16 MB VMEM
SCORE_TILE_BUDGET = 2 * 1024 * 1024


def _head_block(n_heads: int) -> int:
    # blocks are [bb, hb, T, d]: Mosaic needs every block dim divisible by
    # (or equal to) the array dim; hb=8 keeps the score tile bounded for
    # many-head models
    return 8 if n_heads % 8 == 0 else n_heads


def _batch_block(B: int, T: int, hb: int, budget: int) -> int:
    # enough rows per program to amortise grid/DMA overhead (tiny per-head
    # programs are latency-bound), bounded by the score-tile budget
    for bb in (8, 4, 2, 1):
        if B % bb == 0 and bb * hb * T * T * 4 <= budget:
            return bb
    return 1


def supported(seq_len: int, n_heads: int, head_dim: int) -> bool:
    hb = _head_block(n_heads)
    # gate on the BACKWARD budget (half the forward's): even at bb=1 the
    # backward keeps p/dP/dS score tiles live, so a shape that only fits the
    # forward would exhaust VMEM on the grad pass
    return (seq_len % 8 == 0 and head_dim % 8 == 0
            and hb * seq_len * seq_len * 4 <= SCORE_TILE_BUDGET // 2)


def _fold(ref):
    """[bb, hb, T, d] block -> [bb*hb, T, d] (leading-dim reshape is free;
    Mosaic's matmul supports a single batch dim)."""
    bb, hb, T, d = ref.shape
    return ref[...].reshape(bb * hb, T, d)


def _scores(q, k, mask, causal, scale):
    """[bb*hb,T,d] x [bb*hb,T,d] (native dtype) -> masked fp32 [bb*hb,T,T]
    logits; ``mask`` is already expanded to [bb*hb, T]."""
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale
    T = q.shape[1]
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
        s = jnp.where((col <= row)[None], s, -1e9)
    s = jnp.where(mask[:, None, :] != 0, s, -1e9)
    return s


def _expand_mask(mask_ref, hb):
    """[bb, 1, T] mask block -> [bb*hb, T] row mask."""
    bb, _, T = mask_ref.shape
    m = jnp.broadcast_to(mask_ref[...], (bb, hb, T))
    return m.reshape(bb * hb, T)


def _softmax(s):
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, *, causal, scale):
    # blocks are [1, hb, T, d] in the heads-first layout: the batched dots
    # need NO in-VMEM transposes, and inputs stay in their native dtype —
    # the MXU accumulates in fp32 via preferred_element_type; an explicit
    # fp32 upcast would quarter the matmul rate
    bb, hb, T, d = q_ref.shape
    q = _fold(q_ref)
    k = _fold(k_ref)
    v = _fold(v_ref)
    p = _softmax(_scores(q, k, _expand_mask(mask_ref, hb), causal, scale))
    o = jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)            # [bb*hb, T, d]
    o_ref[...] = o.reshape(bb, hb, T, d).astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref,
                dq_ref, dk_ref, dv_ref, *, causal, scale):
    bb, hb, T, d = q_ref.shape
    q = _fold(q_ref)
    k = _fold(k_ref)
    v = _fold(v_ref)
    do = _fold(do_ref)
    cdt = q.dtype
    p = _softmax(_scores(q, k, _expand_mask(mask_ref, hb), causal, scale))
    pc = p.astype(cdt)
    bdims = ((0,), (0,))
    # dV = P^T dO   (contract over the query axis, batched)
    dv = jax.lax.dot_general(pc, do, (((1,), (1,)), bdims),
                             preferred_element_type=jnp.float32)
    # dP = dO V^T
    dp = jax.lax.dot_general(do, v, (((2,), (2,)), bdims),
                             preferred_element_type=jnp.float32)
    # dS = P ∘ (dP − rowsum(dP ∘ P)) ; the scale folds into dQ/dK
    ds = (p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))).astype(cdt)
    dq = jax.lax.dot_general(ds, k, (((2,), (1,)), bdims),
                             preferred_element_type=jnp.float32) * scale
    dk = jax.lax.dot_general(ds, q, (((1,), (1,)), bdims),
                             preferred_element_type=jnp.float32) * scale
    dq_ref[...] = dq.reshape(bb, hb, T, d).astype(dq_ref.dtype)
    dk_ref[...] = dk.reshape(bb, hb, T, d).astype(dk_ref.dtype)
    dv_ref[...] = dv.reshape(bb, hb, T, d).astype(dv_ref.dtype)


def _specs(B, T, n, d, bwd=False):
    hb = _head_block(n)
    # the backward keeps ~2x more score-sized tiles live (p, dP, dS)
    bb = _batch_block(B, T, hb,
                      SCORE_TILE_BUDGET // (2 if bwd else 1))
    # kernel layout is heads-first [B, n, T, d] (the public API transposes
    # on the XLA side, where the copy fuses with the qkv slice)
    qkv = pl.BlockSpec((bb, hb, T, d), lambda i, j: (i, j, 0, 0))
    # mask rides as [B, 1, T] so the trailing block dims are (1, T)
    mask = pl.BlockSpec((bb, 1, T), lambda i, j: (i, 0, 0))
    return qkv, mask, (B // bb, n // hb)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def fused_attention(q, k, v, attn_mask, causal: bool = False,
                    interpret: bool = False):
    """q/k/v: [B, T, n, d]; attn_mask: [B, T] float (1 = attend) — pass
    ``jnp.ones`` for none.  Returns [B, T, n, d] context."""
    return _fwd(q, k, v, attn_mask, causal, interpret)


def _hf(x):
    """public [B, T, n, d] -> kernel [B, n, T, d] (XLA-side transpose)."""
    return jnp.moveaxis(x, 2, 1)


def _fwd(q, k, v, attn_mask, causal, interpret):
    B, T, n, d = q.shape
    qkv_spec, mask_spec, grid = _specs(B, T, n, d)
    scale = 1.0 / (d ** 0.5)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, scale=scale),
        out_shape=jax.ShapeDtypeStruct((B, n, T, d), q.dtype),
        grid=grid,
        in_specs=[qkv_spec, qkv_spec, qkv_spec, mask_spec],
        out_specs=qkv_spec,
        interpret=interpret,
    )(_hf(q), _hf(k), _hf(v), attn_mask[:, None, :])
    return jnp.moveaxis(out, 1, 2)


def _fused_fwd(q, k, v, attn_mask, causal, interpret):
    return _fwd(q, k, v, attn_mask, causal, interpret), (q, k, v, attn_mask)


def _block_bwd_impl(q, k, v, attn_mask, g, causal, interpret):
    """Whole-tile backward on public-layout operands → (dq, dk, dv)."""
    B, T, n, d = q.shape
    qkv_spec, mask_spec, grid = _specs(B, T, n, d, bwd=True)
    scale = 1.0 / (d ** 0.5)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, causal=causal, scale=scale),
        out_shape=(jax.ShapeDtypeStruct((B, n, T, d), q.dtype),
                   jax.ShapeDtypeStruct((B, n, T, d), k.dtype),
                   jax.ShapeDtypeStruct((B, n, T, d), v.dtype)),
        grid=grid,
        in_specs=[qkv_spec, qkv_spec, qkv_spec, mask_spec, qkv_spec],
        out_specs=(qkv_spec, qkv_spec, qkv_spec),
        interpret=interpret,
    )(_hf(q), _hf(k), _hf(v), attn_mask[:, None, :], _hf(g))
    return (jnp.moveaxis(dq, 1, 2), jnp.moveaxis(dk, 1, 2),
            jnp.moveaxis(dv, 1, 2))


def _fused_bwd(causal, interpret, res, g):
    q, k, v, attn_mask = res
    dq, dk, dv = _block_bwd_impl(q, k, v, attn_mask, g, causal, interpret)
    # mask is a float selector, not a trainable input
    return dq, dk, dv, jnp.zeros_like(attn_mask)


fused_attention.defvjp(_fused_fwd, _fused_bwd)


# ==================================================================== stream
# Flash-attention-style ONLINE-SOFTMAX streaming over KV tiles for long
# sequences (seq >= 512, where the whole-score-tile kernel above exceeds
# VMEM).  Standard algebra: the forward keeps a running (row max, denom,
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
# and only past the cap does the backward take the classic two-pass split
# (DSTPU_STREAM_BWD=fused|split pins either).
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


def _stream_fwd_impl(q, k, v, attn_mask, causal, interpret, window=None):
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
    scale = 1.0 / (d ** 0.5)
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def stream_attention(q, k, v, attn_mask, causal: bool = False,
                     interpret: bool = False, window=None):
    """Streaming (online-softmax) attention for long sequences.

    q: [B, T, n, d]; k: [B, T, n / gk, d] and v: [B, T, n / gv, dv], where
    ``gk`` (``gv``) consecutive query heads share a key (value) head and a
    value head may be wider than a key head; attn_mask: [B, T] float (1 =
    attend).  ``window``: key s is visible to query t iff ``t - window < s
    <= t`` (needs ``causal``).  A causal call's tiles the band hides — past
    the diagonal, out of the window — are neither computed nor fetched.
    Returns [B, T, n, dv] context; callers gate on
    ``stream_supported(T, d)``."""
    B, T, n, d = q.shape
    o, _, _ = _stream_fwd_impl(q, k, v, attn_mask, causal, interpret, window)
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


def _stream_vjp_fwd(q, k, v, attn_mask, causal, interpret, window=None):
    B, T, n, d = q.shape
    o, lse, (qg, kg, vg, maskg) = _stream_fwd_impl(q, k, v, attn_mask,
                                                   causal, interpret, window)
    out, lse = _name_stream_residuals(_unfold_gtd(o, B, n), lse)
    return out, (qg, kg, vg, maskg, _fold_gtd(out), lse, B, n)


def _stream_bwd_mode() -> str:
    mode = os.environ.get("DSTPU_STREAM_BWD", "auto")
    if mode not in ("auto", "fused", "split"):
        raise ValueError(
            f"DSTPU_STREAM_BWD={mode!r} is not a valid mode: use 'auto' "
            f"(fused single-pass when the dQ scratch fits the VMEM a "
            f"kernel may ask for), 'fused', or 'split' (classic two-kernel "
            f"backward)")
    return mode


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
                    vmem_cap: Optional[int],
                    mode: str = "auto") -> Tuple[str, Optional[int]]:
    """Which streaming backward a call shape takes, and the
    ``vmem_limit_bytes`` it asks Mosaic for — all read off the shape:

    - ``("fused", None)``: the need (``fused_bwd_vmem``) is inside
      Mosaic's default; the call carries no compiler parameters at all;
    - ``("fused", limit)``: past the default and within ``vmem_cap`` (the
      chip generation's, in bytes): the need rounded up to a MiB;
    - ``("split", None)``: past the cap, or no cap declared: two kernels,
      each score tile visited twice.

    ``mode`` is ``DSTPU_STREAM_BWD``: "split" pins the split, "fused" the
    fused kernel whatever the cap says (under the limit it needs)."""
    if mode == "split":
        return "split", None
    need = fused_bwd_vmem(gb, T, d, itemsize)
    if need <= VMEM_SCOPED_LIMIT:
        return "fused", None
    if mode == "fused" or (vmem_cap is not None and need <= vmem_cap):
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
                     window=None):
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
    scale = 1.0 / (d ** 0.5)
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
                                       _kernel_vmem_cap(),
                                       _stream_bwd_mode())
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


def _stream_vjp_bwd(causal, interpret, window, res, g):
    qg, kg, vg, maskg, o, lse, B, n = res
    dq, dk, dv = _stream_bwd_impl(qg, kg, vg, maskg, o, lse, _fold_gtd(g),
                                  causal, interpret, window)
    T = qg.shape[1]
    # the mask is a float selector, not a trainable input
    return (_unfold_gtd(dq, B, n), _unfold_gtd(dk, B, kg.shape[0] // B),
            _unfold_gtd(dv, B, vg.shape[0] // B),
            jnp.zeros((B, T), jnp.float32))


stream_attention.defvjp(_stream_vjp_fwd, _stream_vjp_bwd)


# ==================================================================== hybrid
# Forward and backward chosen INDEPENDENTLY per (seq, kind): the end-to-end
# sweeps (bench_attn_sweep.json) measure fwd+bwd together, but the two
# passes have different crossovers — the backward streams 5 matmul passes
# per tile pair against the forward's 2, so the kernel's DMA savings pay
# off earlier there.  ``dispatch_attention`` is the custom-VJP shell that
# lets models/layers.py pick {"xla", "block", "stream"} per direction; the
# single-impl cases degenerate to the kernels above.

ATTN_IMPLS = ("xla", "block", "stream")


def _check_impls(fwd_impl: str, bwd_impl: str) -> None:
    if fwd_impl not in ATTN_IMPLS or bwd_impl not in ATTN_IMPLS:
        raise ValueError(
            f"attention impls must be one of {ATTN_IMPLS}, got "
            f"fwd={fwd_impl!r} bwd={bwd_impl!r}")
    if bwd_impl == "stream" and fwd_impl == "block":
        raise ValueError(
            "bwd_impl='stream' needs the forward logsumexp, which the "
            "whole-tile kernel does not emit — use fwd_impl 'stream' or "
            "'xla'")


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


def xla_attention(q, k, v, attn_mask, causal, with_lse=False, window=None):
    """Plain-XLA attention (the models/layers.py einsum path), optionally
    emitting the logsumexp in the streaming kernels' [G, 1, T] layout so a
    streaming backward can follow an XLA forward.  Shared k/v heads, a wider
    value head and ``window`` as in ``stream_attention``."""
    _check_window(window, causal)
    B, T, n, d = q.shape
    k, v = _repeat_heads(k, n), _repeat_heads(v, n)
    scores = _qk_scores(q, k)
    scores = scores / jnp.sqrt(jnp.asarray(d, jnp.float32))
    if causal:
        cmask = jnp.tril(jnp.ones((T, T), jnp.bool_))
        if window is not None:
            cmask = cmask & ~jnp.tril(jnp.ones((T, T), jnp.bool_), -window)
        scores = jnp.where(cmask[None, None], scores, -1e9)
    scores = jnp.where(attn_mask[:, None, None, :].astype(jnp.bool_),
                       scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bnts,bsnd->btnd", probs, v)
    if not with_lse:
        return out, None
    lse = jax.scipy.special.logsumexp(scores, axis=-1)      # [B, n, T]
    return out, lse.reshape(B * n, 1, T)


def _mask_gtd(attn_mask, B, T, n):
    return jnp.broadcast_to(
        attn_mask.astype(jnp.float32)[:, None, :], (B, n, T)
    ).reshape(B * n, 1, T)


def _check_block(fwd_impl, bwd_impl, q, k, v, window):
    if "block" in (fwd_impl, bwd_impl) and (
            window is not None or k.shape != q.shape or v.shape != q.shape):
        raise ValueError("the whole-tile kernel takes neither a window nor "
                         "shared or wider k/v heads")


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def dispatch_attention(q, k, v, attn_mask, causal: bool = False,
                       fwd_impl: str = "xla", bwd_impl: str = "xla",
                       interpret: bool = False, window=None):
    """Attention with independently chosen forward/backward kernels.

    q/k/v: [B, T, n, d] (k/v heads shared, v wider and ``window`` as in
    ``stream_attention``, on "xla" and "stream"); attn_mask: [B, T] float
    (1 = attend).  The impls are {"xla", "block", "stream"}; bwd "stream"
    after fwd "block" is rejected (no logsumexp).  Callers gate shapes via
    ``supported`` / ``stream_supported`` per impl."""
    _check_impls(fwd_impl, bwd_impl)
    _check_block(fwd_impl, bwd_impl, q, k, v, window)
    B, _, n, _ = q.shape
    if fwd_impl == "stream":
        o, _, _ = _stream_fwd_impl(q, k, v, attn_mask, causal, interpret,
                                   window)
        return _unfold_gtd(o, B, n)
    if fwd_impl == "block":
        return _fwd(q, k, v, attn_mask, causal, interpret)
    return xla_attention(q, k, v, attn_mask, causal, window=window)[0]


def _dispatch_vjp_fwd(q, k, v, attn_mask, causal, fwd_impl, bwd_impl,
                      interpret, window=None):
    _check_impls(fwd_impl, bwd_impl)
    _check_block(fwd_impl, bwd_impl, q, k, v, window)
    B, T, n, d = q.shape
    need_stream_res = bwd_impl == "stream"
    lse = None
    if fwd_impl == "stream":
        o, lse, _ = _stream_fwd_impl(q, k, v, attn_mask, causal, interpret,
                                     window)
        out = _unfold_gtd(o, B, n)
    elif fwd_impl == "block":
        out = _fwd(q, k, v, attn_mask, causal, interpret)
    else:
        out, lse = xla_attention(q, k, v, attn_mask, causal,
                                 with_lse=need_stream_res, window=window)
    if need_stream_res:
        out, lse = _name_stream_residuals(out, lse)
    return out, (q, k, v, attn_mask,
                 (out, lse) if need_stream_res else None)


def _dispatch_vjp_bwd(causal, fwd_impl, bwd_impl, interpret, window, res,
                      g):
    q, k, v, attn_mask, extra = res
    B, T, n, d = q.shape
    if bwd_impl == "stream":
        out, lse = extra
        dq, dk, dv = _stream_bwd_impl(
            _fold_gtd(q), _fold_gtd(k), _fold_gtd(v),
            _mask_gtd(attn_mask, B, T, n), _fold_gtd(out), lse,
            _fold_gtd(g), causal, interpret, window)
        dq, dk, dv = (_unfold_gtd(x, B, like.shape[2])
                      for x, like in ((dq, q), (dk, k), (dv, v)))
    elif bwd_impl == "block":
        dq, dk, dv = _block_bwd_impl(q, k, v, attn_mask, g, causal,
                                     interpret)
    else:
        # XLA backward: recompute-and-differentiate the einsum forward
        # (the same work a remat'd XLA attention does in the replay)
        _, pull = jax.vjp(
            lambda q_, k_, v_: xla_attention(q_, k_, v_, attn_mask, causal,
                                             window=window)[0],
            q, k, v)
        dq, dk, dv = pull(g)
    return dq, dk, dv, jnp.zeros_like(attn_mask)


dispatch_attention.defvjp(_dispatch_vjp_fwd, _dispatch_vjp_bwd)


def calibrate_stream_threshold(seq_lens=(256, 512, 1024, 2048),
                               batch=8, n_heads=12, head_dim=64,
                               steps=6, verbose=True):
    """Measure the streaming-kernel vs XLA crossover on the ATTACHED chip
    and return the smallest winning sequence length.

    The shipped auto-dispatch threshold encodes the v5e sweep
    (analysis/profiles.py ``stream_attn_min_*``); other chip generations
    shift the crossover.  This times fwd+bwd of both paths at each length and
    returns the first where the kernel is >= 5% faster (falling back to
    the table default when none wins).  Persist the result with::

        export DSTPU_STREAM_ATTN_MIN_CAUSAL=<returned value>

    (causal-scoped: the calibration loss is causal, and a both-axes pin
    would force the kernel on non-causal shapes where XLA wins)

    Host-side utility; requires a TPU backend.
    """
    import time

    import numpy as np

    if jax.default_backend() != "tpu":
        raise RuntimeError(
            "calibrate_stream_threshold needs a TPU backend (the kernel "
            "never dispatches off-TPU)")

    def time_path(T, use_kernel):
        rng = np.random.default_rng(0)
        q, k, v = (jnp.asarray(rng.normal(size=(batch, T, n_heads,
                                                head_dim)),
                               jnp.bfloat16) for _ in range(3))
        mask = jnp.ones((batch, T), jnp.float32)

        def xla_attn(q, k, v):
            s = jnp.einsum("btnd,bsnd->bnts", q, k,
                           preferred_element_type=jnp.float32)
            s = s / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
            cm = jnp.tril(jnp.ones((T, T), jnp.bool_))
            s = jnp.where(cm[None, None], s, -1e9)
            p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
            return jnp.einsum("bnts,bsnd->btnd", p, v)

        def loss(q, k, v):
            o = (stream_attention(q, k, v, mask, True) if use_kernel
                 else xla_attn(q, k, v))
            return jnp.sum(o.astype(jnp.float32) ** 2)

        fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        fn(q, k, v)[0].block_until_ready()           # compile + warmup
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(q, k, v)
        jax.tree_util.tree_leaves(out)[0].block_until_ready()
        return (time.perf_counter() - t0) / steps

    from deepspeed_tpu.models import layers as _L

    threshold = None
    for T in sorted(seq_lens):
        if not stream_supported(T, head_dim):
            continue
        t_xla = time_path(T, use_kernel=False)
        t_ker = time_path(T, use_kernel=True)
        ratio = t_xla / t_ker
        if verbose:
            print(f"seq {T}: xla {t_xla * 1e3:.2f} ms, "
                  f"kernel {t_ker * 1e3:.2f} ms, {ratio:.2f}x")
        if threshold is None and ratio >= 1.05:
            threshold = T
    if threshold is None:
        # deliberately IGNORE any existing env pin here: this measurement
        # just showed the kernel losing, so fall back to the chip's
        # profile row / default (the calibration loss is causal, so read
        # the causal column)
        from deepspeed_tpu.analysis import profiles
        pair = profiles.default_profile().stream_attn_min_causal
        threshold = min(pair) if pair else _L.STREAM_AUTO_MIN_CAUSAL
        if verbose:
            print(f"kernel never won >=1.05x; keeping {threshold}")
    elif verbose:
        print(f"crossover at seq {threshold}: "
              f"export DSTPU_STREAM_ATTN_MIN_CAUSAL={threshold}")
    return threshold
