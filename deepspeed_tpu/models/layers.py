"""Tensor-parallel layer primitives (Megatron-style) for the ('data','model')
mesh.

The reference consumes an external Megatron-LM for tensor parallelism through
the ``mpu`` protocol (/root/reference/docs/_pages/features.md §"Support for
Custom Model Parallelism"; engine hooks at
/root/reference/deepspeed/pt/deepspeed_light.py:420-430).  On TPU we own the
model layer, so the Megatron column/row-parallel linears, vocab-parallel
embedding and vocab-parallel cross-entropy are provided here as pure functions
meant to run INSIDE ``shard_map``: every function sees *local* shards of its
weights and issues explicit collectives (``psum``/``pmax``) over the ``model``
mesh axis.  With ``model`` axis size 1 every collective degenerates to a
no-op, so the same model code serves mp=1 and mp>1.

Conventions:
* column-parallel weight  [in, out/mp]  — output stays sharded, no collective
  in forward (Megatron's "f" operator: JAX autodiff inserts the backward
  all-reduce for the replicated input automatically through shard_map).
* row-parallel weight     [in/mp, out]  — forward ends with a psum over
  ``model`` (Megatron's "g" operator); bias is replicated and added after.
* QKV packing is head-major ``(n_heads, 3, head_dim)`` flattened on the output
  dim, so an even split over ``model`` hands each shard whole heads with their
  q, k and v together.
"""

from __future__ import annotations

import contextlib
import os

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from deepspeed_tpu.observability import scopes as S
from deepspeed_tpu.ops.remat_names import MIXER_IN, QKV
from deepspeed_tpu.parallel.topology import MODEL_AXIS, SEQ_AXIS

# Pallas attention dispatch (DSTPU_FUSED_ATTN = "auto" | "1" | "0").
# Measured on a v5e chip, END-TO-END training step (12-layer model, under
# a selective remat that replayed the kernel's forward in the backward
# pass — today's keeps its output, PERF.md PR 27, so these ratios are
# upper bounds; bench_attn_sweep.json r4/r5):
#   GPT-2 causal:   kernel 1.127x @128 (whole-tile — streaming needs a
#                   256 tile), 1.18x @512, 1.87x @1024, 2.44x @2048,
#                   3.21x @4096
#   BERT-large 128: whole-tile kernel 0.92x (375.6 vs 409.2 samples/s,
#                   non-causal, 16 heads) -> XLA below the threshold
# "auto" (default) picks per DIRECTION and per KIND: the streaming
# online-softmax kernel from the calibrated threshold up, the whole-tile
# kernel for causal shapes from BLOCK_AUTO_MIN_CAUSAL (the seq-128 causal
# sweep row the old threshold left on the table — VERDICT r5 weak #3),
# XLA otherwise; "1" forces a kernel wherever one supports the shape; "0"
# disables both.  Causal thresholds are lower: both kernels skip (or never
# compute) fully-masked KV tiles, which the XLA einsum path cannot.
# Forward and backward resolve INDEPENDENTLY (ops/pallas_attention.py
# dispatch_attention): the backward runs ~2.5x the forward's matmul passes
# per tile pair, so its kernel crossover sits lower on DMA-bound shapes.
#
# The crossover is chip-generation dependent.  Resolution order per
# (kind, direction):
#   1. DSTPU_STREAM_ATTN_MIN_CAUSAL_FWD / _BWD (most specific)
#   2. DSTPU_STREAM_ATTN_MIN_CAUSAL (causal, both directions — what
#      calibrate() prints, since it measures the causal crossover)
#   3. DSTPU_STREAM_ATTN_MIN_FWD / _BWD (both kinds, one direction)
#   4. DSTPU_STREAM_ATTN_MIN (applies everywhere; a causal-measured value
#      here would force the kernel on non-causal shapes where XLA wins —
#      prefer the causal-scoped pin)
#   5. the attached chip's row in analysis/profiles.py
#      (BackendProfile.stream_attn_min_*; extend as sweeps run on new
#      generations: BENCH_ATTN_SWEEP=1 BENCH_SEQ=<n> python bench.py)
#   6. the defaults below, for a chip whose row carries no sweep
# `ops.pallas_attention.calibrate_stream_threshold()` measures the
# crossover on the attached chip and prints the env pin to persist.
STREAM_AUTO_MIN = 1024            # non-causal default (conservative)
STREAM_AUTO_MIN_CAUSAL = 512      # causal default (v5e end-to-end sweep)

#: whole-tile kernel auto-dispatch BELOW the streaming threshold, causal
#: only: the committed causal seq-128 sweep row (bench_attn_sweep.json,
#: 1.127x end-to-end — under force mode seq 128 selects the whole-tile
#: kernel since streaming needs a 256-token tile) was previously
#: unreachable in auto mode.  Non-causal short sequences keep XLA (0.92x
#: measured, BERT-large 128).  Env pin: DSTPU_BLOCK_ATTN_MIN_CAUSAL
#: (0 disables the whole-tile auto path).
BLOCK_AUTO_MIN_CAUSAL = 128


def _env_int(name):
    env = os.environ.get(name)
    if not env:
        return None
    try:
        v = int(env)
    except ValueError:
        raise ValueError(
            f"{name}={env!r} is not an integer token count") from None
    if v < 0:
        raise ValueError(f"{name}={env!r} must be a non-negative count")
    return v


def stream_auto_min(causal: bool = False, direction: str = "fwd") -> int:
    """The streaming auto-dispatch threshold for the CURRENT backend and
    the given pass direction ("fwd" | "bwd"); see the resolution order
    above."""
    if direction not in ("fwd", "bwd"):
        raise ValueError(f"direction must be 'fwd' or 'bwd', "
                         f"got {direction!r}")
    suff = direction.upper()
    names = ((f"DSTPU_STREAM_ATTN_MIN_CAUSAL_{suff}",
              "DSTPU_STREAM_ATTN_MIN_CAUSAL",
              f"DSTPU_STREAM_ATTN_MIN_{suff}",
              "DSTPU_STREAM_ATTN_MIN") if causal else
             (f"DSTPU_STREAM_ATTN_MIN_{suff}", "DSTPU_STREAM_ATTN_MIN"))
    for name in names:
        v = _env_int(name)
        if v is None:
            continue
        if v == 0:
            raise ValueError(
                f"{name}=0 is not a valid token count (use "
                f"DSTPU_FUSED_ATTN=0 to disable kernels)")
        return v
    from deepspeed_tpu.analysis import profiles
    prof = profiles.default_profile()     # an unknown TPU kind raises
    pair = None if prof is None else (
        prof.stream_attn_min_causal if causal
        else prof.stream_attn_min_noncausal)
    if pair is None:
        return STREAM_AUTO_MIN_CAUSAL if causal else STREAM_AUTO_MIN
    return pair[0] if direction == "fwd" else pair[1]


def block_auto_min_causal():
    """Whole-tile kernel auto threshold for causal shapes; None disables
    (env pin 0)."""
    v = _env_int("DSTPU_BLOCK_ATTN_MIN_CAUSAL")
    if v is None:
        v = BLOCK_AUTO_MIN_CAUSAL
    return None if v == 0 else v


def _attn_mode() -> str:
    mode = os.environ.get("DSTPU_FUSED_ATTN", "auto")
    if mode not in ("auto", "1", "0"):
        # fail loudly, not open: "off"/"false"/"" must not silently enable
        # the kernel the operator meant to disable
        raise ValueError(
            f"DSTPU_FUSED_ATTN={mode!r} is not a valid mode: use 'auto' "
            f"(streaming kernel from the calibrated threshold, "
            f"DSTPU_STREAM_ATTN_MIN), '1' (force a kernel), or '0' "
            f"(XLA only)")
    return mode


def axis_size_or_1(axis) -> int:
    """Static size of a mesh axis, or 1 when the axis isn't bound (allows
    the same layer code under 2-axis test meshes and the full
    ('data','seq','model') mesh)."""
    try:
        return jax.lax.axis_size(axis)
    except (NameError, KeyError, ValueError):
        return 1


def column_parallel_linear(x, w_local, b_local=None):
    """x: [..., in] replicated over model axis; w_local: [in, out/mp].
    Returns [..., out/mp] (sharded on the feature dim).  ``w_local`` may
    be an int8-quantized subtree (serving — see ``matmul_dequant``)."""
    if is_quantized(w_local):
        y = matmul_dequant(x, w_local)
    else:
        y = x @ w_local.astype(x.dtype)
    if b_local is not None:
        y = y + b_local.astype(y.dtype)
    return y


def row_parallel_linear(x_local, w_local, b=None, axis=MODEL_AXIS):
    """x_local: [..., in/mp]; w_local: [in/mp, out].  psum completes the
    contraction over the sharded input dim; result is replicated.
    Quantized weights dequantize per shard BEFORE the psum — per-output-
    channel scales are identical on every model rank, so the reduction
    is unchanged."""
    if is_quantized(w_local):
        y = jax.lax.psum(matmul_dequant(x_local, w_local), axis)
    else:
        y = jax.lax.psum(x_local @ w_local.astype(x_local.dtype), axis)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def vocab_parallel_embedding(tokens, wte_local, axis=MODEL_AXIS):
    """tokens: int [...]; wte_local: [vocab/mp, h] (vocab dim sharded).

    Masked local lookup + psum (Megatron VocabParallelEmbedding): each shard
    contributes rows it owns, zeros elsewhere.
    """
    if is_quantized(wte_local):
        # int8 rows dequantize AFTER the lookup (per-ROW scales: the
        # embedding's output channel is the vocab row)
        q, s = wte_local["q"], wte_local["s"]
        vocab_local = q.shape[0]
        start = jax.lax.axis_index(axis) * vocab_local
        idx = tokens - start
        valid = (idx >= 0) & (idx < vocab_local)
        idx = jnp.clip(idx, 0, vocab_local - 1)
        emb = (jnp.take(q, idx, axis=0).astype(s.dtype)
               * jnp.take(s.reshape(-1), idx)[..., None])
        emb = emb * valid[..., None].astype(emb.dtype)
        return jax.lax.psum(emb, axis)
    vocab_local = wte_local.shape[0]
    start = jax.lax.axis_index(axis) * vocab_local
    idx = tokens - start
    valid = (idx >= 0) & (idx < vocab_local)
    idx = jnp.clip(idx, 0, vocab_local - 1)
    emb = jnp.take(wte_local, idx, axis=0)
    emb = emb * valid[..., None].astype(emb.dtype)
    return jax.lax.psum(emb, axis)


def vocab_parallel_logits(h, wte_local):
    """Weight-tied LM head: h [..., hid] replicated; wte_local [vocab/mp, hid]
    → logits [..., vocab/mp] sharded on the vocab dim (feeds directly into
    ``vocab_parallel_cross_entropy`` with no gather).  An int8-quantized
    ``wte`` follows the matmul-dequant dispatch (per-row scales are the
    logits' per-output-channel scales)."""
    if is_quantized(wte_local):
        if quant_matmul_plan() == "dequant":
            return h @ dequantize(wte_local).astype(h.dtype).T
        y = h @ wte_local["q"].astype(h.dtype).T
        return y * wte_local["s"].reshape(-1).astype(y.dtype)
    return h @ wte_local.astype(h.dtype).T


def vocab_parallel_cross_entropy(logits_local, labels, axis=MODEL_AXIS):
    """Per-token CE over vocab-sharded logits (Megatron's vocab-parallel
    softmax-CE: pmax for the max, psum for the partition function and the
    target logit — never materialises the full-vocab softmax on one shard).

    logits_local: [..., vocab/mp] (any float dtype; math in fp32)
    labels:       int [...]
    returns       fp32 [...] per-token loss
    """
    logits_local = logits_local.astype(jnp.float32)
    vocab_local = logits_local.shape[-1]
    start = jax.lax.axis_index(axis) * vocab_local

    # the max shift is numerical stabilisation only — stop-grad before the
    # pmax (which has no differentiation rule); CE grads flow via shifted/tgt
    lmax = jax.lax.pmax(
        jnp.max(jax.lax.stop_gradient(logits_local), axis=-1), axis)
    shifted = logits_local - lmax[..., None]
    sumexp = jax.lax.psum(jnp.sum(jnp.exp(shifted), axis=-1), axis)

    idx = labels - start
    valid = (idx >= 0) & (idx < vocab_local)
    idxc = jnp.clip(idx, 0, vocab_local - 1)
    tgt_local = jnp.take_along_axis(shifted, idxc[..., None], axis=-1)[..., 0]
    tgt = jax.lax.psum(tgt_local * valid.astype(jnp.float32), axis)

    return jnp.log(sumexp) - tgt


def seq_shard_positions(wpe, t_local):
    """Position embeddings for THIS sequence shard: global offset
    ``seq_index * t_local`` under context parallelism, 0 otherwise."""
    pos0 = (jax.lax.axis_index(SEQ_AXIS) * t_local
            if axis_size_or_1(SEQ_AXIS) > 1 else 0)
    return jax.lax.dynamic_slice_in_dim(wpe, pos0, t_local)


def _gather_mode() -> str:
    mode = os.environ.get("DSTPU_MLM_GATHER", "auto")
    if mode not in ("auto", "onehot", "take"):
        raise ValueError(
            f"DSTPU_MLM_GATHER={mode!r} is not a valid mode: use 'auto' "
            f"(one-hot matmul on TPU, take_along_axis elsewhere), "
            f"'onehot', or 'take'")
    return mode


def gather_positions(x, positions):
    """Gather per-sequence positions: x [B, T, H], positions int [B, P] →
    [B, P, H] (the masked-LM head's input selection).

    On TPU the gather is expressed as a one-hot MATMUL: ``take_along_axis``
    lowers to an HBM gather whose VJP is a serialized scatter-add over the
    [B, T, H] activations — the dominant cost of the maxpred-80 head at
    seq 512 (bench_mfu_breakdown.json).  The one-hot form keeps both
    directions on the MXU (B·P·T·H MACs, ~0.5 ms at the phase-2 shape
    against tens of ms of scatter).  Off-TPU the plain gather wins; env
    DSTPU_MLM_GATHER pins either."""
    mode = _gather_mode()
    if mode == "onehot" or (mode == "auto"
                            and jax.default_backend() == "tpu"):
        T = x.shape[1]
        onehot = jax.nn.one_hot(positions.astype(jnp.int32), T,
                                dtype=x.dtype)              # [B, P, T]
        return jax.lax.dot_general(
            onehot, x, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=x.dtype)
    return jnp.take_along_axis(
        x, positions[..., None].astype(jnp.int32), axis=1)


def masked_mean_loss(loss, mask):
    """Global masked mean of a per-token loss under sequence sharding.

    Returns a value whose pmean over the seq axis equals the TRUE global
    masked mean (sum of masked losses / total valid count), and whose
    psum-of-grads/sp under the engine's aggregation yields the true global
    gradient — valid-token counts may differ per shard (trailing padding,
    sparse MLM labels).  With sp == 1 this is the plain masked mean.
    """
    mask = mask.astype(jnp.float32)
    local_sum = jnp.sum(loss * mask)
    local_cnt = jnp.sum(mask)
    sp = axis_size_or_1(SEQ_AXIS)
    if sp > 1:
        total_cnt = jax.lax.psum(local_cnt, SEQ_AXIS)
        return local_sum * sp / jnp.maximum(total_cnt, 1.0)
    return local_sum / jnp.maximum(local_cnt, 1.0)


@S.scoped("norm")
def layer_norm(x, scale, bias, eps=1e-5):
    """LayerNorm in fp32 (bf16/fp16 inputs upcast for the moments)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def gelu(x):
    """tanh-approx GELU (matches GPT-2/BERT)."""
    xf = x.astype(jnp.float32)
    y = 0.5 * xf * (1.0 + jnp.tanh(
        0.7978845608028654 * (xf + 0.044715 * xf ** 3)))
    return y.astype(x.dtype)


def _rms_norm(x, scale, eps, zero_centred=False):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                           + eps)
    scale = scale.astype(jnp.float32)
    return (y * (1.0 + scale if zero_centred else scale)).astype(x.dtype)


@S.scoped("norm")
def rms_norm(x, scale, eps=1e-6, zero_centred=False):
    """RMSNorm: ``x / sqrt(mean(x^2) + eps) * scale``, the statistic in
    fp32 (no mean subtraction, no offset); ``zero_centred``: times ``1 +
    scale``, a scale that starts at zero."""
    return _rms_norm(x, scale, eps, zero_centred)


def silu(x):
    xf = x.astype(jnp.float32)
    return (xf * jax.nn.sigmoid(xf)).astype(x.dtype)


@S.scoped("rope")
def rotary_tables(t_local, head_dim, theta):
    """``(cos, sin)``, each fp32 ``[t_local, head_dim]``, of the rotary
    position embedding for THIS sequence shard (global offset
    ``seq_index * t_local`` under context parallelism, like
    ``seq_shard_positions``): frequency ``theta^(-2i/d)`` for the pair
    ``(i, i + d/2)`` — the "rotate-half" pairing."""
    pos0 = (jax.lax.axis_index(SEQ_AXIS) * t_local
            if axis_size_or_1(SEQ_AXIS) > 1 else 0)
    pos = (pos0 + jnp.arange(t_local)).astype(jnp.float32)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                                / head_dim))
    angles = pos[:, None] * inv_freq[None, :]               # [T, d/2]
    angles = jnp.concatenate([angles, angles], axis=-1)     # [T, d]
    return jnp.cos(angles), jnp.sin(angles)


@S.scoped("rope")
def apply_rotary(x, rope):
    """Rotate ``x`` [B, T, n, d] by its position: ``x cos + rotate_half(x)
    sin`` with ``rotate_half(x) = (-x[d/2:], x[:d/2])``; the arithmetic in
    fp32, the result in ``x``'s dtype."""
    cos, sin = rope
    xf = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-xf[..., half:], xf[..., :half]], axis=-1)
    y = xf * cos[None, :, None, :] + rotated * sin[None, :, None, :]
    return y.astype(x.dtype)


# --------------------------------------------------------------- serving
# int8 weight-only quantization (deepspeed_tpu/inference/): weights are
# stored as {"q": int8, "s": per-output-channel scale} subtrees, and the
# matmul-dequant strategy rides a per-backend dispatch table like the
# attention kernels above (docs/inference.md "Quantization").  Two impls:
#   "dequant" — materialise W = q*s in the compute dtype, then matmul
#               (the exactness anchor: one rounding per weight element)
#   "scaled"  — contract x @ q first, scale the [..., out] activation
#               (the serving default: per-output-channel scales commute
#               with the contraction, so this is the same math with the
#               scale applied once per OUTPUT element — it never
#               materialises the dequantized [in, out] weight, which is
#               the entire memory win of int8 at decode batch sizes)
# The two differ by float rounding only; the contract is pinned in
# tests/test_inference.py and documented in docs/inference.md.
QUANT_MATMUL_IMPLS = ("auto", "dequant", "scaled")


def quant_matmul_plan() -> str:
    """Resolved matmul-dequant impl ("dequant" | "scaled") for the current
    mode: env ``DSTPU_QUANT_MATMUL`` pins one; "auto" (default) picks
    "scaled" — at serving shapes the activation side is orders of
    magnitude smaller than the weight it would otherwise dequantize."""
    mode = os.environ.get("DSTPU_QUANT_MATMUL", "auto")
    if mode not in QUANT_MATMUL_IMPLS:
        raise ValueError(
            f"DSTPU_QUANT_MATMUL={mode!r} is not a valid impl: use 'auto', "
            f"'dequant' or 'scaled'")
    return "scaled" if mode == "auto" else mode


def is_quantized(w) -> bool:
    """True for an int8-quantized weight subtree ({"q", "s"})."""
    return isinstance(w, dict) and set(w) == {"q", "s"}


def dequantize(wq):
    """Materialise the full-precision weight of a quantized subtree: the
    scale's dtype IS the serving compute dtype (inference/quant.py)."""
    return wq["q"].astype(wq["s"].dtype) * wq["s"]


def matmul_dequant(x, wq):
    """``x @ W`` for an int8 per-OUTPUT-channel quantized ``W`` (scale
    keepdims-shaped ``[1, out]``), per the dispatch plan."""
    if quant_matmul_plan() == "dequant":
        return x @ dequantize(wq).astype(x.dtype)
    y = x @ wq["q"].astype(x.dtype)
    return y * wq["s"].reshape(-1).astype(y.dtype)


def gather_kv_rows(pool, rows):
    """Per-slot view of the flat KV page pool: ``pool`` [R, n, d],
    ``rows`` int32 [B, cap] (the host-resolved page-table row map) →
    [B, cap, n, d].  Shared pages appear in several slots' views at
    zero copy cost — the gather is the read attention does anyway."""
    return jnp.take(pool, rows, axis=0, mode="clip")


def scatter_kv_rows(pool, new, rows):
    """Write ``new`` token rows into the flat pool: ``pool`` [R, n, d],
    ``new`` [..., n, d] with ``rows`` int32 matching its leading dims.
    Rows ``>= R`` are DROPPED — the masked-write convention (padding /
    inactive slots aim at the out-of-range drop row).  In-bounds rows
    are exclusively owned by their writer (the page table's refcount
    discipline), so duplicates only ever occur among dropped writes."""
    n, d = new.shape[-2], new.shape[-1]
    flat = new.reshape(-1, n, d).astype(pool.dtype)
    return pool.at[rows.reshape(-1)].set(flat, mode="drop")


def cached_attention(q, k_cache, v_cache, pos, ring: bool = False):
    """Single-query attention against a per-slot KV cache.

    q: [B, n, d] (this step's query, already written to the cache at its
    own index); caches: [B, cap, n, d]; pos: int32 [B] — the query's own
    position, so cache entries ``<= pos`` attend.  ``ring=True`` admits
    every entry once a slot has wrapped (the sliding-window layout).
    Numerics mirror ``ops.pallas_attention.xla_attention`` (fp32 MXU
    accumulation for the scores and softmax, probabilities cast to the
    compute dtype before the value contraction) so incremental decode
    stays within dtype tolerance of a full-context re-forward."""
    d = q.shape[-1]
    scores = jnp.einsum("bnd,btnd->bnt", q, k_cache,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(d, jnp.float32))
    cap = k_cache.shape[1]
    valid = jnp.arange(cap, dtype=jnp.int32)[None, :] <= pos[:, None]
    if ring:
        valid = valid | (pos[:, None] >= cap)
    scores = jnp.where(valid[:, None, :], scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bnt,btnd->bnd", probs, v_cache.astype(q.dtype))


def extend_attention(q, k_view, v_view, start):
    """Multi-query attention of a block of NEW tokens against a per-slot
    KV view that already contains their rows.

    q: [B, E, n, d] (queries for E new tokens, slot b's first at
    absolute position ``start[b]``); views: [B, cap, n, d] (gathered
    AFTER this block's K/V rows were scattered in).  Query e attends
    rows ``t <= start + e`` — earlier new tokens included, later ones
    masked out, exactly causal.  Numerics mirror :func:`cached_attention`
    (fp32 score accumulation and softmax, probs cast to compute dtype)
    so a tail prefill over reused pages stays within dtype tolerance of
    the full-prompt forward.  The caller guarantees no ring wrap inside
    the block (``start + E <= cap`` — admission starts slots fresh and
    the schedulers bound prompt length by the bucket)."""
    d = q.shape[-1]
    scores = jnp.einsum("bend,btnd->bent", q, k_view,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(d, jnp.float32))
    cap = k_view.shape[1]
    e_pos = (start[:, None]
             + jnp.arange(q.shape[1], dtype=jnp.int32)[None, :])  # [B, E]
    valid = (jnp.arange(cap, dtype=jnp.int32)[None, None, :]
             <= e_pos[:, :, None])                               # [B, E, t]
    scores = jnp.where(valid[:, :, None, :], scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bent,btnd->bend", probs, v_view.astype(q.dtype))


def decode_multihead_attention(x, qkv_w_local, qkv_b_local, proj_w_local,
                               proj_b, k_pool, v_pool, pos, rows,
                               write_rows, *, n_heads_global,
                               ring: bool = False, axis=MODEL_AXIS):
    """One-token attention step against the KV page pool.

    x: [B, 1, h]; pools: [R, n_local, d] flat rows; pos: int32 [B]
    (absolute position the new token occupies); rows: int32 [B, cap]
    (the slot's page-table row map); write_rows: int32 [B] (this
    step's flat target row, ``>= R`` = masked write).  Scatters this
    step's K/V, gathers the per-slot view, attends, and returns
    ``(out [B, 1, h], k_pool', v_pool')``."""
    B, _, h = x.shape
    d = h // n_heads_global
    qkv = column_parallel_linear(x, qkv_w_local, qkv_b_local)  # [B,1,3h/mp]
    n_local = qkv.shape[-1] // (3 * d)
    qkv = qkv.reshape(B, n_local, 3, d)
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    k_pool = scatter_kv_rows(k_pool, k[:, None], write_rows[:, None])
    v_pool = scatter_kv_rows(v_pool, v[:, None], write_rows[:, None])
    k_view = gather_kv_rows(k_pool, rows)
    v_view = gather_kv_rows(v_pool, rows)
    ctx = cached_attention(q, k_view, v_view, pos, ring=ring)
    ctx = ctx.reshape(B, 1, n_local * d)
    out = row_parallel_linear(ctx, proj_w_local, proj_b, axis=axis)
    return out, k_pool, v_pool


def extend_multihead_attention(x, qkv_w_local, qkv_b_local, proj_w_local,
                               proj_b, k_pool, v_pool, rows, start, n_new,
                               *, n_heads_global, axis=MODEL_AXIS):
    """Attention for a BLOCK of new tokens against the KV page pool —
    the prefill / tail-prefill / speculative-verify path (one program
    shape serves all three, docs/inference.md).

    x: [B, E, h] (E new tokens per slot, left-aligned, ``n_new[b]``
    real); pools: [R, n_local, d]; rows: int32 [B, cap]; start: int32
    [B] (absolute position of each slot's first new token).  Pad
    positions and positions past the slot's range write to the drop row;
    their outputs are garbage the caller masks.  Sequence parallelism is
    not a serving layout, so the seq axis must be unsharded here."""
    if axis_size_or_1(SEQ_AXIS) > 1:
        raise ValueError(
            "extend_multihead_attention: KV-cached serving does not "
            "compose with context parallelism (shard requests over "
            "engine replicas instead)")
    B, E, h = x.shape
    d = h // n_heads_global
    qkv = column_parallel_linear(x, qkv_w_local, qkv_b_local)
    n_local = qkv.shape[-1] // (3 * d)
    qkv = qkv.reshape(B, E, n_local, 3, d)
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    cap = rows.shape[1]
    R = k_pool.shape[0]
    idx = (start[:, None]
           + jnp.arange(E, dtype=jnp.int32)[None, :])            # [B, E]
    wrows = jnp.take_along_axis(rows, jnp.clip(idx, 0, cap - 1), axis=1)
    real = ((jnp.arange(E, dtype=jnp.int32)[None, :] < n_new[:, None])
            & (idx < cap))
    wrows = jnp.where(real, wrows, R)            # pad/overflow → drop row
    k_pool = scatter_kv_rows(k_pool, k, wrows)
    v_pool = scatter_kv_rows(v_pool, v, wrows)
    k_view = gather_kv_rows(k_pool, rows)
    v_view = gather_kv_rows(v_pool, rows)
    ctx = extend_attention(q, k_view, v_view, start)
    ctx = ctx.reshape(B, E, n_local * d)
    return row_parallel_linear(ctx, proj_w_local, proj_b, axis=axis), \
        k_pool, v_pool


def attention_plan(T, n, d, causal, window=None, kv_heads=None):
    """(fwd_impl, bwd_impl), each in {"xla", "block", "stream"}, for the
    current backend/mode — the per-direction dispatch table.  Forward and
    backward resolve independently under "auto" (their crossovers differ);
    "1" forces one kernel for both, "0" / non-TPU yields ("xla", "xla").
    A sliding ``window`` or fewer k/v heads than the ``n`` query heads
    (``kv_heads``, the smaller count of the two) rule the whole-tile kernel
    out: the streaming kernel and the XLA path take them."""
    mode = _attn_mode()
    if mode == "0" or jax.default_backend() != "tpu":
        return "xla", "xla"
    from deepspeed_tpu.ops import pallas_attention as pattn
    stream_ok = pattn.stream_supported(T, d)
    block_ok = (pattn.supported(T, n, d) and window is None
                and kv_heads in (None, n))
    if mode == "1":
        impl = "stream" if stream_ok else ("block" if block_ok else "xla")
        return impl, impl

    def pick(direction):
        if stream_ok and T >= stream_auto_min(causal, direction):
            return "stream"
        bmin = block_auto_min_causal()
        if block_ok and causal and bmin is not None and T >= bmin:
            return "block"
        return "xla"

    fwd, bwd = pick("fwd"), pick("bwd")
    if bwd == "stream" and fwd == "block":
        # a streaming backward needs the forward's logsumexp, which the
        # whole-tile kernel doesn't emit
        bwd = "block"
    return fwd, bwd


def core_attention(q, k, v, *, causal, attn_mask=None, window=None):
    """Single-device attention on [B, T, n, d] q/k/v with the per-direction
    kernel dispatch table (``attention_plan``): streaming Pallas kernel from
    the calibrated threshold, whole-tile kernel for short causal shapes (or
    under force mode), XLA einsum otherwise — forward and backward chosen
    independently.  ``attn_mask``: optional [B, T] float/int, 1 = attend.
    ``k``/``v`` may hold fewer heads than ``q`` (consecutive query heads
    share one) and ``v`` a wider head; ``window``: causal sliding window,
    key s visible to query t iff ``t - window < s <= t``
    (``ops.pallas_attention.stream_attention``).
    Shared by the plain path and Ulysses sequence parallelism (which
    calls it on the all-to-all'd full-sequence view — so long-context
    kernels and sequence sharding compose)."""
    B, T, n, d = q.shape
    fwd_impl, bwd_impl = attention_plan(
        T, n, d, causal, window=window,
        kv_heads=min(k.shape[2], v.shape[2]))
    from deepspeed_tpu.ops import pallas_attention as pattn
    mvec = (jnp.ones((B, T), jnp.float32) if attn_mask is None
            else attn_mask.astype(jnp.float32))
    if fwd_impl == bwd_impl == "stream":
        return pattn.stream_attention(q, k, v, mvec, causal, window=window)
    if fwd_impl == bwd_impl == "block":
        return pattn.fused_attention(q, k, v, mvec, causal)
    if (fwd_impl, bwd_impl) == ("xla", "xla"):
        # single source of the reference einsum math (fp32 MXU
        # accumulation, masked softmax) — also the hybrid paths' "xla"
        # side, so the threshold branches can never drift numerically
        return pattn.xla_attention(q, k, v, mvec, causal, window=window)[0]
    return pattn.dispatch_attention(q, k, v, mvec, causal,
                                    fwd_impl, bwd_impl, window=window)


@S.scoped("attn")
def multihead_attention(x, qkv_w_local, qkv_b_local, proj_w_local, proj_b,
                        *, n_heads_global, causal, attn_mask=None,
                        axis=MODEL_AXIS, sp_impl="ring"):
    """Tensor-parallel multi-head attention over local heads.

    x:            [B, T, h] replicated over ``model``
    qkv_w_local:  [h, 3h/mp]  packed head-major (n_local, 3, d)
    qkv_b_local:  [3h/mp]
    proj_w_local: [h/mp, h]   row-parallel output projection
    proj_b:       [h]         replicated
    attn_mask:    optional [B, T] with 1=attend, 0=pad (BERT)
    sp_impl:      sequence-parallel strategy when the ``seq`` axis is
                  sharded: "ring" (K/V rotation, nearest-neighbour ICI
                  only) or "ulysses" (head<->sequence all-to-all; each
                  shard sees the FULL sequence for n/sp heads, so the
                  streaming kernel dispatch applies — models/ulysses.py)
    """
    B, T, h = x.shape
    d = h // n_heads_global
    qkv = column_parallel_linear(x, qkv_w_local, qkv_b_local)  # [B,T,3h/mp]
    # named for the "selective" remat policy (ops/remat_names.py): backward
    # re-derives q, k, v from the saved qkv by a slice and a layout copy,
    # no qkv matmul.  The streaming kernel's output and log-sum-exp carry
    # names of their own, so its forward is not replayed either; the XLA
    # path's einsums and softmax are.
    qkv = checkpoint_name(qkv, QKV)
    n_local = qkv.shape[-1] // (3 * d)
    qkv = qkv.reshape(B, T, n_local, 3, d)

    if axis_size_or_1(SEQ_AXIS) > 1 and sp_impl == "ulysses":
        # packed entry point: one all-to-all moves q, k and v together
        from deepspeed_tpu.models.ulysses import ulysses_attention_packed
        ctx = ulysses_attention_packed(qkv, causal=causal,
                                       attn_mask=attn_mask)
        ctx = ctx.reshape(B, T, n_local * d)
        return row_parallel_linear(ctx, proj_w_local, proj_b, axis=axis)

    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]   # [B,T,n,d]

    if axis_size_or_1(SEQ_AXIS) > 1:
        if sp_impl == "ring":
            # sequence-sharded: exact blockwise attention over the ring
            from deepspeed_tpu.models.ring_attention import ring_attention
            ctx = ring_attention(q, k, v, causal=causal, kv_mask=attn_mask)
        else:
            raise ValueError(
                f"unknown sequence_parallel_impl {sp_impl!r} "
                "(expected 'ring' or 'ulysses')")
    else:
        ctx = core_attention(q, k, v, causal=causal, attn_mask=attn_mask)
    ctx = ctx.reshape(B, T, n_local * d)                        # [B,T,h/mp]
    return row_parallel_linear(ctx, proj_w_local, proj_b, axis=axis)


@S.scoped("attn")
def rotary_multihead_attention(x, wq_local, wk_local, wv_local, wo_local,
                               rope, *, head_dim, causal, attn_mask=None,
                               axis=MODEL_AXIS):
    """Tensor-parallel multi-head attention with separate, bias-free
    projections and rotary positions on q and k.

    x:        [B, T, h] replicated over ``model``
    wq/wk/wv: [h, n*d/mp]  column-parallel, heads contiguous (a shard holds
              whole heads)
    wo_local: [n*d/mp, h]  row-parallel output projection
    rope:     ``rotary_tables(T, head_dim, theta)`` of this sequence shard

    Same ``core_attention`` dispatch as ``multihead_attention``.  Under
    context parallelism the rotated k/v go round the ring (positions are
    already in them); Ulysses is not wired for this block."""
    B, T, _ = x.shape
    q, k, v = (checkpoint_name(column_parallel_linear(x, w), QKV)
               .reshape(B, T, -1, head_dim)
               for w in (wq_local, wk_local, wv_local))
    q, k = apply_rotary(q, rope), apply_rotary(k, rope)
    if axis_size_or_1(SEQ_AXIS) > 1:
        from deepspeed_tpu.models.ring_attention import ring_attention
        ctx = ring_attention(q, k, v, causal=causal, kv_mask=attn_mask)
    else:
        ctx = core_attention(q, k, v, causal=causal, attn_mask=attn_mask)
    return row_parallel_linear(ctx.reshape(B, T, -1), wo_local, axis=axis)


# ------------------------------------------------------ latent attention

@S.scoped("attn")
def latent_attention(x, p, *, rope, nope_dim, rope_dim, v_dim, latent, eps):
    """Multi-head latent attention (MLA, DeepSeek-V2 / V3; queries not
    compressed), causal, in the EXPANDED form training runs: keys and values
    are rebuilt per head from one latent per token (the absorbed form, which
    attends in the latent space, is serving's).

    ``q = x W_q`` -> heads of ``nope_dim + rope_dim`` = ``[q_nope | q_pe]``;
    ``[c | k_pe] = x W_kv_a``, ``c`` ``latent`` wide, ``k_pe`` ``rope_dim``
    wide and ONE per token, shared by all heads; ``c <- RMSNorm(c)``;
    ``[k_nope | v] = c W_kv_b`` -> heads of ``nope_dim + v_dim``; rotary on
    ``q_pe`` and ``k_pe``; ``k_h = [k_nope_h | k_pe]``; softmax of ``q_h
    k_h^T / sqrt(nope_dim + rope_dim)`` times ``v_h``; heads concatenated
    times ``W_o``.  The core is the one ``core_attention`` dispatch, at a
    query / key head of ``nope_dim + rope_dim`` and a value head of
    ``v_dim``.

    x [B, T, h] replicated over ``model``; ``p``: ``q_w`` [h, n (nope +
    rope) / mp] and ``kv_b_w`` [latent, n (nope + v) / mp] column-parallel,
    heads contiguous (a shard holds whole heads), ``kv_a_w`` [h, latent +
    rope] and ``kv_norm_s`` [latent] replicated (every shard needs the whole
    latent), ``o_w`` [n v / mp, h] row-parallel; ``rope`` =
    ``rotary_tables(T, rope_dim, theta)``.  The three projections and the
    latent's norm run under ``dstpu/mla``."""
    if axis_size_or_1(SEQ_AXIS) > 1:
        raise ValueError(
            "latent_attention is not built for context parallelism: the "
            "ring and the all-to-all paths take one head size for keys and "
            "values, and this core has a key head wider than its value head")
    B, T, _ = x.shape
    with S.scope("mla"):
        # named for the "selective" policy, like q, k, v of the other blocks
        q = checkpoint_name(column_parallel_linear(x, p["q_w"]), QKV)
        down = checkpoint_name(column_parallel_linear(x, p["kv_a_w"]), QKV)
        c = _rms_norm(down[..., :latent], p["kv_norm_s"], eps)
        kv = checkpoint_name(column_parallel_linear(c, p["kv_b_w"]), QKV)
    q = q.reshape(B, T, -1, nope_dim + rope_dim)
    kv = kv.reshape(B, T, -1, nope_dim + v_dim)
    q_pe = apply_rotary(q[..., nope_dim:], rope)
    k_pe = apply_rotary(down[:, :, None, latent:], rope)
    q = jnp.concatenate([q[..., :nope_dim], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope_dim],
         jnp.broadcast_to(k_pe, (B, T, kv.shape[2], rope_dim))], axis=-1)
    ctx = core_attention(q, k, kv[..., nope_dim:], causal=True)
    return row_parallel_linear(ctx.reshape(B, T, -1), p["o_w"])


# ------------------------------------- gated attention / gated delta rule
# The two mixers of a linear-attention / attention hybrid
# (models/delta_moe.py): softmax attention with per-head norms on q and k, a
# partly rotated head and a sigmoid gate on its context, and the Gated
# DeltaNet mixer around ``ops/delta_rule.py``.

@S.scoped("attn")
def gated_attention(x, p, *, rope, head_dim, eps):
    """Causal softmax attention with an OUTPUT GATE: ``[q | gate] = x W_q``
    per head (``2 head_dim`` columns a head), ``k = x W_k``, ``v = x W_v`` on
    fewer heads (consecutive query heads share one); zero-centred RMSNorm
    over every query and key head (``q_norm_s`` / ``k_norm_s`` [head_dim],
    shared by the heads); rotary on the FIRST ``rope[0].shape[-1]`` dims of
    each q and k head, the rest pass; softmax of ``q k^T / sqrt(head_dim)``;
    ``(context * sigmoid(gate)) W_o``.  The core is the one
    ``core_attention`` dispatch.

    x [B, T, h] replicated over ``model``; ``q_w`` [h, n 2 d / mp], ``k_w``
    / ``v_w`` [h, n_kv d / mp] column-parallel, heads contiguous, ``o_w``
    [n d / mp, h] row-parallel; ``rope`` = ``rotary_tables(T, rotated dims,
    theta)``."""
    _no_sequence_shards("gated_attention")
    B, T, _ = x.shape
    q, k, v = (checkpoint_name(column_parallel_linear(x, p[w]), QKV)
               for w in ("q_w", "k_w", "v_w"))
    q = q.reshape(B, T, -1, 2 * head_dim)
    q, gate = q[..., :head_dim], q[..., head_dim:]
    k, v = (t.reshape(B, T, -1, head_dim) for t in (k, v))
    q = _rms_norm(q, p["q_norm_s"], eps, zero_centred=True)
    k = _rms_norm(k, p["k_norm_s"], eps, zero_centred=True)
    rotated = rope[0].shape[-1]
    q, k = (jnp.concatenate([apply_rotary(t[..., :rotated], rope),
                             t[..., rotated:]], axis=-1) for t in (q, k))
    ctx = core_attention(q, k, v, causal=True)
    gated = ctx * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(ctx.dtype)
    return row_parallel_linear(gated.reshape(B, T, -1), p["o_w"])


@S.scoped("gdn")
def gated_delta_net(x, p, *, key_dim, value_dim, eps):
    """Gated DeltaNet mixer (arXiv:2412.06464).  ``[q | k | v] = silu(conv(x
    W_qkv))``, a causal depthwise convolution without bias; ``z = x W_z``,
    ``b = x W_b``, ``a = x W_a``; in fp32 ``q <- q / |q| / sqrt(key_dim)``,
    ``k <- k / |k|`` (1e-6 inside the root), ``beta = sigmoid(b)``, ``g =
    -exp(A_log) softplus(a + dt_bias)``; ``o = gated_delta_rule(q, k, v, g,
    beta)``; ``out = (RMSNorm(o) norm_s * silu(z)) W_out``, the norm per
    head over ``value_dim`` with ONE scale [value_dim] for all heads (not
    zero-centred).

    Sharded over the heads: ``in_qkv_w`` [h, Hk (2 dk + r dv) / mp] holds
    key head ``i``'s columns together — ``[q_i | k_i | v_(r i) ... v_(r i + r
    - 1)]``, ``r`` value heads a key head — so a shard has whole key heads
    with their value heads; ``conv_w`` [K, the same columns]; ``in_z_w`` [h,
    Hv dv / mp], ``in_b_w`` / ``in_a_w`` [h, Hv / mp] column-parallel,
    ``A_log`` / ``dt_bias`` [Hv / mp], ``out_w`` [Hv dv / mp, h]
    row-parallel.  The convolution runs under ``dstpu/conv``, the rule (fp32
    state, ops/delta_rule.py) under ``dstpu/delta``."""
    from deepspeed_tpu.ops.delta_rule import gated_delta_rule
    from deepspeed_tpu.ops.selective_scan import causal_conv1d
    _no_sequence_shards("gated_delta_net")
    B, T, _ = x.shape
    f32 = jnp.float32
    # named for the "selective" policy, like an attention layer's q, k, v
    qkv = checkpoint_name(column_parallel_linear(x, p["in_qkv_w"]), MIXER_IN)
    z = checkpoint_name(column_parallel_linear(x, p["in_z_w"]), MIXER_IN)
    heads = p["A_log"].shape[0]                  # value heads of this shard

    # A checkpoint of its own inside the layer's: the backward keeps the
    # projection and makes q, k and v again (the convolution, its
    # activation, the split and the unit lengths), in place of the
    # convolution's output and the float32 pieces of what follows it — 0.75
    # GB at 16,384 x 8,192 (PERF.md, PR 37).
    @jax.checkpoint
    def convolved(qkv, w):
        with S.scope("conv"):
            qkv = silu(causal_conv1d(qkv, w,
                                     jnp.zeros(qkv.shape[-1:], qkv.dtype)))
        key_heads = (qkv.shape[-1] - heads * value_dim) // (2 * key_dim)
        qkv = qkv.reshape(B, T, key_heads, -1)

        def unit(t):
            tf = t.astype(f32)
            return tf * jax.lax.rsqrt(
                jnp.sum(tf * tf, axis=-1, keepdims=True) + 1e-6)

        return ((unit(qkv[..., :key_dim]) * key_dim ** -0.5).astype(x.dtype),
                unit(qkv[..., key_dim:2 * key_dim]).astype(x.dtype),
                qkv[..., 2 * key_dim:].reshape(B, T, heads, value_dim))

    q, k, v = convolved(qkv, p["conv_w"])
    beta = jax.nn.sigmoid(column_parallel_linear(x, p["in_b_w"]).astype(f32))
    g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
        column_parallel_linear(x, p["in_a_w"]).astype(f32)
        + p["dt_bias"].astype(f32))
    with S.scope("delta"):
        o = gated_delta_rule(q, k, v, g, beta)
    # as the convolution: the rule's output and z are kept, not the norm's
    # and the gate's float32 pieces
    o = jax.checkpoint(lambda o, z, w: _rms_norm(o, w, eps) * silu(z))(
        o, z.reshape(o.shape), p["norm_s"])
    return row_parallel_linear(o.reshape(B, T, -1), p["out_w"])


# ------------------------------------------------ hybrid (SSM / attention)
# The mixers of a decoder-hybrid-decoder stack (models/hybrid.py):
# differential attention over shared key/value heads — self (full or
# sliding-window) and on ANOTHER layer's keys and values — the Gated Memory
# Unit, and the Mamba-1 mixer.  Tensor parallelism by heads (whole groups of
# four query heads, below) and by the state-space channels ``E``.

def differential_lambda(p, lam_init):
    """``exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init`` (fp32 scalar) from a
    layer's four learned vectors."""
    f = lambda a, b: jnp.exp(jnp.sum(p[a].astype(jnp.float32)
                                     * p[b].astype(jnp.float32)))
    return f("lam_q1", "lam_k1") - f("lam_q2", "lam_k2") + lam_init


def _differential_context(q, k, v, p, lam_init, eps, window, core_scope):
    """The differential combination around ONE ``core_attention`` call.

    Heads of ``head_dim`` pair up: pair ``P`` of query heads, sub-head ``s``,
    attends key head ``(P // 2, s)`` and the pair's value is ``[v_(P//2, 1) |
    v_(P//2, 2)]``, ``2 * head_dim`` wide; ``o_P = (P_1 - lambda P_2) V``.
    Laid out for the kernels' "consecutive query heads share a head": query
    head ``4g + 2s + r`` is sub-head ``s`` of pair ``2g + r``, key head ``2g
    + s`` (shared by two query heads), value head ``g`` (shared by four) —
    no new softmax, one kernel call per direction, ``P_2``'s part
    subtracted here.  Then a per-pair RMSNorm with a learned scale and
    ``(1 - lam_init)``.  q [B, T, 4G, d], k [B, T, 2G, d], v [B, T, G, 2d]
    -> [B, T, 2G * 2d]."""
    B, T, n, _ = q.shape
    with S.scope(core_scope) if core_scope else contextlib.nullcontext():
        ctx = core_attention(q, k, v, causal=True, window=window)
    lam = differential_lambda(p, lam_init)
    ctx = ctx.reshape(B, T, n // 4, 2, 2, ctx.shape[-1])
    o = (ctx[:, :, :, 0].astype(jnp.float32)
         - lam * ctx[:, :, :, 1].astype(jnp.float32)).astype(q.dtype)
    o = rms_norm(o, p["subln_s"], eps)
    return (o * (1.0 - lam_init).astype(o.dtype)).reshape(B, T, -1)


def _no_sequence_shards(what):
    if axis_size_or_1(SEQ_AXIS) > 1:
        raise ValueError(
            f"{what} is not built for context parallelism: a window, a "
            f"shared key/value hand-over and a state-space scan across "
            f"sequence shards need their state passed between the shards")


@S.scoped("attn")
def differential_attention(x, p, *, head_dim, lam_init, eps, window=None):
    """Differential self-attention (arXiv:2410.05258) over shared key/value
    heads, causal, full or under a sliding ``window``.

    x [B, T, h] replicated over ``model``; ``p``: ``q_w`` [h, 4G d / mp],
    ``k_w`` and ``v_w`` [h, 2G d / mp] column-parallel, ``o_w`` [2G 2d / mp,
    h] row-parallel, ``lam_q1/k1/q2/k2`` [d], ``subln_s`` [2d].  Returns
    ``(out, k, v)``: k [B, T, 2G/mp, d] and v [B, T, G/mp, 2d] as another
    layer's ``shared_kv_attention`` reads them.  The windowed core runs
    under ``dstpu/swa``."""
    _no_sequence_shards("differential_attention")
    B, T, _ = x.shape
    q, k, v = (checkpoint_name(column_parallel_linear(x, p[w]), QKV)
               for w in ("q_w", "k_w", "v_w"))
    q = q.reshape(B, T, -1, head_dim)
    k = k.reshape(B, T, -1, head_dim)
    v = v.reshape(B, T, -1, 2 * head_dim)
    ctx = _differential_context(q, k, v, p, lam_init, eps, window,
                                "swa" if window is not None else None)
    return row_parallel_linear(ctx, p["o_w"]), k, v


@S.scoped("attn")
def shared_kv_attention(x, p, k, v, *, head_dim, lam_init, eps):
    """Cross-decoder attention: differential attention whose keys and
    values are ANOTHER layer's (``differential_attention``'s second and
    third results), causal over the whole sequence.  ``p`` has ``q_w``,
    ``o_w``, its own four ``lam_*`` vectors and ``subln_s``, and no k/v
    projection; the gradient of ``k``/``v`` flows to the layer that made
    them, summed over the layers that read them.  The core runs under
    ``dstpu/xattn``."""
    _no_sequence_shards("shared_kv_attention")
    B, T, _ = x.shape
    q = checkpoint_name(column_parallel_linear(x, p["q_w"]), QKV)
    ctx = _differential_context(q.reshape(B, T, -1, head_dim), k, v, p,
                                lam_init, eps, None, "xattn")
    return row_parallel_linear(ctx, p["o_w"])


@S.scoped("gmu")
def gated_memory_unit(x, memory, p):
    """``(memory * silu(x W1)) W2``: a gate on ANOTHER layer's state —
    ``memory`` [B, T, E/mp] is a Mamba layer's scan output (``mamba_mixer``'s
    second result); ``w1`` [h, E/mp] column-parallel, ``w2`` [E/mp, h]
    row-parallel."""
    gate = silu(checkpoint_name(column_parallel_linear(x, p["w1"]), MIXER_IN))
    return row_parallel_linear(memory.astype(gate.dtype) * gate, p["w2"])


def softplus(x):
    xf = x.astype(jnp.float32)
    return jax.nn.softplus(xf).astype(x.dtype)


@S.scoped("ssm")
def mamba_mixer(x, p, *, state, dt_rank):
    """Mamba-1 mixer.  ``[u, z] = x W_in``; ``u = silu(conv(u))``; ``[r, B,
    C] = u W_x``; ``delta = softplus(r W_dt + b_dt)``; ``y =
    selective_scan(u, delta, -exp(A_log), B, C, D)``; ``out = (y * silu(z))
    W_out``.  Returns ``(out, y)``: ``y`` [B, T, E/mp], the scan's output
    before the gate, is what a ``gated_memory_unit`` reads.

    Sharded over the channels ``E``: ``in_u_w``/``in_z_w`` [h, E/mp] and
    ``dt_w`` [R, E/mp] column-parallel, ``x_w`` [E/mp, R + 2N] and ``out_w``
    [E/mp, h] row-parallel (so r, B and C are whole on every shard),
    ``conv_w`` [K, E/mp], ``conv_b``/``dt_b``/``D`` [E/mp], ``A_log`` [E/mp,
    N].  The recurrence runs in float32 (ops/selective_scan.py) under
    ``dstpu/scan``, the convolution under ``dstpu/conv``."""
    from deepspeed_tpu.ops.selective_scan import causal_conv1d, selective_scan
    _no_sequence_shards("mamba_mixer")
    # named for the "selective" policy, like an attention layer's q, k, v
    u = checkpoint_name(column_parallel_linear(x, p["in_u_w"]), MIXER_IN)
    z = checkpoint_name(column_parallel_linear(x, p["in_z_w"]), MIXER_IN)
    with S.scope("conv"):
        u = silu(causal_conv1d(u, p["conv_w"], p["conv_b"]))
    rbc = row_parallel_linear(u, p["x_w"])
    r, b, c = (rbc[..., :dt_rank], rbc[..., dt_rank:dt_rank + state],
               rbc[..., dt_rank + state:])
    delta = softplus(column_parallel_linear(r, p["dt_w"], p["dt_b"]))
    with S.scope("scan"):
        y = selective_scan(u, delta, -jnp.exp(p["A_log"].astype(jnp.float32)),
                           b, c, p["D"])
    return row_parallel_linear(y * silu(z), p["out_w"]), y
