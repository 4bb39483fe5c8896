"""Kimi-VL-A3B's language model (the DeepSeek-V3 layer: latent attention and
a dropless expert layer with shared experts and a sigmoid, bias-corrected
router) — its training loss for ONE CHIP'S SHARE of the experts, in plain
float32 ``jax.numpy``.

Written from the published description (``config.json`` of
moonshotai/Kimi-VL-A3B-Instruct, ``text_config``; DeepSeek-V3,
arXiv:2412.19437 section 2.1; DeepSeek-V2, arXiv:2405.04434 for MLA), not
from the program.  Imports nothing from ``deepspeed_tpu``.  No kernel, no
sort, no gather of tokens: an expert layer is a loop over the experts held,
each applied to EVERY token and weighted by a dense mask.

The equations.  ``x`` [T, h]; RMSNorm ``x / sqrt(mean(x^2) + eps) * g``; no
bias anywhere.  Layer ``l``:

    x <- x + MLA(RMSNorm(x))
    x <- x + F_l(RMSNorm(x))

``F_0`` (``dense``) is ``SwiGLU(u) = (silu(u W_gate) * (u W_up)) W_down``;
``F_l``, ``l >= 1`` (``moe``), the expert layer.  Then a final RMSNorm and
an UNTIED head ``x head^T``.

* MLA (``q_lora_rank`` null: queries are not compressed).  ``q = x W_q`` ->
  ``n`` heads of ``d_nope + d_rope`` = ``[q_nope | q_pe]``.  ``[c | k_pe] = x
  W_kv_a``: ``c`` is ``kv_lora_rank`` wide, ``k_pe`` ``d_rope`` wide, ONE per
  token, shared by all heads.  ``c <- RMSNorm(c)``.  ``[k_nope | v] = c
  W_kv_b`` -> ``n`` heads of ``d_nope + d_v``.  Rotary (theta, no scaling)
  on ``q_pe`` and ``k_pe``: position ``t`` turns the pair ``(i, i + d_rope /
  2)`` by ``t * theta^(-2i / d_rope)``.  ``k_h = [k_nope_h | k_pe]``; causal
  softmax of ``q_h k_h^T / sqrt(d_nope + d_rope)`` times ``v_h``; the heads
  concatenated times ``W_o``.  The expanded form (training's).
* Expert layer, token ``u``: ``s = sigmoid(u W_g)`` over ALL ``E`` experts;
  ``K`` = the ``k`` largest of ``s + b`` (``b`` = ``e_score_correction_bias``:
  selection only; ``n_group = topk_group = 1``, so group-limited routing is
  the identity); ``g_e = scale * s_e / (sum_{j in K} s_j + 1e-20)``;

      y = sum_{e in K, first <= e < first + count} g_e SwiGLU_e(u)
          + SwiGLU_shared(u)

  — ``held = (first, count)`` is the chip's share: what the experts outside
  it would add is LEFT OUT, and that partial result goes on to the next
  layer.  ``(0, E)`` is the whole layer.  No token is dropped.
* Balance loss (``seq_aux``), DeepSeek-V3 eqs. 17-20, per sequence of ``T``
  tokens: ``f_e = E / (k T) sum_t 1[e in K_t]``, ``P_e = 1 / T sum_t s_te /
  sum_j s_tj``, ``alpha sum_e f_e P_e``; the mean over the sequences, summed
  over the expert layers, is added to the mean next-token cross-entropy
  (over the rows of the vocabulary that ``head`` holds: a chip's slice of
  the table is a smaller vocabulary).

Departures and assumptions (the configuration file lists them under
``assumed``): ``alpha`` 0.001 and the init are the family's, not in the
catalog row; the rotary layout is the half-split one (the checkpoint's
interleaved layout is a column permutation of ``W_q`` and ``W_kv_a``); ``b``
is a parameter that nothing moves (the step-boundary balancing update of
DeepSeek-V3 section 2.1.2 is not run); no dropout; no vision tower.

Parameters, one dict per layer in ``params["layers"]`` (``kinds[i]`` names
``F``), heads contiguous in the output columns:

    embed head [V, h]  norm_g [h]
    every layer: norm1_g norm2_g [h]  wq [h, n (d_nope + d_rope)]
        wkv_a [h, r + d_rope] (c | k_pe)  kv_norm_g [r]
        wkv_b [r, n (d_nope + d_v)] (per head: k_nope | v)  wo [n d_v, h]
        w_gate w_up [h, f]  w_down [f, h]   (dense: the MLP; moe: the
        shared experts as one SwiGLU)
    moe: router [h, E]  bias [E]
        e_gate e_up [count, h, fe]  e_down [count, fe, h]   (the experts held)

``dtype`` and ``operand_bits``: see ``bert.py`` (parameters and activations
stored in ``dtype``; matmul operands rounded to ``operand_bits`` mantissa
bits) — they price a precision step, they are not the reference.  The
rounding is differentiable here (``rounded``: the gradient passes through,
rounded the same way), so the GRADIENT of a lower precision can be priced
too, and with it the first optimizer step (``first_adam_step``).

So that the gradient at the published widths fits a 16 GB chip beside the
parameters, each layer, each block of queries, each expert and each block of
the head is a ``jax.checkpoint``: the backward keeps their inputs and
computes them again.  The forward changes only in the order the head sums
its positions (block by block).
"""

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.ops import round_mantissa

#: queries per block of the dense masked softmax (2 rows x 16 heads x 256 x
#: 8192 float32 scores are 268 MB)
QUERY_BLOCK = 256
#: positions per row and block of the head and its cross-entropy (2 rows x
#: 2048 x 20,480 float32 logits are 336 MB)
HEAD_BLOCK = 2048


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def rounded(x, bits):
    """``round_mantissa`` with a gradient: the cotangent passes through,
    rounded to the same ``bits`` (a body at that precision rounds the
    operands of its backward products as well)."""
    return round_mantissa(x, bits)


def _rounded_fwd(x, bits):
    return round_mantissa(x, bits), None


def _rounded_bwd(bits, _, g):
    return (round_mantissa(g, bits),)


rounded.defvjp(_rounded_fwd, _rounded_bwd)


def matmul(x, w, bits):
    return jnp.matmul(rounded(x, bits), rounded(w, bits),
                      preferred_element_type=jnp.float32).astype(x.dtype)


def rms_norm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def silu(x):
    xf = x.astype(jnp.float32)
    return (xf * jax.nn.sigmoid(xf)).astype(x.dtype)


def swiglu(u, w_gate, w_up, w_down, bits):
    return matmul(silu(matmul(u, w_gate, bits)) * matmul(u, w_up, bits),
                  w_down, bits)


def rotate(x, theta):
    """Rotary on x [B, T, ..., d]: the pair ``(i, i + d/2)`` of position
    ``t`` turned by ``t * theta^(-2i/d)``."""
    T, d = x.shape[1], x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    shape = (1, T) + (1,) * (x.ndim - 3) + (half,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def latent_attention(x, p, heads, dims, theta, eps, bits):
    """``dims`` = (d_nope, d_rope, d_v).  Dense causal softmax per head, in
    blocks of ``QUERY_BLOCK`` queries."""
    B, T, _ = x.shape
    d_nope, d_rope, d_v = dims
    rank = p["kv_norm_g"].shape[0]
    q = matmul(x, p["wq"], bits).reshape(B, T, heads, d_nope + d_rope)
    down = matmul(x, p["wkv_a"], bits)
    c = rms_norm(down[..., :rank], p["kv_norm_g"], eps)
    k_pe = rotate(down[..., rank:], theta)                     # [B, T, d_rope]
    kv = matmul(c, p["wkv_b"], bits).reshape(B, T, heads, d_nope + d_v)
    q = jnp.concatenate([q[..., :d_nope], rotate(q[..., d_nope:], theta)],
                        axis=-1)
    k = jnp.concatenate(
        [kv[..., :d_nope],
         jnp.broadcast_to(k_pe[:, :, None, :], (B, T, heads, d_rope))],
        axis=-1)
    v = kv[..., d_nope:]
    block = min(T, QUERY_BLOCK)
    key_pos = jnp.arange(T)

    @jax.checkpoint
    def one_block(start, q, k, v):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        seen = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        scores = jnp.einsum("bqnd,bknd->bnqk", rounded(qb, bits),
                            rounded(k, bits),
                            preferred_element_type=jnp.float32) / jnp.sqrt(
                                jnp.float32(d_nope + d_rope))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf),
                               axis=-1).astype(x.dtype)
        return jnp.einsum("bnqk,bknd->bqnd", rounded(probs, bits),
                          rounded(v, bits),
                          preferred_element_type=jnp.float32)

    o = jax.lax.map(lambda start: one_block(start, q, k, v),
                    jnp.arange(0, T, block))              # [nb, B, block, ..]
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, heads * d_v).astype(x.dtype)
    return matmul(o, p["wo"], bits)


def expert_layer(u, p, experts_per_token, held, scale, alpha, bits):
    """u [B, T, h] -> ``(y, balance loss, pairs held)``: the share's part of
    the routed experts plus the shared experts; ``pairs held`` counts the
    (token, choice) pairs that landed on an expert of the share."""
    first, count = held
    E = p["router"].shape[1]
    scores = jax.nn.sigmoid(
        matmul(u.astype(jnp.float32), p["router"].astype(jnp.float32), bits))
    _, chosen = jax.lax.top_k(scores + p["bias"].astype(jnp.float32),
                              experts_per_token)               # [B, T, k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = scale * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    # dense [B, T, E]: the gate of each expert for each token, 0 if not chosen
    member = chosen[..., None] == jnp.arange(E)
    weight = jnp.sum(jnp.where(member, gates[..., None], 0.0), axis=-2)
    y = swiglu(u, p["w_gate"], p["w_up"], p["w_down"], bits)

    @jax.checkpoint
    def weighted(u, w, e_gate, e_up, e_down):
        out = swiglu(u, e_gate, e_up, e_down, bits)
        return (w[..., None] * out.astype(jnp.float32)).astype(u.dtype)

    for i in range(count):                    # every token through each expert
        y = y + weighted(u, weight[..., first + i], p["e_gate"][i],
                         p["e_up"][i], p["e_down"][i])
    tokens = u.shape[1]
    f = (jnp.sum(member, axis=(1, 2)).astype(jnp.float32)
         * (E / (experts_per_token * tokens)))
    P = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=1)
    balance = alpha * jnp.mean(jnp.sum(f * P, axis=-1))
    pairs = jnp.sum((chosen >= first) & (chosen < first + count))
    return y, balance, pairs


def loss(params, batch, *, kinds, heads, dims, experts_per_token, held,
         route_scale, alpha, theta, eps, dtype=jnp.float32,
         operand_bits=None):
    """``(loss, balance, pairs held)`` of ``batch`` = (tokens, labels), both
    [B, T] (negative labels are left out): ``loss`` = the mean next-token
    cross-entropy over the rows of ``params["head"]`` + ``balance``, the
    balance loss summed over the expert layers; ``pairs held`` the (token,
    choice) pairs that landed on the share ``held`` = (first, count),
    summed over the expert layers.  ``kinds``: ``F`` of every layer
    (``dense`` / ``moe``); ``heads``: query heads; ``dims``: (d_nope,
    d_rope, d_v)."""
    tokens, labels = batch
    bits = operand_bits
    params = jax.tree_util.tree_map(lambda w: w.astype(dtype), params)

    def layer(x, p, kind):
        x = x + latent_attention(rms_norm(x, p["norm1_g"], eps), p, heads,
                                 dims, theta, eps, bits)
        u = rms_norm(x, p["norm2_g"], eps)
        if kind == "dense":
            return (x + swiglu(u, p["w_gate"], p["w_up"], p["w_down"], bits),
                    jnp.float32(0.0), jnp.int32(0))
        if kind == "moe":
            y, b, n = expert_layer(u, p, experts_per_token, held,
                                   route_scale, alpha, bits)
            return x + y, b, n
        raise ValueError(f"unknown kind of layer {kind!r}")

    @jax.checkpoint
    def head_block(xb, lb, head):
        """(sum of the labelled positions' log-probabilities, their number)
        of one block of positions."""
        logits = jnp.matmul(rounded(xb, bits), rounded(head.T, bits),
                            preferred_element_type=jnp.float32)
        log_probs = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            log_probs, jnp.maximum(lb, 0)[..., None], axis=-1)[..., 0]
        keep = (lb >= 0).astype(jnp.float32)
        return jnp.sum(picked * keep), jnp.sum(keep)

    balance, pairs = jnp.float32(0.0), jnp.int32(0)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for kind, p in zip(kinds, params["layers"], strict=True):
            x, b, n = jax.checkpoint(functools.partial(layer, kind=kind))(
                x, p)
            balance, pairs = balance + b, pairs + n
        x = rms_norm(x, params["norm_g"], eps)
        B, T = labels.shape
        block = HEAD_BLOCK if T % HEAD_BLOCK == 0 else T
        picked, kept = jax.lax.map(
            lambda xl: head_block(*xl, params["head"]),
            (jnp.moveaxis(x.reshape(B, T // block, block, -1), 1, 0),
             jnp.moveaxis(labels.reshape(B, T // block, block), 1, 0)))
    ce = -jnp.sum(picked) / jnp.maximum(jnp.sum(kept), 1.0)
    return ce + balance, balance, pairs


def first_adam_step(grads, *, lr, clip, beta1=0.9, beta2=0.999, eps=1e-8):
    """The change of every parameter at Adam's FIRST step from zero moments
    on ``grads``, after the gradient's global norm is clipped to ``clip``
    (0: not clipped).  Adam in the form Kingma & Ba (2015) give at the end
    of their section 2 and fused implementations (apex, DeepSpeed) compute:
    ``m = (1 - beta1) g``, ``v = (1 - beta2) g^2``, step size ``lr sqrt(1 -
    beta2) / (1 - beta1)``, ``delta = -step size * m / (sqrt(v) + eps)`` —
    ``eps`` joins ``sqrt(v)`` BEFORE the bias correction, so at the first
    step

        ``delta = -lr g / (|g| + eps / sqrt(1 - beta2))``,  ``g`` clipped.

    (Their algorithm 1 corrects ``v`` first and has ``eps`` alone there: the
    two differ where ``|g|`` is within ``eps / sqrt(1 - beta2)`` = 3e-7 of
    zero, which a layer's query projection is at a random start.)  No
    weight decay.  A weight the gradient reaches moves by about ``lr``
    against it; a weight it does not reach stays."""
    del beta1                         # cancels at the first step
    leaves = jax.tree_util.tree_leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in leaves))
    scale = jnp.minimum(1.0, clip / (norm + 1e-6)) if clip else 1.0
    floor = eps / (1.0 - beta2) ** 0.5
    return jax.tree_util.tree_map(
        lambda g: -lr * (g * scale) / (jnp.abs(g * scale) + floor), grads)
