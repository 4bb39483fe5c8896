"""Qwen3-Next-80B-A3B's decoder (Gated DeltaNet layers three to one with
gated full attention, every layer an expert layer with a softmax router and
a gated shared expert) — its training loss for ONE CHIP'S SHARE of the
experts, in plain float32 ``jax.numpy``.

Written from the published description (``config.json`` of
Qwen/Qwen3-Next-80B-A3B-Instruct; Gated Delta Networks, arXiv:2412.06464;
the public ``Qwen3NextGatedDeltaNet`` / ``Qwen3NextAttention`` /
``Qwen3NextSparseMoeBlock`` modules), not from the program.  Imports nothing
from ``deepspeed_tpu``.  No kernel, no chunked form, no sort, no gather of
tokens: the delta rule is the STEP-BY-STEP recurrence (``delta_rule``: a
scan over time), an expert layer a loop over the experts held, each applied
to EVERY token and weighted by a dense mask.

The equations.  ``x`` [T, h]; ``N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``
(zero-centred RMSNorm: ``w`` starts at zero); no bias anywhere.  Layer ``l``:

    x <- x + Mixer_l(N(x))
    x <- x + MoE(N(x))

``Mixer_l`` is gated full attention (``full``) where ``(l + 1) mod
full_attention_interval = 0`` and Gated DeltaNet (``gdn``) otherwise.  Then a
final ``N`` and an UNTIED head ``x head^T``.

* Gated DeltaNet, ``u = N(x)``.  ``[q | k | v | z] = u W_qkvz`` (``Hk dk + Hk
  dk + Hv dv + Hv dv`` columns, heads contiguous inside each), ``[b | a] = u
  W_ba`` (``Hv + Hv``).  ``[q | k | v] <- silu(conv([q | k | v]))``: a causal
  depthwise convolution of ``K`` taps, no bias, zeros before the start (tap
  ``K - 1`` weighs the current step); ``z`` is not convolved.  Key head ``i``
  serves the value heads ``r i ... r i + r - 1``, ``r = Hv / Hk``.  In
  float32: ``q <- q / sqrt(|q|^2 + 1e-6) / sqrt(dk)``, ``k <- k / sqrt(|k|^2 +
  1e-6)``, ``beta_t = sigmoid(b_t)``, ``g_t = -exp(A_log) softplus(a_t +
  dt_bias)``, ``alpha_t = exp(g_t)``.  Per value head, ``S_0 = 0`` [dk, dv]:

      S_t = alpha_t S_{t-1} + k_t (x) [beta_t (v_t - (alpha_t S_{t-1})^T k_t)]
      o_t = S_t^T q_t

  ``y = (o / sqrt(mean(o^2) + eps) * w_n * silu(z)) W_out``: the norm per
  head over its ``dv`` dims, ``w_n`` [dv] shared by the heads, starting at
  one (NOT zero-centred).
* Gated full attention.  ``[q | gate] = u W_q`` per head (``n`` heads, ``2
  d`` columns each), ``k = u W_k``, ``v = u W_v`` (``n_kv`` heads of ``d``).
  ``q <- N_d(q)``, ``k <- N_d(k)`` per head (zero-centred, one offset vector
  for all heads).  Rotary (theta, no scaling) on the first ``rotary`` dims
  of every q and k head, half-split pairs ``(i, i + rotary / 2)``; the rest
  pass.  Causal softmax of ``q k^T / sqrt(d)``; query heads ``g j ... g j + g
  - 1`` on key head ``j``, ``g = n / n_kv``.  ``y = (context * sigmoid(gate))
  W_o``.
* Expert layer, token ``u``: ``p = softmax(u W_g)`` over ALL ``E`` experts in
  float32; ``K`` = the ``k`` largest of ``p``; ``g_e = p_e / sum_{j in K}
  p_j``;

      y = sum_{e in K, first <= e < first + count} g_e SwiGLU_e(u)
          + sigmoid(u . w_sg) SwiGLU_shared(u)

  — ``held = (first, count)`` is the chip's share: what the experts outside
  it would add is LEFT OUT, and that partial result goes on to the next
  layer.  ``(0, E)`` is the whole layer.  No token is dropped.
* Balance loss, the Switch form over the tokens of the micro-batch: ``c E
  sum_e F_e P_e``, ``F_e`` = (pairs on ``e``) / tokens (no gradient), ``P_e``
  = the mean of ``p_e``; summed over the layers, added to the mean
  next-token cross-entropy (over the rows of the vocabulary that ``head``
  holds: a chip's slice of the table is a smaller vocabulary).

Departures and assumptions (the configuration file lists them under
``assumed``): ``c`` and the initialisers are the family's, not in the
catalog row; the checkpoint's per-key-head interleaving of ``W_qkvz`` /
``W_ba`` is a column permutation of the layout above; no dropout; the
multi-token-prediction module is left out (the row has no sizes for it).

Parameters, one dict per layer in ``params["layers"]`` (``kinds[i]`` names
the mixer):

    embed head [V, h]  norm_w [h]
    every layer: norm1_w norm2_w [h]  router [h, E]
        e_gate e_up [count, h, f] e_down [count, f, h]   (the experts held)
        s_gate s_up [h, fs] s_down [fs, h] w_sg [h]      (the shared expert)
    gdn:  w_qkvz [h, 2 Hk dk + 2 Hv dv]  w_ba [h, 2 Hv]
          conv [K, 2 Hk dk + Hv dv]  A_log dt_bias [Hv]  norm_g [dv]
          w_out [Hv dv, h]
    full: wq [h, n 2 d]  wk wv [h, n_kv d]  q_norm_w k_norm_w [d]
          wo [n d, h]

``dtype`` and ``operand_bits``: see ``bert.py`` (parameters and activations
stored in ``dtype``; matmul operands — the recurrence's q, k and v among
them, never its state — rounded to ``operand_bits`` mantissa bits, the
cotangents too) — they price a precision step, they are not the reference.

So that the gradient at the published widths fits a 16 GB chip beside the
parameters, each layer, each block of ``STEP_BLOCK`` steps of the
recurrence, each block of queries, each expert and each block of the head is
a ``jax.checkpoint``: the backward keeps their inputs and computes them
again.
"""

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.kimi_moe import (first_adam_step, matmul, rounded,
                                          silu, swiglu)

__all__ = ["delta_rule", "first_adam_step", "loss"]

#: queries per block of the dense masked softmax (16 heads x 256 x 16,384
#: float32 scores are 268 MB)
QUERY_BLOCK = 256
#: positions per row and block of the head and its cross-entropy
HEAD_BLOCK = 2048
#: steps of the recurrence per checkpointed block: the backward holds the
#: state at every block's start and one block's states (2 x 128 x 2 MB a row
#: at 16,384 steps, 32 heads of 128 x 128)
STEP_BLOCK = 128


def norm(x, w, eps, zero_centred=True):
    """``x / sqrt(mean(x^2) + eps)`` times ``1 + w`` (or ``w``)."""
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    w = w.astype(jnp.float32)
    return (y * (1.0 + w if zero_centred else w)).astype(x.dtype)


def rotate(x, theta):
    """Rotary on x [B, T, n, r]: the pair ``(i, i + r/2)`` of position ``t``
    turned by ``t * theta^(-2i/r)``."""
    T, r = x.shape[1], x.shape[-1]
    half = r // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / r)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def causal_conv(x, w):
    """``y_t = sum_j w[j] x_{t - (K - 1) + j}`` per channel, zeros before
    the start; x [B, T, C], w [K, C]."""
    K, T = w.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    return sum(w[j].astype(jnp.float32) * padded[:, j:j + T]
               for j in range(K)).astype(x.dtype)


def delta_rule(q, k, v, g, beta):
    """The gated delta rule, one step at a time, in float32: q, k [B, T, H,
    dk] (one per VALUE head), v [B, T, H, dv], g, beta [B, T, H] -> o [B, T,
    H, dv].  Products as sums of elementwise products: no matmul precision
    to set."""
    B, T, H, dk = q.shape
    f32 = jnp.float32

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None, None] * S
        seen = jnp.sum(S * k_t[..., :, None], axis=-2)          # S^T k
        S = S + k_t[..., :, None] * (b_t[..., None] * (v_t - seen))[
            ..., None, :]
        return S, jnp.sum(S * q_t[..., :, None], axis=-2)       # S^T q

    block = STEP_BLOCK if T % STEP_BLOCK == 0 else T
    blocks = tuple(
        jnp.moveaxis(x.astype(f32), 1, 0).reshape(
            T // block, block, *x.shape[:1], *x.shape[2:])
        for x in (q, k, v, g, beta))
    S0 = jnp.zeros((B, H, dk, v.shape[-1]), f32)
    _, o = jax.lax.scan(jax.checkpoint(
        lambda S, xs: jax.lax.scan(step, S, xs)), S0, blocks)
    return jnp.moveaxis(o.reshape(T, B, H, -1), 0, 1)


def gated_delta_net(u, p, heads, dims, eps, bits):
    """``heads`` = (Hk, Hv), ``dims`` = (dk, dv)."""
    B, T, _ = u.shape
    (hk, hv), (dk, dv) = heads, dims
    f32 = jnp.float32
    qkvz = matmul(u, p["w_qkvz"], bits)
    ba = matmul(u, p["w_ba"], bits).astype(f32)
    split = 2 * hk * dk + hv * dv
    qkv = silu(causal_conv(qkvz[..., :split], p["conv"]))
    z = qkvz[..., split:].reshape(B, T, hv, dv)
    q = qkv[..., :hk * dk].reshape(B, T, hk, dk).astype(f32)
    k = qkv[..., hk * dk:2 * hk * dk].reshape(B, T, hk, dk).astype(f32)
    v = qkv[..., 2 * hk * dk:].reshape(B, T, hv, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) \
        / jnp.sqrt(f32(dk))
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
        ba[..., hv:] + p["dt_bias"].astype(f32))
    # value head j reads key head j // (Hv / Hk)
    q, k = (jnp.repeat(rounded(t.astype(u.dtype), bits), hv // hk, axis=2)
            for t in (q, k))
    o = delta_rule(q, k, rounded(v, bits), g, beta).astype(u.dtype)
    o = norm(o, p["norm_g"], eps, zero_centred=False) * silu(z)
    return matmul(o.reshape(B, T, hv * dv), p["w_out"], bits)


def gated_attention(u, p, heads, head_dim, rotary, theta, eps, bits):
    """``heads`` = (n, n_kv).  Dense causal softmax per head, in blocks of
    ``QUERY_BLOCK`` queries."""
    B, T, _ = u.shape
    n, kv = heads
    d, group = head_dim, n // kv
    qg = matmul(u, p["wq"], bits).reshape(B, T, n, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = matmul(u, p["wk"], bits).reshape(B, T, kv, d)
    v = matmul(u, p["wv"], bits).reshape(B, T, kv, d)
    q, k = norm(q, p["q_norm_w"], eps), norm(k, p["k_norm_w"], eps)
    q, k = (jnp.concatenate([rotate(t[..., :rotary], theta),
                             t[..., rotary:]], axis=-1) for t in (q, k))
    q = q.reshape(B, T, kv, group, d)
    block = min(T, QUERY_BLOCK)
    key_pos = jnp.arange(T)

    @jax.checkpoint
    def one_block(start, q, k, v):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        seen = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        scores = jnp.einsum("bqjgd,bsjd->bjgqs", rounded(qb, bits),
                            rounded(k, bits),
                            preferred_element_type=jnp.float32) / jnp.sqrt(
                                jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf),
                               axis=-1).astype(u.dtype)
        return jnp.einsum("bjgqs,bsjd->bqjgd", rounded(probs, bits),
                          rounded(v, bits),
                          preferred_element_type=jnp.float32)

    o = jax.lax.map(lambda start: one_block(start, q, k, v),
                    jnp.arange(0, T, block))              # [nb, B, block, ..]
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, n, d)
    o = (o * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(u.dtype)
    return matmul(o.reshape(B, T, n * d), p["wo"], bits)


def expert_layer(u, p, experts_per_token, held, coefficient, bits):
    """u [B, T, h] -> ``(y, balance loss, pairs held)``: the share's part of
    the routed experts plus the gated shared expert; ``pairs held`` counts
    the (token, choice) pairs that landed on an expert of the share."""
    first, count = held
    E = p["router"].shape[1]
    f32 = jnp.float32
    probs = jax.nn.softmax(
        matmul(u.astype(f32), p["router"].astype(f32), bits), axis=-1)
    picked, chosen = jax.lax.top_k(probs, experts_per_token)      # [B, T, k]
    gates = picked / jnp.sum(picked, axis=-1, keepdims=True)
    # dense [B, T, E]: the gate of each expert for each token, 0 if not chosen
    member = chosen[..., None] == jnp.arange(E)
    weight = jnp.sum(jnp.where(member, gates[..., None], 0.0), axis=-2)
    shared_gate = jax.nn.sigmoid(jnp.sum(
        u.astype(f32) * p["w_sg"].astype(f32), axis=-1))
    y = (shared_gate[..., None] * swiglu(
        u, p["s_gate"], p["s_up"], p["s_down"], bits).astype(f32)
         ).astype(u.dtype)

    @jax.checkpoint
    def weighted(u, w, e_gate, e_up, e_down):
        out = swiglu(u, e_gate, e_up, e_down, bits)
        return (w[..., None] * out.astype(f32)).astype(u.dtype)

    # every token through each expert held, one after the other (the sum is
    # outside the checkpoint: its backward keeps no running sum)
    y, _ = jax.lax.scan(
        lambda y, expert: (y + weighted(u, *expert), None), y,
        (jnp.moveaxis(weight[..., first:first + count], -1, 0),
         p["e_gate"], p["e_up"], p["e_down"]))
    tokens = u.shape[0] * u.shape[1]
    F = jnp.sum(member, axis=(0, 1, 2)).astype(f32) / tokens
    balance = coefficient * E * jnp.sum(
        jax.lax.stop_gradient(F) * jnp.mean(probs, axis=(0, 1)))
    pairs = jnp.sum((chosen >= first) & (chosen < first + count))
    return y, balance, pairs


def loss(params, batch, *, kinds, attn_heads, head_dim, rotary, theta,
         delta_heads, delta_dims, experts_per_token, held, coefficient, eps,
         dtype=jnp.float32, operand_bits=None):
    """``(loss, balance, pairs held)`` of ``batch`` = (tokens, labels), both
    [B, T] (negative labels are left out): ``loss`` = the mean next-token
    cross-entropy over the rows of ``params["head"]`` + ``balance``, the
    balance loss summed over the layers; ``pairs held`` the (token, choice)
    pairs that landed on the share ``held`` = (first, count), summed over
    the layers.  ``kinds``: the mixer of every layer (``gdn`` / ``full``);
    ``attn_heads`` (n, n_kv); ``delta_heads`` (Hk, Hv); ``delta_dims`` (dk,
    dv); ``rotary``: the rotated dims of a head."""
    tokens, labels = batch
    bits = operand_bits
    params = jax.tree_util.tree_map(lambda w: w.astype(dtype), params)

    def layer(x, p, kind):
        u = norm(x, p["norm1_w"], eps)
        if kind == "gdn":
            x = x + gated_delta_net(u, p, delta_heads, delta_dims, eps, bits)
        elif kind == "full":
            x = x + gated_attention(u, p, attn_heads, head_dim, rotary,
                                    theta, eps, bits)
        else:
            raise ValueError(f"unknown kind of layer {kind!r}")
        y, b, n = expert_layer(norm(x, p["norm2_w"], eps), p,
                               experts_per_token, held, coefficient, bits)
        return x + y, b, n

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
        x = norm(x, params["norm_w"], eps)
        B, T = labels.shape
        block = HEAD_BLOCK if T % HEAD_BLOCK == 0 else T
        picked, kept = jax.lax.map(
            lambda xl: head_block(*xl, params["head"]),
            (jnp.moveaxis(x.reshape(B, T // block, block, -1), 1, 0),
             jnp.moveaxis(labels.reshape(B, T // block, block), 1, 0)))
    ce = -jnp.sum(picked) / jnp.maximum(jnp.sum(kept), 1.0)
    return ce + balance, balance, pairs
