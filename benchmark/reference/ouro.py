"""Ouro (looped LM) training loss in plain float32 ``jax.numpy``.

Written from the model's published description (ByteDance Ouro "LoopLM",
2025-10: ``config.json`` of Ouro-2.6B and the family's modelling file and
paper), not from the program.  Imports nothing from ``deepspeed_tpu``.

The equations:

* ``x0 = embed[tokens]``: no position table, no embedding norm.
* One layer (sandwich placement: a norm before and after each sub-layer,
  inside the residual):
  ``a = x + RMSNorm(Attn(RMSNorm(x)))``, ``y = a + RMSNorm(SwiGLU(RMSNorm(a)))``
  with ``RMSNorm(u) = u / sqrt(mean(u^2) + eps) * g``;
  ``Attn``: ``q, k, v = u Wq, u Wk, u Wv`` (no biases), heads of ``head_dim``,
  rotary positions on q and k over the whole head ("rotate-half": dimension
  i pairs with i + head_dim/2, frequency ``theta^(-2i/head_dim)``), causal
  ``softmax(q k^T / sqrt(head_dim)) v``, then ``Wo``;
  ``SwiGLU(u) = (silu(u Wg) * (u Wu)) Wd``.
* The loop: ``h^0 = x0``; ``h^t = RMSNorm_f(layer_L(... layer_1(h^(t-1))))``
  for t = 1..passes, the same weights every pass.
* Exits: ``logits^t = h^t W_head`` (untied); ``lambda_t = sigmoid(h^t .
  w_gate + b_gate)``; ``p_1 = lambda_1``, ``p_t = lambda_t prod_{j<t}(1 -
  lambda_j)``, and the last exit takes what is left, ``prod_{j<last}(1 -
  lambda_j)``.
* Loss per labelled position: ``sum_t p_t CE(logits^t, label) - beta H(p)``,
  ``H(p) = -sum_t p_t ln p_t``; the mean over labelled positions.

Departures and assumptions (the configuration file lists them under
``assumed``): the final norm's output is what the next pass takes in; the
stage-I objective with a fixed ``beta``; no dropout; no early exit.

Parameters (``L`` layers stacked on the leading axis, heads contiguous in
the output columns of ``wq``/``wk``/``wv``):

    embed [V, h]  lm_head [h, V]  norm_g [h]  gate_w [h]  gate_b []
    layers: input_norm_g attn_out_norm_g pre_ffn_norm_g ffn_out_norm_g [L, h]
            wq wk wv [L, h, n*d]  wo [L, n*d, h]
            w_gate w_up [L, h, f]  w_down [L, f, h]

``dtype`` and ``operand_bits``: see ``bert.py`` (parameters and activations
stored in ``dtype``; matmul operands rounded to ``operand_bits`` mantissa
bits) — they price a precision step, they are not the reference.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.ops import matmul, round_mantissa


def rms_norm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def rotate(x, theta):
    """Rotary position embedding of x [B, T, n, d], positions 0..T-1."""
    T, d = x.shape[1], x.shape[-1]
    half = d // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / d)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def attention(u, p, head_dim, theta, bits):
    B, T, _ = u.shape
    q, k, v = (matmul(u, p[w], bits).reshape(B, T, -1, head_dim)
               for w in ("wq", "wk", "wv"))
    q, k = rotate(q, theta), rotate(k, theta)
    scores = jnp.einsum("bqnd,bknd->bnqk", round_mantissa(q, bits),
                        round_mantissa(k, bits),
                        preferred_element_type=jnp.float32) / jnp.sqrt(
                            jnp.float32(head_dim))
    scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(u.dtype)
    ctx = jnp.einsum("bnqk,bknd->bqnd", round_mantissa(probs, bits),
                     round_mantissa(v, bits),
                     preferred_element_type=jnp.float32).astype(u.dtype)
    return matmul(ctx.reshape(B, T, -1), p["wo"], bits)


def swiglu(u, p, bits):
    g = matmul(u, p["w_gate"], bits)
    gate = (g.astype(jnp.float32) * jax.nn.sigmoid(g.astype(jnp.float32))
            ).astype(u.dtype)
    return matmul(gate * matmul(u, p["w_up"], bits), p["w_down"], bits)


def layer(x, p, head_dim, theta, eps, bits):
    a = x + rms_norm(attention(rms_norm(x, p["input_norm_g"], eps), p,
                               head_dim, theta, bits),
                     p["attn_out_norm_g"], eps)
    return a + rms_norm(swiglu(rms_norm(a, p["pre_ffn_norm_g"], eps), p,
                               bits), p["ffn_out_norm_g"], eps)


def exits(params, tokens, labels, *, passes, head_dim, theta, eps, bits):
    """Per-position cross-entropy ``[passes, B, T]`` and gate probability
    ``lambda`` ``[passes, B, T]`` of every exit."""
    h = params["embed"][tokens]
    ce, stop = [], []
    for _ in range(passes):
        # the layers in order, one after the other (a scan like the other
        # references': written out layer by layer the 36 applications of
        # the cell took the TPU's compiler 64 s, and 5 s to read back)
        h, _ = jax.lax.scan(
            lambda x, p: (layer(x, p, head_dim, theta, eps, bits), None),
            h, params["layers"])
        h = rms_norm(h, params["norm_g"], eps)
        logits = jnp.matmul(round_mantissa(h, bits),
                            round_mantissa(params["lm_head"], bits),
                            preferred_element_type=jnp.float32)
        log_probs = jax.nn.log_softmax(logits, axis=-1)
        ce.append(-jnp.take_along_axis(
            log_probs, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0])
        stop.append(jax.nn.sigmoid(
            h.astype(jnp.float32) @ params["gate_w"].astype(jnp.float32)
            + params["gate_b"].astype(jnp.float32)))
    return jnp.stack(ce), jnp.stack(stop)


def exit_distribution(stop):
    """``lambda`` [passes, ...] -> ``p`` [passes, ...]."""
    p, left = [], jnp.ones_like(stop[0])
    for t in range(stop.shape[0] - 1):
        p.append(stop[t] * left)
        left = left * (1.0 - stop[t])
    return jnp.stack(p + [left])


def loss(params, batch, *, passes, head_dim, theta, eps, beta,
         dtype=jnp.float32, operand_bits=None):
    """Mean over the labelled positions of ``sum_t p_t CE_t - beta H(p)`` for
    ``batch`` = (tokens, labels), both [B, T]; positions with a negative
    label are left out."""
    tokens, labels = batch
    params = jax.tree_util.tree_map(lambda w: w.astype(dtype), params)
    with jax.default_matmul_precision("highest"):
        ce, stop = exits(params, tokens, labels, passes=passes,
                         head_dim=head_dim, theta=theta, eps=eps,
                         bits=operand_bits)
    p = exit_distribution(stop)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    per_position = jnp.sum(p * ce, axis=0) - beta * entropy
    keep = (labels >= 0).astype(jnp.float32)
    return jnp.sum(per_position * keep) / jnp.maximum(jnp.sum(keep), 1.0)
