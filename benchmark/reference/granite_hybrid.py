"""Granite-4.0-H-Micro's decoder (Mamba-2 layers nine to one with causal
attention that has no positions, every layer a dense SwiGLU MLP, scaled
paths) — its training loss, in plain float32 ``jax.numpy``.

Written from the published description (``config.json`` of
ibm-granite/granite-4.0-h-micro, ``model_type`` ``granitemoehybrid``:
``layer_types``, the ``mamba_*`` keys, ``attention_multiplier``,
``embedding_multiplier``, ``residual_multiplier``, ``logits_scaling``; the
public ``GraniteMoeHybridForCausalLM`` / ``Mamba2Mixer`` modules; Mamba-2,
arXiv:2405.21060), not from the program.  Imports nothing from
``deepspeed_tpu``.  No kernel, no chunked form: the state-space duality is
the STEP-BY-STEP recurrence (``ssd``: a scan over time), attention a dense
softmax under a plain causal mask.

The equations.  ``x`` [T, h]; ``N(x) = x / sqrt(mean(x^2) + eps) * w`` (``w``
starts at one); no bias on any matrix.

    x <- m_e E[t]
    x <- x + m_r Mixer_l(N1(x))          (layer l)
    x <- x + m_r MLP(N2(x))
    logits = N_f(x) E^T / m_l            (E tied: the embedding's table)

``Mixer_l`` is Mamba-2 where ``layer_types[l]`` is ``mamba`` and attention
where it is ``attention``; ``MLP(u) = (silu(a) * b) W_out`` with ``[a | b]
= u W_in`` (``ffn`` columns each).

* Mamba-2, ``u = N1(x)``.  ``[z | xBC | dt] = u W_in_proj`` (``E = H P``,
  ``E + 2 G N``, ``H`` columns); ``xBC <- silu(conv(xBC) + b)``: a causal
  depthwise convolution of ``K`` taps with a bias, zeros before the start
  (tap ``K - 1`` weighs the current step); ``[x | B | C] = xBC`` (``E``,
  ``G N``, ``G N``), x in ``H`` heads of ``P``, B and C in ``G`` groups of
  ``N``; group ``i`` serves the heads ``i H/G ... (i + 1) H/G - 1``.  In
  float32: ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, and per
  head ``S_0 = 0`` [N, P]:

      S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T
      y_t = S_t^T C_t + D x_t

  ``out = (w_g * N_g(y * silu(z))) W_out_proj``: the gate BEFORE the norm,
  the norm over each group's ``E / G`` channels.
* Attention.  ``q = u W_q`` (``n`` heads of ``d``), ``k = u W_k``, ``v = u
  W_v`` (``n_kv`` heads of ``d``); query heads ``g j ... g j + g - 1`` on key
  head ``j``, ``g = n / n_kv``; no positions; softmax of ``m_a q k^T`` over
  the keys ``s <= t``; ``y = context W_o``.

Departures and assumptions (the configuration file lists them under
``assumed``): the initialisation is the family's, not in the catalog row;
the published ``[z | xBC | dt]`` and ``[a | b]`` column orders are kept; no
dropout; the loss is over the rows of the table that ``embed`` holds (a
chip's slice of the table is a smaller vocabulary).

Parameters, one dict per layer in ``params["layers"]`` (``kinds[i]`` names
the mixer):

    embed [V, h]  norm_w [h]
    every layer: norm1_w norm2_w [h]  mlp_in [h, 2 ffn]  mlp_out [ffn, h]
    mamba2:    in_proj [h, 2 E + 2 G N + H]  conv [K, E + 2 G N]
               conv_b [E + 2 G N]  A_log D dt_bias [H]  norm_g [E]
               out_proj [E, h]
    attention: wq [h, n d]  wk wv [h, n_kv d]  wo [n d, h]

``dtype`` and ``operand_bits``: see ``bert.py`` (parameters and activations
stored in ``dtype``; matmul operands — the recurrence's x, B and C among
them, never its state — and their cotangents rounded to ``operand_bits``
mantissa bits) — they price a precision step, they are not the reference.

So that the gradient at the published widths fits a 16 GB chip beside the
parameters, each layer, each block of ``STEP_BLOCK`` steps of the
recurrence, each block of queries and each block of the head is a
``jax.checkpoint``: the backward keeps their inputs and computes them
again.
"""

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.kimi_moe import first_adam_step, matmul, rounded

__all__ = ["first_adam_step", "loss", "ssd"]

#: queries per block of the dense masked softmax (32 heads x 256 x 8,192
#: float32 scores are 268 MB)
QUERY_BLOCK = 256
#: positions per row and block of the head and its cross-entropy
HEAD_BLOCK = 2048
#: steps of the recurrence per checkpointed block: the backward holds the
#: state at every block's start and one block's states (64 + 128 x 2 MB a
#: row at 8,192 steps, 64 heads of 128 x 64)
STEP_BLOCK = 128


def norm(x, w, eps):
    """``x / sqrt(mean(x^2) + eps) * w`` over the last axis, in float32."""
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def silu(x):
    xf = x.astype(jnp.float32)
    return xf * jax.nn.sigmoid(xf)


def causal_conv(x, w, b):
    """``y_t = b + sum_j w[j] x_{t - (K - 1) + j}`` per channel, zeros
    before the start; x [B, T, C], w [K, C], b [C]."""
    K, T = w.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    return (b.astype(jnp.float32) + sum(
        w[j].astype(jnp.float32) * padded[:, j:j + T] for j in range(K))
            ).astype(x.dtype)


def ssd(x, dt, A, B, C, D):
    """The Mamba-2 recurrence, one step at a time, in float32: x [Bt, T, H,
    P], dt [Bt, T, H], A and D [H], B and C [Bt, T, G, N] -> y [Bt, T, H,
    P].  Products as sums of elementwise products: no matmul precision to
    set."""
    Bt, T, H, P = x.shape
    G, N = B.shape[2:]
    f32 = jnp.float32
    # head h = g H/G + j reads group g: [G, H/G] heads, B and C read by
    # broadcasting over j
    A, D = (t.astype(f32).reshape(G, -1) for t in (A, D))
    x, dt = x.reshape(Bt, T, G, -1, P), dt.reshape(Bt, T, G, -1)

    def step(S, xs):
        x_t, dt_t, b_t, c_t = xs
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + b_t[:, :, None, :, None] * (dt_t[..., None] * x_t)[
                 ..., None, :])
        return S, (jnp.sum(S * c_t[:, :, None, :, None], axis=-2)
                   + D[..., None] * x_t)

    block = STEP_BLOCK if T % STEP_BLOCK == 0 else T
    blocks = tuple(
        jnp.moveaxis(t.astype(f32), 1, 0).reshape(
            T // block, block, Bt, *t.shape[2:])
        for t in (x, dt, B, C))
    S0 = jnp.zeros((Bt, G, H // G, N, P), f32)
    _, y = jax.lax.scan(jax.checkpoint(
        lambda S, xs: jax.lax.scan(step, S, xs)), S0, blocks)
    return jnp.moveaxis(y.reshape(T, Bt, H, P), 0, 1)


def mamba2(u, p, heads, head_dim, state, groups, eps, bits):
    Bt, T, _ = u.shape
    E = heads * head_dim
    proj = matmul(u, p["in_proj"], bits)
    z, xbc, dt = proj[..., :E], proj[..., E:-heads], proj[..., -heads:]
    xbc = silu(causal_conv(xbc, p["conv"], p["conv_b"])).astype(u.dtype)
    x = xbc[..., :E].reshape(Bt, T, heads, head_dim)
    B = xbc[..., E:E + groups * state].reshape(Bt, T, groups, state)
    C = xbc[..., E + groups * state:].reshape(Bt, T, groups, state)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    y = ssd(rounded(x, bits), dt, -jnp.exp(p["A_log"].astype(jnp.float32)),
            rounded(B, bits), rounded(C, bits), p["D"])
    gated = (y * silu(z).reshape(y.shape)).reshape(Bt, T, groups, -1)
    y = norm(gated, p["norm_g"].reshape(groups, -1), eps)
    return matmul(y.reshape(Bt, T, E).astype(u.dtype), p["out_proj"], bits)


def attention(u, p, heads, head_dim, scale, bits):
    """``heads`` = (n, n_kv).  Dense causal softmax per head, in blocks of
    ``QUERY_BLOCK`` queries."""
    Bt, T, _ = u.shape
    n, kv = heads
    d, group = head_dim, n // kv
    q = matmul(u, p["wq"], bits).reshape(Bt, T, kv, group, d)
    k = matmul(u, p["wk"], bits).reshape(Bt, T, kv, d)
    v = matmul(u, p["wv"], bits).reshape(Bt, T, kv, d)
    block = min(T, QUERY_BLOCK)
    key_pos = jnp.arange(T)

    @jax.checkpoint
    def one_block(start, q, k, v):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        seen = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        scores = scale * jnp.einsum("bqjgd,bsjd->bjgqs", rounded(qb, bits),
                                    rounded(k, bits),
                                    preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf),
                               axis=-1).astype(u.dtype)
        return jnp.einsum("bjgqs,bsjd->bqjgd", rounded(probs, bits),
                          rounded(v, bits),
                          preferred_element_type=jnp.float32)

    o = jax.lax.map(lambda start: one_block(start, q, k, v),
                    jnp.arange(0, T, block))              # [nb, B, block, ..]
    o = jnp.moveaxis(o, 0, 1).reshape(Bt, T, n * d).astype(u.dtype)
    return matmul(o, p["wo"], bits)


def mlp(u, p, bits):
    """``(silu(a) * b) W_out``, ``[a | b] = u W_in``."""
    ab = matmul(u, p["mlp_in"], bits)
    a, b = jnp.split(ab, 2, axis=-1)
    return matmul((silu(a) * b).astype(u.dtype), p["mlp_out"], bits)


def loss(params, batch, *, kinds, heads, head_dim, ssm, multipliers, eps,
         dtype=jnp.float32, operand_bits=None):
    """The mean next-token cross-entropy of ``batch`` = (tokens, labels),
    both [B, T] (negative labels are left out), over the rows of
    ``params["embed"]``.  ``kinds``: the mixer of every layer (``mamba2`` /
    ``attention``); ``heads`` (n, n_kv); ``ssm`` (H, P, N, G);
    ``multipliers`` (embedding, residual, attention, logits): ``m_e``,
    ``m_r``, ``m_a`` and ``m_l`` of the equations."""
    tokens, labels = batch
    bits = operand_bits
    m_e, m_r, m_a, m_l = multipliers
    params = jax.tree_util.tree_map(lambda w: w.astype(dtype), params)

    def layer(x, p, kind):
        u = norm(x, p["norm1_w"], eps)
        if kind == "mamba2":
            a = mamba2(u, p, *ssm, eps, bits)
        elif kind == "attention":
            a = attention(u, p, heads, head_dim, m_a, bits)
        else:
            raise ValueError(f"unknown kind of layer {kind!r}")
        x = x + m_r * a
        return x + m_r * mlp(norm(x, p["norm2_w"], eps), p, bits)

    @jax.checkpoint
    def head_block(xb, lb, table):
        """(sum of the labelled positions' log-probabilities, their number)
        of one block of positions."""
        logits = jnp.matmul(rounded(xb, bits), rounded(table.T, bits),
                            preferred_element_type=jnp.float32) / m_l
        log_probs = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            log_probs, jnp.maximum(lb, 0)[..., None], axis=-1)[..., 0]
        keep = (lb >= 0).astype(jnp.float32)
        return jnp.sum(picked * keep), jnp.sum(keep)

    with jax.default_matmul_precision("highest"):
        x = m_e * params["embed"][tokens]
        for kind, p in zip(kinds, params["layers"], strict=True):
            x = jax.checkpoint(functools.partial(layer, kind=kind))(x, p)
        x = norm(x, params["norm_w"], eps)
        B, T = labels.shape
        block = HEAD_BLOCK if T % HEAD_BLOCK == 0 else T
        picked, kept = jax.lax.map(
            lambda xl: head_block(*xl, params["embed"]),
            (jnp.moveaxis(x.reshape(B, T // block, block, -1), 1, 0),
             jnp.moveaxis(labels.reshape(B, T // block, block), 1, 0)))
    return -jnp.sum(picked) / jnp.maximum(jnp.sum(kept), 1.0)
