"""Phi-4-mini-flash-reasoning (SambaY decoder-hybrid-decoder) training loss in
plain float32 ``jax.numpy``.

Written from the model's published description (``config.json`` of
microsoft/Phi-4-mini-flash-reasoning, ``model_type`` ``phi4flash``; the
SambaY paper, arXiv:2507.06607; Mamba, arXiv:2312.00752; Differential
Transformer, arXiv:2410.05258), not from the program.  Imports nothing from
``deepspeed_tpu``.

The equations, layer ``i`` of 32 by its mixer:

* Every layer: ``x += mixer(LN(x)); x += MLP(LN(x))``, LayerNorm with bias
  (eps 1e-5), ``MLP(x) = (silu(x Wg) * (x Wu)) Wd``, no bias.  Token
  embedding only — NO positional encoding of any kind — a final LayerNorm,
  the head tied to the embedding.
* ``mamba`` (``i`` even, ``i <= 16``): ``E = 2 h``, ``N = 16``, ``R =
  ceil(h / 16)``, convolution width 4.  ``[u, z] = x W_in``; ``u =
  silu(conv1d_causal_depthwise(u, w_c) + b_c)``; ``[r, B, C] = u W_x``;
  ``delta = softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``; ``h_t =
  exp(delta_t * A) * h_{t-1} + (delta_t * u_t) (x) B_t``, ``h_0 = 0``;
  ``y_t = h_t . C_t + D * u_t``; ``out = (y * silu(z)) W_out``.  The
  recurrence runs in float32 whatever ``dtype``.  Layer 16's ``y`` (before
  the gate and ``W_out``) is the memory ``m`` every GMU reads.
* ``swa`` (``i`` odd, ``i < 16``): differential attention, causal, key ``s``
  visible to query ``t`` iff ``t - 512 < s <= t``.
* ``full`` (``i`` = 17): differential attention, causal over the whole
  sequence; its K and V are what every cross layer reads.
* ``gmu`` (``i`` even, ``i >= 18``): ``out = (m * silu(x W_1)) W_2``.
* ``cross`` (``i`` odd, ``i >= 19``): ``q = x W_q``, keys and values are
  layer 17's, causal; ``W_q`` and ``W_o`` only (and its own lambdas).
* Differential attention: 40 query heads and 20 key heads of 64 are 20
  pairs of query heads over 10 pairs of key/value heads.  Pair ``p``,
  sub-head ``s``: ``P_s = softmax(q_{p,s} k_{p//2,s}^T / 8 + mask)``; ``o_p
  = (P_1 - lambda P_2) [v_{p//2,1} | v_{p//2,2}]`` (128 wide); ``lambda =
  exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(i)``, ``lambda_init(i) =
  0.8 - 0.6 exp(-0.3 i)``; ``o_p <- RMSNorm_128(o_p) * (1 -
  lambda_init(i))`` with a learned scale; concat 20 x 128 -> ``W_o``.

Departures and assumptions (the configuration file lists them under
``assumed``): ``config.json`` has no key for differential attention, for
the Mamba sizes, or for which layer is of which kind — they follow the
papers above and ``mb_per_layer`` 2; no dropout; the loss is the mean
cross-entropy over the rows of the vocabulary that ``embed`` holds (a
chip's slice of the table is a smaller vocabulary).

Parameters, one dict per layer in ``params["layers"]`` (``kinds[i]`` names
its mixer), heads contiguous in the output columns:

    embed [V, h]  norm_g norm_b [h]
    every layer: ln1_g ln1_b ln2_g ln2_b [h]  w_gate w_up [h, f]  w_down [f, h]
    mamba:  in_proj [h, 2E] (u | z)  conv_w [E, 4]  conv_b [E]
            x_proj [E, R + 2N] (r | B | C)  dt_w [R, E]  dt_b [E]
            A_log [E, N]  D [E]  out_proj [E, h]
    swa / full: wq [h, 20*2*64] (pair, sub-head)  wk [h, 10*2*64]
            wv [h, 10*128]  wo [20*128, h]
            lq1 lk1 lq2 lk2 [64]  subln_g [128]
    cross:  wq wo lq1 lk1 lq2 lk2 subln_g
    gmu:    w1 [h, E]  w2 [E, h]

``dtype`` and ``operand_bits``: see ``bert.py`` (parameters and activations
stored in ``dtype``; matmul operands rounded to ``operand_bits`` mantissa
bits) — they price a precision step, they are not the reference.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.ops import layer_norm, matmul, round_mantissa

#: queries per block of the dense masked softmax (40 heads x 256 x 8192
#: float32 scores are 336 MB)
QUERY_BLOCK = 256


def silu(x):
    xf = x.astype(jnp.float32)
    return (xf * jax.nn.sigmoid(xf)).astype(x.dtype)


def swiglu(u, p, bits):
    return matmul(silu(matmul(u, p["w_gate"], bits))
                  * matmul(u, p["w_up"], bits), p["w_down"], bits)


def causal_conv(u, w, b):
    """``y_t = b + sum_k w[:, k] u_{t-3+k}``: a plain shifted sum."""
    T, K = u.shape[1], w.shape[1]
    padded = jnp.pad(u.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(w[:, k].astype(jnp.float32) * padded[:, k:k + T]
            for k in range(K))
    return (y + b.astype(jnp.float32)).astype(u.dtype)


def recurrence(u, delta, A, B, C, D):
    """The selective scan, step by step in float32: [rows, T, E] inputs,
    ``B``/``C`` [rows, T, N] -> ``y`` [rows, T, E] float32."""
    u, delta, B, C = (x.astype(jnp.float32) for x in (u, delta, B, C))

    def step(h, xs):
        u_t, d_t, b_t, c_t = xs
        h = (jnp.exp(d_t[:, :, None] * A[None]) * h
             + (d_t * u_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.einsum("ren,rn->re", h, c_t) + D * u_t

    h0 = jnp.zeros((u.shape[0], u.shape[2], A.shape[1]), jnp.float32)
    _, y = jax.lax.scan(step, h0, tuple(jnp.moveaxis(x, 1, 0)
                                        for x in (u, delta, B, C)))
    return jnp.moveaxis(y, 0, 1)


def mamba(x, p, bits):
    """Returns ``(out, y)``: ``y`` is the scan's output before the gate."""
    E, N = p["A_log"].shape
    uz = matmul(x, p["in_proj"], bits)
    u, z = uz[..., :E], uz[..., E:]
    u = silu(causal_conv(u, p["conv_w"], p["conv_b"]))
    rbc = matmul(u, p["x_proj"], bits)
    R = rbc.shape[-1] - 2 * N
    r, B, C = rbc[..., :R], rbc[..., R:R + N], rbc[..., R + N:]
    delta = jax.nn.softplus(
        (matmul(r, p["dt_w"], bits) + p["dt_b"]).astype(jnp.float32)
    ).astype(x.dtype)
    y = recurrence(u, delta, -jnp.exp(p["A_log"].astype(jnp.float32)), B, C,
                   p["D"].astype(jnp.float32)).astype(x.dtype)
    return matmul(y * silu(z), p["out_proj"], bits), y


def lambda_init(depth):
    return 0.8 - 0.6 * jnp.exp(-0.3 * depth)


def differential(q, k, v, p, depth, window, eps, bits):
    """q [B, T, pairs, 2, d], k [B, T, kv pairs, 2, d], v [B, T, kv pairs,
    2d] -> [B, T, pairs * 2d]: dense masked softmax per head, in blocks of
    ``QUERY_BLOCK`` queries."""
    B, T, pairs, _, d = q.shape
    group = pairs // k.shape[2]
    k = jnp.repeat(k, group, axis=2)                  # pair p reads p // group
    v = jnp.repeat(v, group, axis=2)
    f32 = lambda a: p[a].astype(jnp.float32)
    lam = (jnp.exp(jnp.sum(f32("lq1") * f32("lk1")))
           - jnp.exp(jnp.sum(f32("lq2") * f32("lk2"))) + lambda_init(depth))
    block = min(T, QUERY_BLOCK)
    key_pos = jnp.arange(T)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        query_pos = start + jnp.arange(block)
        seen = key_pos[None, :] <= query_pos[:, None]
        if window is not None:
            seen &= key_pos[None, :] > query_pos[:, None] - window
        scores = jnp.einsum("bqpsd,bkpsd->bpsqk", round_mantissa(qb, bits),
                            round_mantissa(k, bits),
                            preferred_element_type=jnp.float32) / jnp.sqrt(
                                jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        diff = (probs[:, :, 0] - lam * probs[:, :, 1]).astype(q.dtype)
        return jnp.einsum("bpqk,bkpe->bqpe", round_mantissa(diff, bits),
                          round_mantissa(v, bits),
                          preferred_element_type=jnp.float32)

    o = jax.lax.map(one_block, jnp.arange(0, T, block))   # [nb, B, block, ..]
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, pairs, 2 * d)
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = o * f32("subln_g") * (1.0 - lambda_init(depth))
    return o.reshape(B, T, pairs * 2 * d).astype(q.dtype)


def attention(x, p, depth, heads, window, eps, bits, kv=None):
    """Differential attention on its own keys and values, or on ``kv`` =
    another layer's ``(k, v)``.  Returns ``(out, (k, v))``."""
    B, T, _ = x.shape
    pairs, kv_pairs, d = heads
    q = matmul(x, p["wq"], bits).reshape(B, T, pairs, 2, d)
    if kv is None:
        kv = (matmul(x, p["wk"], bits).reshape(B, T, kv_pairs, 2, d),
              matmul(x, p["wv"], bits).reshape(B, T, kv_pairs, 2 * d))
    o = differential(q, *kv, p, depth, window, eps, bits)
    return matmul(o, p["wo"], bits), kv


def hidden_states(params, tokens, *, kinds, first_layer, heads, window, eps,
                  bits):
    """The final hidden states [B, T, h], before the final LayerNorm."""
    x = params["embed"][tokens]
    memory = kv = None
    for i, (kind, p) in enumerate(zip(kinds, params["layers"], strict=True)):
        depth = jnp.float32(first_layer + i)
        u = layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
        if kind == "mamba":
            a, memory = mamba(u, p, bits)       # the LAST Mamba layer's y
        elif kind == "gmu":
            a = matmul(memory * silu(matmul(u, p["w1"], bits)), p["w2"], bits)
        elif kind == "cross":
            a, _ = attention(u, p, depth, heads, None, eps, bits, kv=kv)
        elif kind == "full":
            a, kv = attention(u, p, depth, heads, None, eps, bits)
        elif kind == "swa":
            a, _ = attention(u, p, depth, heads, window, eps, bits)
        else:
            raise ValueError(f"unknown kind of layer {kind!r}")
        x = x + a
        x = x + swiglu(layer_norm(x, p["ln2_g"], p["ln2_b"], eps), p, bits)
    return x


def loss(params, batch, *, kinds, first_layer, heads, window, eps,
         dtype=jnp.float32, operand_bits=None):
    """Mean next-token cross-entropy over the labelled positions of
    ``batch`` = (tokens, labels), both [B, T] (negative labels are left
    out), over the rows of ``params["embed"]``.  ``kinds``: the kind of
    every layer held; ``first_layer``: the published depth of the first;
    ``heads``: (query pairs, key/value pairs, head size)."""
    tokens, labels = batch
    params = jax.tree_util.tree_map(lambda w: w.astype(dtype), params)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, kinds=kinds,
                          first_layer=first_layer, heads=heads, window=window,
                          eps=eps, bits=operand_bits)
        x = layer_norm(x, params["norm_g"], params["norm_b"], eps)
        logits = jnp.matmul(round_mantissa(x, operand_bits),
                            round_mantissa(params["embed"].T, operand_bits),
                            preferred_element_type=jnp.float32)
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(log_probs, jnp.maximum(labels, 0)[..., None],
                                 axis=-1)[..., 0]
    keep = (labels >= 0).astype(jnp.float32)
    return -jnp.sum(picked * keep) / jnp.maximum(jnp.sum(keep), 1.0)
