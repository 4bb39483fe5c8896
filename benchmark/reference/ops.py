"""Building blocks both references share: plain float32 ``jax.numpy``.

``bits`` (matmul operands rounded to that many mantissa bits) exists to price
a precision step against the float32 answer — see ``bert.py``.  Imports
nothing from ``deepspeed_tpu``.
"""

import jax
import jax.numpy as jnp


def round_mantissa(x, bits):
    """``x`` (float32) rounded to nearest at ``bits`` mantissa bits."""
    if bits is None:
        return x
    drop = 23 - bits
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    u = (u + jnp.uint32(1 << (drop - 1))) & jnp.uint32(~((1 << drop) - 1)
                                                       & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(u, jnp.float32).astype(x.dtype)


def matmul(x, w, bits):
    return jnp.matmul(round_mantissa(x, bits), round_mantissa(w, bits),
                      preferred_element_type=jnp.float32).astype(x.dtype)


def layer_norm(x, g, b, eps):
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = jnp.square(xf - mean).mean(-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def gelu(x):
    xf = x.astype(jnp.float32)
    return (0.5 * xf * (1.0 + jnp.tanh(
        0.7978845608028654 * (xf + 0.044715 * xf ** 3)))).astype(x.dtype)


def attention(x, p, key_bias, heads, bits, causal=False):
    """Multi-head self-attention on x [B, T, h]; ``key_bias`` [B, 1, 1, T] is
    added to the scores (0 to attend, -10000 on padding)."""
    B, T, h = x.shape
    d = h // heads

    def split(w, b):
        return (matmul(x, w, bits) + b).reshape(B, T, heads, d)

    q, k, v = split(p["wq"], p["bq"]), split(p["wk"], p["bk"]), \
        split(p["wv"], p["bv"])
    scores = jnp.einsum("bqnd,bknd->bnqk", round_mantissa(q, bits),
                        round_mantissa(k, bits),
                        preferred_element_type=jnp.float32) / jnp.sqrt(
                            jnp.float32(d))
    scores = scores + key_bias
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores, -1e10)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bnqk,bknd->bqnd", round_mantissa(probs, bits),
                     round_mantissa(v, bits),
                     preferred_element_type=jnp.float32).astype(x.dtype)
    return matmul(ctx.reshape(B, T, h), p["wo"], bits) + p["bo"]
