"""GPT-2 language-model loss in plain float32 ``jax.numpy``.

Written from the published description (Radford et al. 2019 and openai/gpt-2
``src/model.py``), not from the program: learned token and position
embeddings, pre-LayerNorm blocks (``x + attn(ln_1(x))``, ``x + mlp(ln_2(x))``),
causal attention with masked scores set to -1e10, tanh-approximated GELU, a
final LayerNorm and an output projection tied to the token embeddings; the
loss is the mean next-token cross-entropy over every labelled position.
Departure from the publication: no dropout (the program has none).  Imports
nothing from ``deepspeed_tpu``.

Parameters (``L`` layers stacked on the leading axis, heads contiguous in the
output columns of ``wq``/``wk``/``wv`` — the three blocks of the published
``c_attn``):

    wte [V, h]  wpe [P, h]  ln_f_g/ln_f_b [h]
    layers: ln_1_g/_b [L, h]  wq wk wv wo [L, h, h]  bq bk bv bo [L, h]
            ln_2_g/_b [L, h]  w_in [L, h, f]  b_in [L, f]
            w_out [L, f, h]  b_out [L, h]

``dtype`` and ``operand_bits``: see ``bert.py``.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.ops import (attention, gelu, layer_norm, matmul,
                                     round_mantissa)


def loss(params, batch, *, heads, eps, dtype=jnp.float32, operand_bits=None):
    """Mean cross-entropy of ``batch`` = (tokens, labels), both [B, T];
    positions with a negative label are left out."""
    tokens, labels = batch
    bits = operand_bits
    params = jax.tree_util.tree_map(lambda w: w.astype(dtype), params)
    T = tokens.shape[1]
    with jax.default_matmul_precision("highest"):
        x = params["wte"][tokens] + params["wpe"][:T][None]

        def block(x, p):
            x = x + attention(layer_norm(x, p["ln_1_g"], p["ln_1_b"], eps),
                              p, 0.0, heads, bits, causal=True)
            y = layer_norm(x, p["ln_2_g"], p["ln_2_b"], eps)
            y = matmul(gelu(matmul(y, p["w_in"], bits) + p["b_in"]),
                       p["w_out"], bits) + p["b_out"]
            return x + y, None

        x, _ = jax.lax.scan(block, x, params["layers"])
        x = layer_norm(x, params["ln_f_g"], params["ln_f_b"], eps)
        logits = jnp.matmul(round_mantissa(x, bits),
                            round_mantissa(params["wte"], bits).T,
                            preferred_element_type=jnp.float32)
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    per_position = -jnp.take_along_axis(
        log_probs, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    keep = (labels >= 0).astype(jnp.float32)
    return jnp.sum(per_position * keep) / jnp.maximum(jnp.sum(keep), 1.0)
