"""BERT masked-LM pretraining loss in plain float32 ``jax.numpy``.

Written from the published description (Devlin et al. 2018 and
google-research/bert ``modeling.py`` / ``run_pretraining.py``), not from the
program: post-LayerNorm encoder blocks, learned position and segment
embeddings, tanh-approximated GELU (``modeling.gelu``), an additive -10000
mask on padded keys, and the masked-LM head — transform, GELU, LayerNorm,
decoder tied to the word embeddings plus an output bias — over the gathered
masked positions, averaged with ``sum(w · loss) / (sum(w) + 1e-5)``.
Departures from the publication: no dropout (the program has none) and no
next-sentence head (the cells train MLM only).  Imports nothing from
``deepspeed_tpu``.

Parameters (``L`` layers stacked on the leading axis, heads contiguous in the
output columns of ``wq``/``wk``/``wv``):

    word [V, h]  position [P, h]  segment [2, h]  emb_ln_g/emb_ln_b [h]
    layers: wq wk wv wo [L, h, h]  bq bk bv bo [L, h]  attn_ln_g/_b [L, h]
            w_in [L, h, f]  b_in [L, f]  w_out [L, f, h]  b_out [L, h]
            out_ln_g/_b [L, h]
    mlm_w [h, h]  mlm_b [h]  mlm_ln_g/mlm_ln_b [h]  mlm_out_b [V]

``dtype`` and ``operand_bits`` exist to price a precision step against the
float32 answer (PERF.md): ``dtype`` stores parameters and activations in a
lower type, ``operand_bits`` rounds every matmul operand to that many
mantissa bits (7 is bfloat16's, 3 an fp8 e4m3's).  The reference proper is
the default: float32 throughout, matmuls at ``highest`` precision.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.ops import (attention, gelu, layer_norm, matmul,
                                     round_mantissa)


def encode(params, input_ids, attention_mask, token_type_ids, *, heads, eps,
           bits):
    T = input_ids.shape[1]
    x = (params["word"][input_ids] + params["position"][:T][None]
         + params["segment"][token_type_ids])
    x = layer_norm(x, params["emb_ln_g"], params["emb_ln_b"], eps)
    key_bias = (1.0 - attention_mask.astype(jnp.float32))[:, None, None, :] \
        * -10000.0

    def block(x, p):
        x = layer_norm(x + attention(x, p, key_bias, heads, bits),
                       p["attn_ln_g"], p["attn_ln_b"], eps)
        ffn = matmul(gelu(matmul(x, p["w_in"], bits) + p["b_in"]),
                     p["w_out"], bits) + p["b_out"]
        return layer_norm(x + ffn, p["out_ln_g"], p["out_ln_b"], eps), None

    x, _ = jax.lax.scan(block, x, params["layers"])
    return x


def loss(params, batch, *, heads, eps, dtype=jnp.float32, operand_bits=None):
    """Mean masked-LM loss of ``batch`` = (input_ids, attention_mask,
    token_type_ids, masked_positions, masked_ids, masked_weights)."""
    input_ids, attention_mask, token_type_ids, positions, ids, weights = batch
    params = jax.tree_util.tree_map(lambda w: w.astype(dtype), params)
    with jax.default_matmul_precision("highest"):
        x = encode(params, input_ids, attention_mask, token_type_ids,
                   heads=heads, eps=eps, bits=operand_bits)
        x = jnp.take_along_axis(x, positions[..., None], axis=1)   # [B,P,h]
        x = gelu(matmul(x, params["mlm_w"], operand_bits)
                 + params["mlm_b"])
        x = layer_norm(x, params["mlm_ln_g"], params["mlm_ln_b"], eps)
        logits = (jnp.matmul(round_mantissa(x, operand_bits),
                             round_mantissa(params["word"], operand_bits).T,
                             preferred_element_type=jnp.float32)
                  + params["mlm_out_b"].astype(jnp.float32))
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    per_position = -jnp.take_along_axis(log_probs, ids[..., None],
                                        axis=-1)[..., 0]
    w = weights.astype(jnp.float32)
    return jnp.sum(per_position * w) / (jnp.sum(w) + 1e-5)
