"""What the dense-transformer families (``bert``, ``gpt2``) share: both run
on ``deepspeed_tpu.models.transformer``'s block stack, so they build its
configuration and unpack its parameters the same way."""

import math

from benchmark import flops


def tiny(config, overrides):
    """``config`` at a family's ``--rehearse-cpu`` sizes (``assumed`` merges
    key by key)."""
    out = {**config, **overrides}
    out["assumed"] = {**config.get("assumed", {}),
                      **overrides.get("assumed", {})}
    return out


def transformer_config(*, layers, hidden, heads, ffn, vocab_rows, positions,
                       init_std, ln_eps, pre_ln, causal):
    from deepspeed_tpu.models.transformer import TransformerConfig
    if ffn % hidden:
        raise ValueError(f"FFN width {ffn} is not a multiple of the hidden "
                         f"size {hidden}: the program's block takes a ratio")
    return TransformerConfig(
        vocab_size=vocab_rows, max_seq_len=positions, hidden_size=hidden,
        num_layers=layers, num_heads=heads, mlp_ratio=ffn // hidden,
        pre_ln=pre_ln, causal=causal, init_std=init_std, ln_eps=ln_eps)


def blocks_to_reference(blocks, heads, ln_names):
    """The program's stacked block parameters in the references' layout.

    The program packs QKV head-major — output column ``(head, q|k|v, dim)``
    — so that a tensor-parallel split keeps a head's q, k and v together;
    the references take ``wq``/``wk``/``wv`` apart, heads contiguous.
    ``ln_names`` maps the program's ``ln1``/``ln2`` to the reference's names
    (post-LN BERT and pre-LN GPT-2 call them differently)."""
    L, h, _ = blocks["qkv_w"].shape
    d = h // heads
    w = blocks["qkv_w"].reshape(L, h, heads, 3, d)
    b = blocks["qkv_b"].reshape(L, heads, 3, d)
    out = {"wo": blocks["proj_w"], "bo": blocks["proj_b"],
           "w_in": blocks["fc_w"], "b_in": blocks["fc_b"],
           "w_out": blocks["fc2_w"], "b_out": blocks["fc2_b"]}
    for i, name in enumerate("qkv"):
        out["w" + name] = w[:, :, :, i, :].reshape(L, h, h)
        out["b" + name] = b[:, :, i, :].reshape(L, h)
    for ours, theirs in ln_names.items():
        out[theirs + "_g"] = blocks[ours + "_s"]
        out[theirs + "_b"] = blocks[ours + "_b"]
    return out


def attention_call(sizes, traffic, causal):
    """Shapes of one attention call of the cell's step, for
    ``flops.attention_kernel_cost``."""
    return {"rows": traffic["micro_batch"], "seq": traffic["seq"],
            "heads": sizes["heads"],
            "head_dim": sizes["hidden"] // sizes["heads"],
            "causal": causal, "itemsize": 2}


def loss_ceiling(sizes):
    """A loss above this is a run gone wrong: 1.5 x the loss of a uniform
    guess over the vocabulary."""
    return 1.5 * math.log(sizes["vocab_rows"])


def train_flops(sizes, traffic, *, causal, labeled_per_seq, head_dense):
    return flops.train_flops_per_token(
        layers=sizes["layers"], hidden=sizes["hidden"], ffn=sizes["ffn"],
        seq=traffic["seq"], vocab=sizes["vocab"], causal=causal,
        labeled_per_seq=labeled_per_seq, head_dense=head_dense)
