"""Family ``bert``: masked-LM pretraining of a BERT encoder
(``deepspeed_tpu.models.BertForPreTraining``), masked-positions batches.

A family turns a configuration file and a traffic file into the program's
model, its batches, the plain reference's view of the same weights, and the
operations a token requires.  The configuration file carries the published
``bert_config.json`` keys."""

import numpy as np

from benchmark.families import common
from benchmark.reference import bert as reference

#: the ``--rehearse-cpu`` sizes: they debug the harness and are never a result
TINY = {"num_hidden_layers": 2, "hidden_size": 128, "num_attention_heads": 4,
        "intermediate_size": 512, "vocab_size": 500,
        "assumed": {"vocab_rows_held": 512}}


def tiny(config):
    return common.tiny(config, TINY)


def with_depth(config, layers):
    return {**config, "num_hidden_layers": layers}


def sizes(config):
    return {"layers": config["num_hidden_layers"],
            "hidden": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "ffn": config["intermediate_size"],
            "vocab": config["vocab_size"],
            "vocab_rows": config["assumed"]["vocab_rows_held"]}


def build_model(config, traffic):
    from deepspeed_tpu.models import BertForPreTraining
    sz = sizes(config)
    if traffic["seq"] > config["max_position_embeddings"]:
        raise ValueError(f"seq {traffic['seq']} exceeds the model's "
                         f"{config['max_position_embeddings']} positions")
    return BertForPreTraining(common.transformer_config(
        layers=sz["layers"], hidden=sz["hidden"], heads=sz["heads"],
        ffn=sz["ffn"], vocab_rows=sz["vocab_rows"],
        positions=config["max_position_embeddings"],
        init_std=config["initializer_range"],
        ln_eps=config["assumed"]["layer_norm_eps"],
        pre_ln=False, causal=False))


def make_batch(rng, rows, config, traffic):
    """One masked-positions pretraining batch of ``rows`` full sequences:
    (input_ids, attention_mask, token_type_ids, masked_positions, masked_ids,
    masked_weights).  No padding, so every position counts as a token."""
    seq, n_pred = traffic["seq"], traffic["masked_positions"]
    ids = rng.integers(0, config["vocab_size"], size=(rows, seq),
                       dtype=np.int32)
    # n_pred distinct positions per row, ascending as the data pipeline
    # emits them
    positions = np.sort(np.argsort(rng.random((rows, seq)), axis=1)
                        [:, :n_pred], axis=1).astype(np.int32)
    return (ids, np.ones((rows, seq), np.int32),
            np.zeros((rows, seq), np.int32), positions,
            np.take_along_axis(ids, positions, axis=1),
            np.ones((rows, n_pred), np.float32))


def tokens_per_row(traffic):
    return traffic["seq"]


def flops_per_token(config, traffic):
    return common.train_flops(
        sizes(config), traffic, causal=False,
        labeled_per_seq=traffic["masked_positions"], head_dense=True)


def attention_call(config, traffic):
    return common.attention_call(sizes(config), traffic, causal=False)


def loss_ceiling(config):
    return common.loss_ceiling(sizes(config))


def to_reference(params, config):
    """The program's parameter tree in ``reference.bert``'s layout."""
    return {
        "word": params["wte"], "position": params["wpe"],
        "segment": params["wtt"],
        "emb_ln_g": params["ln_emb_s"], "emb_ln_b": params["ln_emb_b"],
        "layers": common.blocks_to_reference(
            params["blocks"], config["num_attention_heads"],
            {"ln1": "attn_ln", "ln2": "out_ln"}),
        "mlm_w": params["mlm_dense_w"], "mlm_b": params["mlm_dense_b"],
        "mlm_ln_g": params["mlm_ln_s"], "mlm_ln_b": params["mlm_ln_b"],
        "mlm_out_b": params["mlm_bias"],
    }


def reference_loss(params, batch, config, **precision):
    """``reference.bert.loss`` on the program's parameters (jit-safe)."""
    return reference.loss(
        to_reference(params, config), batch,
        heads=config["num_attention_heads"],
        eps=config["assumed"]["layer_norm_eps"], **precision)
