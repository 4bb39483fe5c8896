"""Family ``gpt2``: next-token language modelling with GPT-2
(``deepspeed_tpu.models.GPT2``).  The configuration file carries the
published ``config.json`` keys (``n_embd``, ``n_head``, ``n_layer``, ...)."""

import numpy as np

from benchmark.families import common
from benchmark.reference import gpt2 as reference

#: the ``--rehearse-cpu`` sizes: they debug the harness and are never a result
TINY = {"n_layer": 2, "n_embd": 128, "n_head": 4, "vocab_size": 500,
        "assumed": {"vocab_rows_held": 512}}


def tiny(config):
    return common.tiny(config, TINY)


def with_depth(config, layers):
    return {**config, "n_layer": layers}


def sizes(config):
    return {"layers": config["n_layer"], "hidden": config["n_embd"],
            "heads": config["n_head"], "ffn": 4 * config["n_embd"],
            "vocab": config["vocab_size"],
            "vocab_rows": config["assumed"]["vocab_rows_held"]}


def build_model(config, traffic):
    from deepspeed_tpu.models import GPT2
    sz = sizes(config)
    if traffic["seq"] > config["n_positions"]:
        raise ValueError(f"seq {traffic['seq']} exceeds the model's "
                         f"{config['n_positions']} positions")
    return GPT2(common.transformer_config(
        layers=sz["layers"], hidden=sz["hidden"], heads=sz["heads"],
        ffn=sz["ffn"], vocab_rows=sz["vocab_rows"],
        positions=config["n_positions"],
        init_std=config["initializer_range"],
        ln_eps=config["layer_norm_epsilon"], pre_ln=True, causal=True))


def make_batch(rng, rows, config, traffic):
    """(tokens, labels): ``rows`` documents of ``seq`` + 1 random tokens, the
    labels the tokens shifted by one, so every position carries a label."""
    doc = rng.integers(0, config["vocab_size"],
                       size=(rows, traffic["seq"] + 1), dtype=np.int32)
    return np.ascontiguousarray(doc[:, :-1]), np.ascontiguousarray(doc[:, 1:])


def tokens_per_row(traffic):
    return traffic["seq"]


def flops_per_token(config, traffic):
    return common.train_flops(
        sizes(config), traffic, causal=True,
        labeled_per_seq=traffic["seq"], head_dense=False)


def attention_call(config, traffic):
    return common.attention_call(sizes(config), traffic, causal=True)


def loss_ceiling(config):
    return common.loss_ceiling(sizes(config))


def to_reference(params, config):
    """The program's parameter tree in ``reference.gpt2``'s layout."""
    return {
        "wte": params["wte"], "wpe": params["wpe"],
        "ln_f_g": params["lnf_s"], "ln_f_b": params["lnf_b"],
        "layers": common.blocks_to_reference(
            params["blocks"], config["n_head"],
            {"ln1": "ln_1", "ln2": "ln_2"}),
    }


def reference_loss(params, batch, config, **precision):
    """``reference.gpt2.loss`` on the program's parameters (jit-safe)."""
    return reference.loss(to_reference(params, config), batch,
                          heads=config["n_head"],
                          eps=config["layer_norm_epsilon"], **precision)
