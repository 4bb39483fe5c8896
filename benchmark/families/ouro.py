"""Family ``ouro``: next-token language modelling with a looped (weight-shared
depth) LM (``deepspeed_tpu.models.LoopedLM``).  The configuration file
carries the published ``config.json`` keys of Ouro (``hidden_size``,
``num_attention_heads``, ``head_dim``, ``intermediate_size``,
``total_ut_steps``, ...) unchanged; the one cut is ``layers_held``, the
layers of the published ``num_hidden_layers`` this chip holds."""

import numpy as np

from benchmark.families import common
from benchmark.reference import ouro as reference

#: the ``--rehearse-cpu`` sizes: they debug the harness and are never a
#: result.  All four passes are kept: the loop is the model.
TINY = {"layers_held": 2, "num_hidden_layers": 2, "hidden_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 32,
        "intermediate_size": 352, "vocab_size": 512,
        # a CPU step at the cell's 4,096 tokens takes 20 s: the rehearsal's
        # batches are cut to this many (make_batch), like every other size
        "rehearsal_seq": 256}


def tiny(config):
    return common.tiny(config, TINY)


def with_depth(config, layers):
    return {**config, "layers_held": layers}


def sizes(config):
    return {"layers": config["layers_held"], "hidden": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "head_dim": config["head_dim"],
            "ffn": config["intermediate_size"],
            "vocab": config["vocab_size"],
            "passes": config["total_ut_steps"]}


def build_model(config, traffic):
    from deepspeed_tpu.models import LoopedConfig, LoopedLM
    sz = sizes(config)
    if traffic["seq"] > config["max_position_embeddings"]:
        raise ValueError(f"seq {traffic['seq']} exceeds the model's "
                         f"{config['max_position_embeddings']} positions")
    if config["num_key_value_heads"] != sz["heads"]:
        raise ValueError("the program's block has one head count for q, k "
                         "and v")
    if config["tie_word_embeddings"] or config["hidden_act"] != "silu":
        raise ValueError("LoopedLM has an untied head and a SiLU-gated FFN")
    return LoopedLM(LoopedConfig(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"],
        num_layers=sz["layers"], num_heads=sz["heads"],
        head_dim=sz["head_dim"], ffn_size=sz["ffn"],
        loop_passes=sz["passes"], norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        exit_entropy_weight=config["assumed"]["exit_entropy_weight"],
        init_std=config["assumed"]["initializer_range"]))


def make_batch(rng, rows, config, traffic):
    """(tokens, labels): ``rows`` documents of ``seq`` + 1 random tokens from
    the whole vocabulary, the labels the tokens shifted by one, so every
    position carries a label."""
    seq = config.get("rehearsal_seq", traffic["seq"])
    doc = rng.integers(0, config["vocab_size"], size=(rows, seq + 1),
                       dtype=np.int32)
    return np.ascontiguousarray(doc[:, :-1]), np.ascontiguousarray(doc[:, 1:])


def tokens_per_row(traffic):
    return traffic["seq"]


def flops_per_token(config, traffic):
    """Matmul FLOPs one token of a training step requires, forward and
    backward, with the parts the harness prints (``benchmark/flops.py``
    counts a two-matrix FFN and one head, so the count is made here):

    * ``body``: 6 x the held layers' matmul parameters (q, k, v, o and the
      gated FFN's three matrices) x the passes: every pass is required work,
      none of it recomputation.
    * ``attention``: score and value matmuls, 12 x seq x heads x head_dim
      per layer application forward and backward, half for the causal mask.
    * ``head``: the untied vocabulary projection, once per exit.

    The exit gate (hidden + 1 parameters) is left out.  Recomputed work does
    not count: nothing here reads the job's recomputation policy."""
    sz = sizes(config)
    width = sz["heads"] * sz["head_dim"]
    layer = 4 * sz["hidden"] * width + 3 * sz["hidden"] * sz["ffn"]
    applications = sz["layers"] * sz["passes"]
    body = 6.0 * layer * applications
    attention = 12.0 * applications * traffic["seq"] * width * 0.5
    head = 6.0 * sz["hidden"] * sz["vocab"] * sz["passes"]
    return {"body": body, "attention": attention, "head": head,
            "total": body + attention + head}


def attention_call(config, traffic):
    sz = sizes(config)
    return {"rows": traffic["micro_batch"], "seq": traffic["seq"],
            "heads": sz["heads"], "head_dim": sz["head_dim"],
            "causal": True, "itemsize": 2}


def loss_ceiling(config):
    """``common.loss_ceiling`` over the whole vocabulary (no padding rows;
    the entropy term only lowers the loss)."""
    return common.loss_ceiling({"vocab_rows": config["vocab_size"]})


def to_reference(params, config):
    """The program's parameter tree in ``reference.ouro``'s layout."""
    b = params["blocks"]
    return {
        "embed": params["wte"], "lm_head": params["head"].T,
        "norm_g": params["normf_s"],
        "gate_w": params["gate_w"], "gate_b": params["gate_b"],
        "layers": {
            "input_norm_g": b["norm1_s"], "attn_out_norm_g": b["norm2_s"],
            "pre_ffn_norm_g": b["norm3_s"], "ffn_out_norm_g": b["norm4_s"],
            "wq": b["q_w"], "wk": b["k_w"], "wv": b["v_w"], "wo": b["o_w"],
            "w_gate": b["gate_w"], "w_up": b["up_w"],
            "w_down": b["down_w"]},
    }


def reference_loss(params, batch, config, **precision):
    """``reference.ouro.loss`` on the program's parameters (jit-safe)."""
    return reference.loss(
        to_reference(params, config), batch,
        passes=config["total_ut_steps"], head_dim=config["head_dim"],
        theta=float(config["rope_theta"]), eps=config["rms_norm_eps"],
        beta=config["assumed"]["exit_entropy_weight"], **precision)
