"""Family ``granite_hybrid``: next-token language modelling with Mamba-2
(state-space duality) and position-free attention layers over dense SwiGLU
MLPs, with scaled embedding, residual and logit paths
(``deepspeed_tpu.models.Mamba2HybridLM``).  The configuration file carries
the published ``config.json`` keys of granite-4.0-h-micro unchanged; the
cuts are ``layers_held`` (the published depths this chip holds: whole
periods of ``layer_types``) and ``vocab_held``, its rows of the tied table —
ids, logits and the loss are over that slice.

Readers count the Mamba-2 layers by the program's gauge ``layers_mamba2``,
not by this file's names of the kinds."""

import math

import jax
import jax.numpy as jnp

from benchmark.families import common
# what the LM families that hold a slice of the vocabulary do alike:
# documents drawn from the rows held, a row's tokens, a causal mask's
# pairs, the loss's ceiling
from benchmark.families.kimi_moe import (_direction, allowed_pairs,  # noqa: F401
                                         loss_ceiling, make_batch,
                                         tokens_per_row)
from benchmark.reference import granite_hybrid as reference

#: the published ``layer_types`` names -> the program's kinds
KINDS = {"mamba": "mamba2", "attention": "attention"}

#: the ``--rehearse-cpu`` sizes: they debug the harness and are never a
#: result.  Depths 4-6 of the published layout (Mamba-2, attention,
#: Mamba-2), 8 state-space heads of 16 in one group, 4 query heads on 2.
TINY = {"layers_held": [4, 5, 6], "hidden_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 128, "shared_intermediate_size": 128,
        "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 8,
        "vocab_size": 4096, "vocab_held": 512,
        # a CPU step at the cell's 8,192 tokens takes minutes: the
        # rehearsal's batches are cut to this many (make_batch)
        "rehearsal_seq": 128}

#: the cell's rate moves a model this small too little in two steps; at this
#: one the rehearsal's warm-up check can tell a gradient that reaches the
#: optimizer from one that does not
REHEARSAL_LR = 1e-3

#: the chunk the published model walks its state-space duality in: the
#: roofline's operations are counted at it, whatever chunk the program takes
PUBLISHED_CHUNK_KEY = "mamba_chunk_size"


def tiny(config):
    out = common.tiny(config, TINY)
    out["job"] = {**config["job"], "optimizer": {
        "type": "Adam", "params": {"lr": REHEARSAL_LR}}}
    return out


def with_depth(config, layers):
    """The first ``layers`` published layers."""
    return {**config, "layers_held": list(range(layers))}


def kind_of(depth, config):
    """``mamba2`` where ``layer_types[depth]`` is ``mamba``, ``attention``
    where it is ``attention``."""
    return KINDS[config["layer_types"][depth]]


def kinds_held(config):
    return tuple(kind_of(i, config) for i in config["layers_held"])


def segments(config):
    """``Mamba2HybridConfig.segments`` of the layers held: equal neighbours
    merged into repeats (one scan each)."""
    held = config["layers_held"]
    if held != list(range(held[0], held[0] + len(held))):
        raise ValueError(f"layers_held {held}: consecutive published depths")
    out = []
    for kind in kinds_held(config):
        if out and out[-1][0] == (kind,):
            out[-1] = ((kind,), out[-1][1] + 1)
        else:
            out.append(((kind,), 1))
    return tuple(out)


def sizes(config):
    return {"hidden": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["hidden_size"]
            // config["num_attention_heads"],
            "ffn": config["shared_intermediate_size"],
            "ssm_heads": config["mamba_n_heads"],
            "ssm_head_dim": config["mamba_d_head"],
            "state": config["mamba_d_state"],
            "groups": config["mamba_n_groups"],
            "conv": config["mamba_d_conv"],
            "chunk": config[PUBLISHED_CHUNK_KEY],
            "vocab": config["vocab_held"]}


def multipliers(config):
    """(embedding, residual, attention, logits) of the published paths."""
    return (float(config["embedding_multiplier"]),
            float(config["residual_multiplier"]),
            float(config["attention_multiplier"]),
            float(config["logits_scaling"]))


def build_model(config, traffic):
    from deepspeed_tpu.models import Mamba2HybridConfig, Mamba2HybridLM
    sz = sizes(config)
    if traffic["seq"] > config["max_position_embeddings"]:
        raise ValueError(f"seq {traffic['seq']} exceeds the model's "
                         f"{config['max_position_embeddings']} positions")
    if (config["position_embedding_type"] != "nope"
            or not config["tie_word_embeddings"]
            or config["attention_bias"] or config["mamba_proj_bias"]
            or not config["mamba_conv_bias"]
            or config["hidden_act"] != "silu"
            or config["normalization_function"] != "rmsnorm"
            or config["num_local_experts"] or config["num_experts_per_tok"]
            or config["mamba_expand"] * sz["hidden"]
            != sz["ssm_heads"] * sz["ssm_head_dim"]):
        raise ValueError(
            "Mamba2HybridLM has no positions, a tied head, bias-free "
            "projections, a convolution with a bias, SiLU gates, RMSNorm, "
            "no experts, and mamba_expand x hidden state-space channels")
    embedding, residual, attention, logits = multipliers(config)
    return Mamba2HybridLM(Mamba2HybridConfig(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"], ffn_size=sz["ffn"],
        num_heads=sz["heads"], num_kv_heads=sz["kv_heads"],
        head_dim=sz["head_dim"], ssm_heads=sz["ssm_heads"],
        ssm_head_dim=sz["ssm_head_dim"], ssm_state=sz["state"],
        ssm_groups=sz["groups"], ssm_conv=sz["conv"],
        embedding_multiplier=embedding, residual_multiplier=residual,
        attention_multiplier=attention, logits_scaling=logits,
        segments=segments(config), norm_eps=config["rms_norm_eps"],
        init_std=config["assumed"]["initializer_range"]))


def matmul_parameters(config):
    """Matmul parameters by part: ``mamba2`` (a Mamba-2 mixer's in and out
    projections), ``attention`` (the four projections), ``mlp`` (a layer's
    SwiGLU)."""
    sz = sizes(config)
    h, E = sz["hidden"], sz["ssm_heads"] * sz["ssm_head_dim"]
    n_d, kv_d = sz["heads"] * sz["head_dim"], sz["kv_heads"] * sz["head_dim"]
    in_proj = 2 * E + 2 * sz["groups"] * sz["state"] + sz["ssm_heads"]
    return {"mamba2": h * in_proj + E * h,
            "attention": 2 * h * n_d + 2 * h * kv_d,
            "mlp": 3 * h * sz["ffn"]}


def parameters(config, vocab_rows=None):
    """All parameters of the layers held and a tied table of
    ``vocab_rows`` rows (default: the rows held)."""
    sz, mm = sizes(config), matmul_parameters(config)
    kinds = kinds_held(config)
    E, H = sz["ssm_heads"] * sz["ssm_head_dim"], sz["ssm_heads"]
    channels = E + 2 * sz["groups"] * sz["state"]
    # the convolution with its bias, A_log / D / dt_bias, the gated norm
    mixer = mm["mamba2"] + (sz["conv"] + 1) * channels + 3 * H + E
    layer = mm["mlp"] + 2 * sz["hidden"]
    rows = sz["vocab"] if vocab_rows is None else vocab_rows
    return (kinds.count("mamba2") * (layer + mixer)
            + kinds.count("attention") * (layer + mm["attention"])
            + rows * sz["hidden"] + sz["hidden"])


def ssd_cost(config, traffic, direction):
    """(FLOPs, bytes) the state-space duality of ONE Mamba-2 layer needs on
    the micro-batch, fixed by the configuration: the chunked form's
    products at the PUBLISHED chunk (``mamba_chunk_size`` 256), whatever
    chunk the program walks.

    Forward, per chunk of ``C`` steps: the group's scores ``C B^T`` (``2
    C^2 N`` a group), and per head the masked scores on ``dt x`` (``2 C^2
    P``), the chunk's write into the state and the read from it (``2 C N
    P`` each).  Backward: twice the forward's products (each product's two
    gradients).  Bytes: what the op must read and write in the compute
    dtype — x, B and C in, y out; dt in float32 — forward; backward reads
    those and y's gradient and writes the five gradients.  The states a
    chunked walk keeps are its own choice and not counted."""
    _direction(direction)
    sz = sizes(config)
    rows, T, item = traffic["micro_batch"], traffic["seq"], 2
    C, N, P = sz["chunk"], sz["state"], sz["ssm_head_dim"]
    H, G = sz["ssm_heads"], sz["groups"]
    tokens = rows * math.ceil(T / C) * C
    forward = tokens * (2.0 * C * N * G + H * (2.0 * C * P + 4.0 * N * P))
    x_y = rows * T * H * P * item              # x; y and their gradients
    b_c = 2 * rows * T * G * N * item
    dt = rows * T * H * 4
    if direction == "fwd":
        return forward, float(2 * x_y + b_c + dt)
    return 2 * forward, float(4 * x_y + 2 * b_c + 2 * dt)


def flops_per_token(config, traffic):
    """Matmul FLOPs one token of a training step requires, forward and
    backward, with the parts the harness prints:

    * ``mamba2``: 6 x every Mamba-2 mixer's in and out projections.
    * ``ssd``: the recurrence's own products, ``4 N P`` a head and token
      (the write ``B (dt x)^T`` and the read ``S^T C``), x 3 with the
      backward — not the chunked form's.
    * ``mlp``: 6 x every layer's SwiGLU.
    * ``projections``: 6 x every attention layer's four projections.
    * ``attention``: its score and value matmuls over the pairs the causal
      mask ALLOWS: ``4 d`` a pair and query head, x 3 with the backward.
    * ``head``: the tied vocabulary projection over the rows held.

    Nothing recomputed counts."""
    sz, mm = sizes(config), matmul_parameters(config)
    kinds, seq = kinds_held(config), traffic["seq"]
    n_mamba, n_attn = kinds.count("mamba2"), kinds.count("attention")
    parts = {
        "mamba2": 6.0 * n_mamba * mm["mamba2"],
        "ssd": 3.0 * n_mamba * 4 * sz["state"] * sz["ssm_head_dim"]
        * sz["ssm_heads"],
        "mlp": 6.0 * len(kinds) * mm["mlp"],
        "projections": 6.0 * n_attn * mm["attention"],
        "attention": (3.0 * 4 * sz["head_dim"] * sz["heads"] * n_attn
                      * allowed_pairs(seq) / seq),
        "head": 6.0 * sz["hidden"] * sz["vocab"]}
    return {**parts, "total": sum(parts.values())}


def attention_call(config, traffic):
    """The attention layer's core call, as ``attention_plan`` sees it: 32
    query heads of 64 (on 8 key/value heads)."""
    sz = sizes(config)
    return {"rows": traffic["micro_batch"], "seq": traffic["seq"],
            "heads": sz["heads"], "head_dim": sz["head_dim"], "causal": True,
            "itemsize": 2}


def to_reference(params, config):
    """The program's parameter tree in ``reference.granite_hybrid``'s
    layout: the segments' stacked layers unstacked into one dict per layer,
    the SwiGLU's gate and up joined into ``mlp_in`` = ``[a | b]``."""
    common_names = {"norm1_w": "norm1_s", "norm2_w": "norm2_s",
                    "mlp_out": "down_w"}
    mixer = {
        "mamba2": {"in_proj": "in_w", "conv": "conv_w", "conv_b": "conv_b",
                   "A_log": "A_log", "D": "D", "dt_bias": "dt_bias",
                   "norm_g": "norm_s", "out_proj": "out_w"},
        "attention": {"wq": "q_w", "wk": "k_w", "wv": "v_w", "wo": "o_w"}}
    layers = []
    for (kinds, repeats), stacked in zip(segments(config), params["blocks"],
                                         strict=True):
        for r in range(repeats):
            for j, kind in enumerate(kinds):
                ours = stacked[f"l{j}"]
                names = {**common_names, **mixer[kind]}
                layer = {theirs: ours[mine][r]
                         for theirs, mine in names.items()}
                layer["mlp_in"] = jnp.concatenate(
                    [ours["gate_w"][r], ours["up_w"][r]], axis=-1)
                layers.append(layer)
    return {"embed": params["wte"], "norm_w": params["normf_s"],
            "layers": layers}


def reference_loss_of(ref_params, batch, config, **precision):
    """``reference.granite_hybrid.loss`` on parameters in ITS layout
    (jit-safe)."""
    sz = sizes(config)
    return reference.loss(
        ref_params, batch, kinds=kinds_held(config),
        heads=(sz["heads"], sz["kv_heads"]), head_dim=sz["head_dim"],
        ssm=(sz["ssm_heads"], sz["ssm_head_dim"], sz["state"],
             sz["groups"]),
        multipliers=multipliers(config), eps=config["rms_norm_eps"],
        **precision)


def reference_loss(params, batch, config, **precision):
    """The reference's loss on the program's parameters, as the harness
    compares it (jit-safe)."""
    return reference_loss_of(to_reference(params, config), batch, config,
                             **precision)


def reference_first_update(params, batch, config, **precision):
    """``(loss, change, gradient)``: the reference's loss on ``batch``, what
    the job's FIRST optimizer step on its gradient adds to every parameter
    (``reference.first_adam_step`` under the job's rate and clipping) and
    that gradient, both in the reference's layout (``to_reference``), from
    the program's parameters.  Jit-safe.  The traffic kind
    ``train_steps_update`` holds the engine's first step to it."""
    job = config["job"]
    hypers = dict(job["optimizer"]["params"])
    if job["optimizer"]["type"] != "Adam" or hypers.get("weight_decay"):
        raise ValueError(f"the reference's first step is plain Adam's; the "
                         f"job says {job['optimizer']}")
    value, grads = jax.value_and_grad(
        lambda p: reference_loss_of(p, batch, config, **precision))(
            to_reference(params, config))
    betas = hypers.pop("betas", (0.9, 0.999))
    change = reference.first_adam_step(
        grads, lr=hypers.pop("lr"), clip=job.get("gradient_clipping", 0.0),
        beta1=betas[0], beta2=betas[1], **hypers)
    return value, change, grads
