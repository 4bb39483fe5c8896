"""Family ``kimi_moe``: next-token language modelling with latent attention
and dropless expert layers (``deepspeed_tpu.models.LatentMoELM``), held as
ONE CHIP'S SHARE of an expert-parallel deployment.  The configuration file
carries the published ``config.json`` keys of Kimi-VL-A3B-Instruct's
language model unchanged; the cuts are ``layers_held`` (the published depths
this chip holds: the leading dense layer and the expert layers that follow
it), ``n_routed_held`` with ``first_routed_held`` (its routed experts
of every layer; the router keeps all ``n_routed_experts``) and
``vocab_held``, its rows of the table and of the head — ids, logits and the
loss are over that slice."""

import jax
import numpy as np

from benchmark.families import common
from benchmark.reference import kimi_moe as reference

#: the ``--rehearse-cpu`` sizes: they debug the harness and are never a
#: result.  One dense layer and two expert layers, 16 experts in 4 shares
#: of 4, top-3.
TINY = {"num_hidden_layers": 4, "layers_held": [0, 1, 2],
        "hidden_size": 64, "num_attention_heads": 2,
        "num_key_value_heads": 2, "kv_lora_rank": 32,
        "qk_nope_head_dim": 24, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 160, "moe_intermediate_size": 48,
        "n_routed_experts": 16, "num_experts_per_tok": 3,
        "n_routed_held": 4, "first_routed_held": 0,
        "vocab_size": 4096, "vocab_held": 512,
        # a CPU step at the cell's 2 x 8,192 tokens takes minutes: the
        # rehearsal's batches are cut to this many (make_batch)
        "rehearsal_seq": 128}


#: the cell's rate moves a model this small by 0.06% in two steps; at this
#: one the rehearsal's warm-up check can tell a gradient that reaches the
#: optimizer from one that does not
REHEARSAL_LR = 1e-3


def tiny(config):
    out = common.tiny(config, TINY)
    out["job"] = {**config["job"], "optimizer": {
        "type": "Adam", "params": {"lr": REHEARSAL_LR}}}
    return out


def with_depth(config, layers):
    """The first ``layers`` published layers (the leading dense ones and
    the expert layers that follow)."""
    return {**config, "layers_held": list(range(layers))}


def kind_of(depth, config):
    """``dense`` for the ``first_k_dense_replace`` leading layers, ``moe``
    for every layer after them (``moe_layer_freq`` 1)."""
    return "dense" if depth < config["first_k_dense_replace"] else "moe"


def kinds_held(config):
    return tuple(kind_of(i, config) for i in config["layers_held"])


def segments(config):
    """``LatentMoEConfig.segments`` of the layers held: equal neighbours
    merged into repeats."""
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
            "latent": config["kv_lora_rank"],
            "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"], "v": config["v_head_dim"],
            "qk": config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
            "ffn": config["intermediate_size"],
            "expert_ffn": config["moe_intermediate_size"],
            "experts": config["n_routed_experts"],
            "held": config["n_routed_held"],
            "first": config["first_routed_held"],
            "top_k": config["num_experts_per_tok"],
            "shared": config["n_shared_experts"],
            "vocab": config["vocab_held"]}


def build_model(config, traffic):
    from deepspeed_tpu.models import LatentMoEConfig, LatentMoELM
    sz = sizes(config)
    if traffic["seq"] > config["max_position_embeddings"]:
        raise ValueError(f"seq {traffic['seq']} exceeds the model's "
                         f"{config['max_position_embeddings']} positions")
    if (config["q_lora_rank"] is not None or config["rope_scaling"]
            or config["tie_word_embeddings"] or config["attention_bias"]
            or config["hidden_act"] != "silu"
            or config["scoring_func"] != "sigmoid"
            or config["topk_method"] != "noaux_tc"
            or not config["norm_topk_prob"] or not config["seq_aux"]
            or config["n_group"] != 1 or config["topk_group"] != 1
            or config["moe_layer_freq"] != 1
            or config["num_key_value_heads"] != sz["heads"]):
        raise ValueError(
            "LatentMoELM has uncompressed queries, plain rotary positions, "
            "an untied bias-free head, SiLU-gated experts, a sigmoid router "
            "with a selection-only bias and normalised gates over one group "
            "of experts, a sequence-wise balance loss and an expert layer "
            "at every depth after the dense ones")
    return LatentMoELM(LatentMoEConfig(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"],
        num_heads=sz["heads"], latent_rank=sz["latent"],
        nope_dim=sz["nope"], rope_dim=sz["rope"], v_dim=sz["v"],
        dense_ffn_size=sz["ffn"], expert_ffn_size=sz["expert_ffn"],
        num_experts=sz["experts"], experts_per_token=sz["top_k"],
        shared_experts=sz["shared"],
        experts_held=(sz["first"], sz["held"]),
        route_scale=config["routed_scaling_factor"],
        balance_alpha=config["assumed"]["aux_loss_alpha"],
        segments=segments(config), rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        init_std=config["assumed"]["initializer_range"]))


def make_batch(rng, rows, config, traffic):
    """(tokens, labels): ``rows`` documents of ``seq`` + 1 random tokens from
    the rows of the vocabulary held here, the labels the tokens shifted by
    one, so every position carries a label."""
    seq = config.get("rehearsal_seq", traffic["seq"])
    doc = rng.integers(0, config["vocab_held"], size=(rows, seq + 1),
                       dtype=np.int32)
    return np.ascontiguousarray(doc[:, :-1]), np.ascontiguousarray(doc[:, 1:])


def tokens_per_row(traffic):
    return traffic["seq"]


def matmul_parameters(config):
    """Matmul parameters by part: ``mla`` (the four projections of one
    layer's attention), ``dense`` (the dense layer's MLP), ``expert`` (ONE
    routed expert), ``shared`` (the shared experts), ``router``."""
    sz = sizes(config)
    h, n = sz["hidden"], sz["heads"]
    return {"mla": (h * n * sz["qk"] + h * (sz["latent"] + sz["rope"])
                    + sz["latent"] * n * (sz["nope"] + sz["v"])
                    + n * sz["v"] * h),
            "dense": 3 * h * sz["ffn"],
            "expert": 3 * h * sz["expert_ffn"],
            "shared": 3 * h * sz["shared"] * sz["expert_ffn"],
            "router": h * sz["experts"]}


def parameters(config, vocab_rows=None, experts=None):
    """All parameters of the layers held with ``experts`` routed experts a
    layer (default: those held) and a table and a head of ``vocab_rows``
    rows each (default: the rows held)."""
    sz, mm = sizes(config), matmul_parameters(config)
    kinds = kinds_held(config)
    e = sz["held"] if experts is None else experts
    rows = sz["vocab"] if vocab_rows is None else vocab_rows
    # the two layer norms and the latent's norm
    norms = 2 * sz["hidden"] + sz["latent"]
    dense = mm["mla"] + norms + mm["dense"]
    moe = (mm["mla"] + norms + e * mm["expert"] + mm["shared"]
           + mm["router"] + sz["experts"])
    return (kinds.count("dense") * dense + kinds.count("moe") * moe
            + 2 * rows * sz["hidden"] + sz["hidden"])


def allowed_pairs(seq):
    """(query, key) pairs one head's causal mask allows in a sequence."""
    return seq * (seq + 1) // 2


def routed_share(config):
    """Expert applications a token needs on THIS chip by expectation:
    ``num_experts_per_tok`` choices, each on a held expert with probability
    held / published (6 * 8 / 64 = 0.75)."""
    sz = sizes(config)
    return sz["top_k"] * sz["held"] / sz["experts"]


def flops_per_token(config, traffic):
    """Matmul FLOPs one token of a training step requires ON THIS CHIP,
    forward and backward, with the parts the harness prints:

    * ``mla``: 6 x the four projections of every layer's attention.
    * ``attention``: the score and value matmuls over the pairs the causal
      mask ALLOWS: per pair and head ``2 (d_nope + d_rope)`` (scores) + ``2
      d_v`` (values), x 3 with the backward.
    * ``dense``: 6 x the dense layer's MLP.
    * ``routed``: 6 x one expert x ``routed_share`` per expert layer — the
      experts held, BY EXPECTATION under the router's published width; the
      gather, the sort and the weighted sum are not required work.
    * ``shared``: 6 x (the shared experts + the router) per expert layer.
    * ``head``: the untied vocabulary projection over the rows held.

    Nothing recomputed counts."""
    sz, mm = sizes(config), matmul_parameters(config)
    kinds, seq = kinds_held(config), traffic["seq"]
    n_moe = kinds.count("moe")
    parts = {
        "mla": 6.0 * len(kinds) * mm["mla"],
        "attention": (3.0 * (2 * sz["qk"] + 2 * sz["v"]) * sz["heads"]
                      * len(kinds) * allowed_pairs(seq) / seq),
        "dense": 6.0 * kinds.count("dense") * mm["dense"],
        "routed": 6.0 * n_moe * mm["expert"] * routed_share(config),
        "shared": 6.0 * n_moe * (mm["shared"] + mm["router"]),
        "head": 6.0 * sz["hidden"] * sz["vocab"]}
    return {**parts, "total": sum(parts.values())}


def attention_call(config, traffic):
    """The core's call, as ``attention_plan`` sees it: 16 heads at the
    192-wide query / key head (the value head is 128 wide)."""
    sz = sizes(config)
    return {"rows": traffic["micro_batch"], "seq": traffic["seq"],
            "heads": sz["heads"], "head_dim": sz["qk"], "causal": True,
            "itemsize": 2}


def _direction(direction):
    if direction not in ("fwd", "bwd"):
        raise ValueError(f"direction must be 'fwd' or 'bwd', got "
                         f"{direction!r}")


def latent_attention_cost(config, traffic, direction):
    """(FLOPs, bytes) ONE call of the latent attention's core needs on the
    micro-batch: the causal triangle at a ``d_nope + d_rope`` = 192-wide
    query / key head and a ``d_v`` = 128-wide value head, 16 heads.

    Forward: scores (``2 dqk`` a pair and head) and values (``2 dv``); reads
    q, k, v, writes the output and one fp32 log-sum-exp per query and head.
    Backward: the scores again, dQ and dK (``3 x 2 dqk``), dP and dV (``2 x
    2 dv``); reads q, k, v, the output, its gradient and the log-sum-exp,
    writes dq, dk, dv.  The published 192 whatever the kernel pads to."""
    _direction(direction)
    sz = sizes(config)
    rows, T, item = traffic["micro_batch"], traffic["seq"], 2
    n, dqk, dv = sz["heads"], sz["qk"], sz["v"]
    pairs = rows * n * allowed_pairs(T)
    qk = 2 * rows * T * n * dqk * item            # q and k
    v = rows * T * n * dv * item                  # v; the output is as large
    lse = rows * T * n * 4
    if direction == "fwd":
        return 2.0 * pairs * (dqk + dv), float(qk + 2 * v + lse)
    return 2.0 * pairs * (3 * dqk + 2 * dv), float(2 * (qk + v) + 2 * v + lse)


def expert_matmul_cost(config, traffic, direction):
    """(FLOPs, bytes) the three grouped products of ONE expert layer need on
    the micro-batch, over the rows routed to this chip BY EXPECTATION
    (``rows x seq x routed_share``: 12,288 of 16,384 x 6 pairs at the cell's
    sizes), whatever the routing of a run gives.

    Forward: gate, up and down, ``2 h f`` a row each; reads the rows and the
    held experts' three matrices, writes gate and up, reads their product,
    writes the output rows.  Backward: twice the forward's products (input
    and weight gradients); reads what the forward read and the gradients of
    its outputs, writes the rows' gradient and the three matrices'."""
    _direction(direction)
    sz = sizes(config)
    item = 2
    rows = traffic["micro_batch"] * traffic["seq"] * routed_share(config)
    h, f = sz["hidden"], sz["expert_ffn"]
    weights = 3 * sz["held"] * h * f * item
    acts = rows * (2 * h + 3 * f) * item     # in, out; gate, up, their product
    forward = 2.0 * rows * 3 * h * f
    if direction == "fwd":
        return forward, float(weights + acts)
    return 2 * forward, float(2 * weights + 2 * acts)


def loss_ceiling(config):
    """``common.loss_ceiling`` over the rows of the vocabulary held (the
    balance loss, ~alpha a layer, is far inside the factor 1.5)."""
    return common.loss_ceiling({"vocab_rows": config["vocab_held"]})


def to_reference(params, config):
    """The program's parameter tree in ``reference.kimi_moe``'s layout: the
    segments' stacked layers unstacked into one dict per layer."""
    names = {"norm1_g": "norm1_s", "norm2_g": "norm2_s", "wq": "q_w",
             "wkv_a": "kv_a_w", "kv_norm_g": "kv_norm_s", "wkv_b": "kv_b_w",
             "wo": "o_w", "w_gate": "gate_w", "w_up": "up_w",
             "w_down": "down_w"}
    moe_names = {"router": "router_w", "bias": "router_b",
                 "e_gate": "exp_gate_w", "e_up": "exp_up_w",
                 "e_down": "exp_down_w"}
    layers = []
    for (kinds, repeats), stacked in zip(segments(config), params["blocks"],
                                         strict=True):
        (kind,) = kinds
        both = {**names, **(moe_names if kind == "moe" else {})}
        layers.extend({theirs: stacked["l0"][ours][r]
                       for theirs, ours in both.items()}
                      for r in range(repeats))
    return {"embed": params["wte"], "head": params["head"],
            "norm_g": params["normf_s"], "layers": layers}


def reference_parts_of(ref_params, batch, config, **precision):
    """``reference.kimi_moe.loss`` on parameters in ITS layout (jit-safe):
    ``(loss, balance loss, pairs held)`` for this chip's share."""
    sz = sizes(config)
    return reference.loss(
        ref_params, batch, kinds=kinds_held(config),
        heads=sz["heads"], dims=(sz["nope"], sz["rope"], sz["v"]),
        experts_per_token=sz["top_k"], held=(sz["first"], sz["held"]),
        route_scale=config["routed_scaling_factor"],
        alpha=config["assumed"]["aux_loss_alpha"],
        theta=float(config["rope_theta"]), eps=config["rms_norm_eps"],
        **precision)


def reference_parts(params, batch, config, **precision):
    """``reference_parts_of`` on the program's parameters."""
    return reference_parts_of(to_reference(params, config), batch, config,
                              **precision)


def reference_loss(params, batch, config, **precision):
    """The reference's loss alone, as the harness compares it (jit-safe;
    ``reference_parts`` also gives the balance loss and the pairs that
    landed on the experts held)."""
    return reference_parts(params, batch, config, **precision)[0]


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
        lambda p: reference_parts_of(p, batch, config, **precision)[0])(
            to_reference(params, config))
    betas = hypers.pop("betas", (0.9, 0.999))
    change = reference.first_adam_step(
        grads, lr=hypers.pop("lr"), clip=job.get("gradient_clipping", 0.0),
        beta1=betas[0], beta2=betas[1], **hypers)
    return value, change, grads
