"""Family ``qwen3_next``: next-token language modelling with Gated DeltaNet
and gated-attention layers over dropless expert layers
(``deepspeed_tpu.models.DeltaMoELM``), held as ONE CHIP'S SHARE of an
expert-parallel deployment.  The configuration file carries the published
``config.json`` keys of Qwen3-Next-80B-A3B-Instruct unchanged; the cuts are
``layers_held`` (the published depths this chip holds: whole periods of
``full_attention_interval`` layers), ``n_routed_held`` with
``first_routed_held`` (its routed experts of every layer; the router keeps
all ``num_experts``) and ``vocab_held``, its rows of the table and of the
head — ids, logits and the loss are over that slice."""

import jax
import jax.numpy as jnp

from benchmark.families import common
# what the two share-of-the-experts families do alike: documents drawn from
# the rows held, a row's tokens, a causal mask's pairs, the loss's ceiling
from benchmark.families.kimi_moe import (_direction, allowed_pairs,  # noqa: F401
                                         loss_ceiling, make_batch,
                                         tokens_per_row)
from benchmark.reference import qwen3_next as reference

#: the ``--rehearse-cpu`` sizes: they debug the harness and are never a
#: result.  One period, 16 experts in 4 shares of 4, top-3.
TINY = {"num_hidden_layers": 8, "layers_held": [0, 1, 2, 3],
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 8, "linear_value_head_dim": 8,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "num_experts": 16, "num_experts_per_tok": 3,
        "n_routed_held": 4, "first_routed_held": 0,
        "vocab_size": 4096, "vocab_held": 512,
        # a CPU step at the cell's 16,384 tokens takes minutes: the
        # rehearsal's batches are cut to this many (make_batch)
        "rehearsal_seq": 128}

#: the cell's rate moves a model this small too little in two steps; at this
#: one the rehearsal's warm-up check can tell a gradient that reaches the
#: optimizer from one that does not
REHEARSAL_LR = 1e-3

def tiny(config):
    out = common.tiny(config, TINY)
    out["job"] = {**config["job"], "optimizer": {
        "type": "Adam", "params": {"lr": REHEARSAL_LR}}}
    return out


def with_depth(config, layers):
    """The first ``layers`` published layers (whole periods)."""
    return {**config, "layers_held": list(range(layers))}


def kind_of(depth, config):
    """``full`` where ``(depth + 1) mod full_attention_interval = 0``,
    ``gdn`` (Gated DeltaNet) otherwise."""
    return ("full" if (depth + 1) % config["full_attention_interval"] == 0
            else "gdn")


def kinds_held(config):
    return [kind_of(i, config) for i in config["layers_held"]]


def segments(config):
    """``DeltaMoEConfig.segments`` of the layers held: whole periods of
    ``full_attention_interval`` layers, from a period's first layer."""
    held, period = config["layers_held"], config["full_attention_interval"]
    if (held != list(range(held[0], held[0] + len(held)))
            or held[0] % period or len(held) % period):
        raise ValueError(f"layers_held {held}: whole periods of {period} "
                         f"consecutive published depths")
    return ((tuple(kinds_held(config)[:period]), len(held) // period),)


def sizes(config):
    rotary = int(config["head_dim"] * config["partial_rotary_factor"])
    return {"hidden": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"], "rotary": rotary,
            "key_heads": config["linear_num_key_heads"],
            "value_heads": config["linear_num_value_heads"],
            "key_dim": config["linear_key_head_dim"],
            "value_dim": config["linear_value_head_dim"],
            "conv": config["linear_conv_kernel_dim"],
            "expert_ffn": config["moe_intermediate_size"],
            "shared_ffn": config["shared_expert_intermediate_size"],
            "experts": config["num_experts"],
            "held": config["n_routed_held"],
            "first": config["first_routed_held"],
            "top_k": config["num_experts_per_tok"],
            "vocab": config["vocab_held"]}


def build_model(config, traffic):
    from deepspeed_tpu.models import DeltaMoEConfig, DeltaMoELM
    sz = sizes(config)
    if traffic["seq"] > config["max_position_embeddings"]:
        raise ValueError(f"seq {traffic['seq']} exceeds the model's "
                         f"{config['max_position_embeddings']} positions")
    if (config["rope_scaling"] or config["tie_word_embeddings"]
            or config["use_sliding_window"] or config["mlp_only_layers"]
            or config["decoder_sparse_step"] != 1
            or config["hidden_act"] != "silu"
            or not config["norm_topk_prob"]):
        raise ValueError(
            "DeltaMoELM has plain rotary positions, an untied head, no "
            "sliding window, an expert layer at every depth, SiLU-gated "
            "experts and gates renormalised over the chosen experts")
    return DeltaMoELM(DeltaMoEConfig(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"],
        num_heads=sz["heads"], num_kv_heads=sz["kv_heads"],
        head_dim=sz["head_dim"], rotary_dim=sz["rotary"],
        rope_theta=float(config["rope_theta"]),
        key_heads=sz["key_heads"], value_heads=sz["value_heads"],
        key_dim=sz["key_dim"], value_dim=sz["value_dim"],
        conv_kernel=sz["conv"], expert_ffn_size=sz["expert_ffn"],
        shared_ffn_size=sz["shared_ffn"], num_experts=sz["experts"],
        experts_per_token=sz["top_k"],
        experts_held=(sz["first"], sz["held"]),
        balance_alpha=config["assumed"]["router_aux_loss_coef"],
        segments=segments(config), norm_eps=config["rms_norm_eps"],
        init_std=config["assumed"]["initializer_range"]))


def matmul_parameters(config):
    """Matmul parameters by part: ``gdn`` (a DeltaNet mixer's three
    projections: ``in_proj_qkvz``, ``in_proj_ba``, ``out_proj``), ``full``
    (a gated-attention mixer's four), ``expert`` (ONE routed expert),
    ``shared`` (the shared expert), ``router`` (with the shared expert's
    gate, ``hidden`` more)."""
    sz = sizes(config)
    h = sz["hidden"]
    keys, values = (sz["key_heads"] * sz["key_dim"],
                    sz["value_heads"] * sz["value_dim"])
    n_d = sz["heads"] * sz["head_dim"]
    return {"gdn": (h * (2 * keys + 2 * values) + h * 2 * sz["value_heads"]
                    + values * h),
            "full": (h * 2 * n_d + 2 * h * sz["kv_heads"] * sz["head_dim"]
                     + n_d * h),
            "expert": 3 * h * sz["expert_ffn"],
            "shared": 3 * h * sz["shared_ffn"],
            "router": h * sz["experts"] + h}


def parameters(config, vocab_rows=None, experts=None):
    """All parameters of the layers held with ``experts`` routed experts a
    layer (default: those held) and a table and a head of ``vocab_rows``
    rows each (default: the rows held)."""
    sz, mm = sizes(config), matmul_parameters(config)
    kinds = kinds_held(config)
    e = sz["held"] if experts is None else experts
    rows = sz["vocab"] if vocab_rows is None else vocab_rows
    moe = 2 * sz["hidden"] + e * mm["expert"] + mm["shared"] + mm["router"]
    # the convolution, A_log, dt_bias and the output norm's scale
    gdn = (mm["gdn"] + sz["conv"] * (2 * sz["key_heads"] * sz["key_dim"]
                                     + sz["value_heads"] * sz["value_dim"])
           + 2 * sz["value_heads"] + sz["value_dim"])
    full = mm["full"] + 2 * sz["head_dim"]             # q_norm, k_norm
    return (kinds.count("gdn") * (gdn + moe) + kinds.count("full")
            * (full + moe) + 2 * rows * sz["hidden"] + sz["hidden"])


def routed_share(config):
    """Expert applications a token needs on THIS chip by expectation:
    ``num_experts_per_tok`` choices, each on a held expert with probability
    held / published (10 * 32 / 512 = 0.625)."""
    sz = sizes(config)
    return sz["top_k"] * sz["held"] / sz["experts"]


def delta_rule_cost(config, traffic, direction):
    """(FLOPs, bytes) the gated delta rule of ONE layer needs on the
    micro-batch, WHATEVER IMPLEMENTS IT: the recurrence itself, not a
    chunked form's extra products.

    Forward, per token and value head: the state's prediction for the key
    (``S^T k``), the rank-one write (``k (x) v'``) and the read (``S^T q``),
    ``2 dk dv`` each; reads q, k (per key head), v, writes o, in the compute
    dtype, reads g and beta in float32.  Backward: thrice the forward's
    products (each product's two gradients, and the state run again beside
    them); reads what the forward read, o's gradient, writes the five
    gradients.  Both directions move the fp32 boundary states a chunked
    implementation keeps, at the published chunk of 64: written forward,
    read backward."""
    _direction(direction)
    sz = sizes(config)
    rows, T, item = traffic["micro_batch"], traffic["seq"], 2
    hk, hv, dk, dv = (sz["key_heads"], sz["value_heads"], sz["key_dim"],
                      sz["value_dim"])
    forward = 3.0 * 2 * dk * dv * hv * rows * T
    qkvo = rows * T * (2 * hk * dk + 2 * hv * dv) * item
    gates = rows * T * 2 * hv * 4
    states = rows * -(-T // 64) * hv * dk * dv * 4
    if direction == "fwd":
        return forward, float(qkvo + gates + states)
    return 3 * forward, float(2 * qkvo + 2 * gates + states)


def gated_attention_cost(config, traffic, direction):
    """(FLOPs, bytes) ONE call of the gated attention's core needs on the
    micro-batch: the causal triangle at the published 256-wide head, 16
    query heads on 2 key/value heads.

    Forward: scores and values, ``2 d`` a pair and query head each; reads q
    (16 heads), k, v (2 heads), writes the output and one fp32 log-sum-exp
    per query and head.  Backward: the scores again, dQ, dK, dP and dV (``5
    x 2 d``); reads q, k, v, the output, its gradient and the log-sum-exp,
    writes dq, dk, dv.  The gate, the norms and the rotation are not the
    core's."""
    _direction(direction)
    sz = sizes(config)
    rows, T, item = traffic["micro_batch"], traffic["seq"], 2
    n, kv, d = sz["heads"], sz["kv_heads"], sz["head_dim"]
    pairs = rows * n * allowed_pairs(T)
    q = rows * T * n * d * item                   # q; the output is as large
    k_v = 2 * rows * T * kv * d * item
    lse = rows * T * n * 4
    if direction == "fwd":
        return 2.0 * pairs * 2 * d, float(2 * q + k_v + lse)
    return 2.0 * pairs * 5 * d, float(4 * q + 2 * k_v + lse)


def expert_matmul_cost(config, traffic, direction):
    """(FLOPs, bytes) the three grouped products of ONE expert layer need on
    the micro-batch, over the rows routed to this chip BY EXPECTATION
    (``rows x seq x routed_share``: 10,240 of 16,384 x 10 pairs at the
    cell's sizes), whatever the routing of a run gives; as
    ``kimi_moe.expert_matmul_cost``."""
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


def flops_per_token(config, traffic):
    """Matmul FLOPs one token of a training step requires ON THIS CHIP,
    forward and backward, with the parts the harness prints:

    * ``gdn``: 6 x the three projections of every DeltaNet mixer.
    * ``delta``: ``delta_rule_cost``'s FLOPs, forward + backward, per
      DeltaNet layer — the recurrence's three products, not the chunked
      form's.
    * ``full``: 6 x the four projections of every gated-attention mixer.
    * ``attention``: its score and value matmuls over the pairs the causal
      mask ALLOWS: ``4 d`` a pair and query head, x 3 with the backward.
    * ``routed``: 6 x one expert x ``routed_share`` per layer — the experts
      held, BY EXPECTATION under the router's published width.
    * ``shared``: 6 x (the shared expert + the router + its gate) per layer.
    * ``head``: the untied vocabulary projection over the rows held.

    Nothing recomputed counts."""
    sz, mm = sizes(config), matmul_parameters(config)
    kinds, seq = kinds_held(config), traffic["seq"]
    n_gdn, n_full = kinds.count("gdn"), kinds.count("full")
    tokens = traffic["micro_batch"] * seq
    parts = {
        "gdn": 6.0 * n_gdn * mm["gdn"],
        "delta": n_gdn * sum(delta_rule_cost(config, traffic, d)[0]
                             for d in ("fwd", "bwd")) / tokens,
        "full": 6.0 * n_full * mm["full"],
        "attention": (3.0 * 4 * sz["head_dim"] * sz["heads"] * n_full
                      * allowed_pairs(seq) / seq),
        "routed": 6.0 * len(kinds) * mm["expert"] * routed_share(config),
        "shared": 6.0 * len(kinds) * (mm["shared"] + mm["router"]),
        "head": 6.0 * sz["hidden"] * sz["vocab"]}
    return {**parts, "total": sum(parts.values())}


def attention_call(config, traffic):
    """The gated attention's core call, as ``attention_plan`` sees it: 16
    query heads of 256 (on 2 key/value heads)."""
    sz = sizes(config)
    return {"rows": traffic["micro_batch"], "seq": traffic["seq"],
            "heads": sz["heads"], "head_dim": sz["head_dim"], "causal": True,
            "itemsize": 2}


def _ungroup(w, sz):
    """A DeltaNet mixer's convolved columns from the program's order — key
    head ``i``'s ``[q_i | k_i | its value heads]`` together, so a
    tensor-parallel split keeps them on one shard — to the reference's ``[q
    | k | v]``, heads contiguous in each (a column permutation)."""
    hk, dk = sz["key_heads"], sz["key_dim"]
    w = w.reshape(*w.shape[:-1], hk, -1)
    return jnp.concatenate(
        [w[..., :dk].reshape(*w.shape[:-2], -1),
         w[..., dk:2 * dk].reshape(*w.shape[:-2], -1),
         w[..., 2 * dk:].reshape(*w.shape[:-2], -1)], axis=-1)


def to_reference(params, config):
    """The program's parameter tree in ``reference.qwen3_next``'s layout:
    the segment's stacked periods unstacked into one dict per layer, the
    DeltaNet projections joined into ``w_qkvz`` / ``w_ba``."""
    sz = sizes(config)
    moe = {"norm1_w": "norm1_s", "norm2_w": "norm2_s", "router": "router_w",
           "e_gate": "exp_gate_w", "e_up": "exp_up_w", "e_down": "exp_down_w",
           "s_gate": "gate_w", "s_up": "up_w", "s_down": "down_w",
           "w_sg": "shared_gate_w"}
    full = {"wq": "q_w", "wk": "k_w", "wv": "v_w", "q_norm_w": "q_norm_s",
            "k_norm_w": "k_norm_s", "wo": "o_w"}
    gdn = {"A_log": "A_log", "dt_bias": "dt_bias", "norm_g": "norm_s",
           "w_out": "out_w"}
    layers = []
    for (kinds, repeats), stacked in zip(segments(config), params["blocks"],
                                         strict=True):
        for r in range(repeats):
            for j, kind in enumerate(kinds):
                ours = stacked[f"l{j}"]
                names = {**moe, **(full if kind == "full" else gdn)}
                layer = {theirs: ours[mine][r]
                         for theirs, mine in names.items()}
                if kind == "gdn":
                    layer["w_qkvz"] = jnp.concatenate(
                        [_ungroup(ours["in_qkv_w"][r], sz),
                         ours["in_z_w"][r]], axis=-1)
                    layer["w_ba"] = jnp.concatenate(
                        [ours["in_b_w"][r], ours["in_a_w"][r]], axis=-1)
                    layer["conv"] = _ungroup(ours["conv_w"][r], sz)
                layers.append(layer)
    return {"embed": params["wte"], "head": params["head"],
            "norm_w": params["normf_s"], "layers": layers}


def reference_parts_of(ref_params, batch, config, **precision):
    """``reference.qwen3_next.loss`` on parameters in ITS layout (jit-safe):
    ``(loss, balance loss, pairs held)`` for this chip's share."""
    sz = sizes(config)
    return reference.loss(
        ref_params, batch, kinds=kinds_held(config),
        attn_heads=(sz["heads"], sz["kv_heads"]), head_dim=sz["head_dim"],
        rotary=sz["rotary"], theta=float(config["rope_theta"]),
        delta_heads=(sz["key_heads"], sz["value_heads"]),
        delta_dims=(sz["key_dim"], sz["value_dim"]),
        experts_per_token=sz["top_k"], held=(sz["first"], sz["held"]),
        coefficient=config["assumed"]["router_aux_loss_coef"],
        eps=config["rms_norm_eps"], **precision)


def reference_parts(params, batch, config, **precision):
    """``reference_parts_of`` on the program's parameters."""
    return reference_parts_of(to_reference(params, config), batch, config,
                              **precision)


def reference_loss(params, batch, config, **precision):
    """The reference's loss alone, as the harness compares it (jit-safe)."""
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
