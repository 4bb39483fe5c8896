"""Family ``phi4flash``: next-token language modelling with a
decoder-hybrid-decoder stack (``deepspeed_tpu.models.HybridLM``): Mamba,
sliding-window, full and cross-decoder attention on shared keys and values,
Gated Memory Units.  The configuration file carries the published
``config.json`` keys of Phi-4-mini-flash-reasoning unchanged; the cuts are
``layers_held``, the published layers this chip holds (a list of depths),
and ``vocab_held``, its rows of the vocabulary — ids, logits and the loss
are over that slice."""


import jax.numpy as jnp
import numpy as np

from benchmark.families import common
from benchmark.reference import phi4flash as reference

#: the ``--rehearse-cpu`` sizes: they debug the harness and are never a
#: result.  Twelve layers lay out like the published 32 (Mamba on the even
#: depths up to the middle, the full layer after it, then GMU / cross), and
#: the six held are the same six kinds in the same order.
TINY = {"num_hidden_layers": 12, "layers_held": [4, 5, 6, 7, 8, 9],
        "hidden_size": 64, "num_attention_heads": 8,
        "num_key_value_heads": 4, "intermediate_size": 128,
        "vocab_size": 4096, "vocab_held": 512, "sliding_window": 16,
        "assumed": {"d_state": 4},
        # a CPU step at the cell's 8,192 tokens takes minutes: the
        # rehearsal's batches are cut to this many (make_batch)
        "rehearsal_seq": 128}


def tiny(config):
    return common.tiny(config, TINY)


def with_depth(config, layers):
    """``layers`` published layers from the first one held on."""
    first = config["layers_held"][0]
    return {**config, "layers_held": list(range(first, first + layers))}


def kind_of(depth, config):
    """The kind of the published layer at ``depth`` (``assumed.layer_kinds``
    of the configuration file): with ``L`` layers and ``mb_per_layer`` 2,
    Mamba on the even depths up to ``L/2``, sliding-window attention on the
    odd ones before it, full attention at ``L/2 + 1``, then GMU on the even
    and cross-decoder attention on the odd depths."""
    half, period = config["num_hidden_layers"] // 2, config["mb_per_layer"]
    if depth <= half:
        return "mamba" if depth % period == 0 else "swa"
    if depth == half + 1:
        return "full"
    return "gmu" if depth % period == 0 else "cross"


def kinds_held(config):
    return tuple(kind_of(i, config) for i in config["layers_held"])


def segments(config):
    """``HybridConfig.segments`` of the layers held: whole periods of
    ``mb_per_layer`` layers, equal neighbours merged into repeats."""
    held, period = config["layers_held"], config["mb_per_layer"]
    if (held != list(range(held[0], held[0] + len(held)))
            or held[0] % period or len(held) % period):
        raise ValueError(f"layers_held {held}: consecutive whole periods of "
                         f"{period} layers")
    kinds, out = kinds_held(config), []
    for i in range(0, len(held), period):
        pair = kinds[i:i + period]
        if out and out[-1][0] == pair:
            out[-1] = (pair, out[-1][1] + 1)
        else:
            out.append((pair, 1))
    return tuple(out)


def sizes(config):
    h, heads = config["hidden_size"], config["num_attention_heads"]
    a = config["assumed"]
    return {"hidden": h, "heads": heads,
            "kv_heads": config["num_key_value_heads"],
            "head_dim": h // heads, "ffn": config["intermediate_size"],
            "vocab": config["vocab_held"], "window": config["sliding_window"],
            "channels": a["expand"] * h, "state": a["d_state"],
            "conv": a["d_conv"], "dt_rank": -(-h // 16)}


def build_model(config, traffic):
    from deepspeed_tpu.models import HybridConfig, HybridLM
    sz = sizes(config)
    if traffic["seq"] > config["max_position_embeddings"]:
        raise ValueError(f"seq {traffic['seq']} exceeds the model's "
                         f"{config['max_position_embeddings']} positions")
    if (not config["tie_word_embeddings"] or config["hidden_act"] != "silu"
            or config["mlp_bias"] or config["lm_head_bias"]
            or config["embd_pdrop"] or config["resid_pdrop"]):
        raise ValueError("HybridLM has a tied, bias-free head, a SiLU-gated "
                         "bias-free MLP and no dropout")
    if config["assumed"]["dt_rank"] != "auto":
        raise ValueError("dt_rank is ceil(hidden_size / 16)")
    return HybridLM(HybridConfig(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"],
        num_heads=sz["heads"], num_kv_heads=sz["kv_heads"],
        head_dim=sz["head_dim"], ffn_size=sz["ffn"], window=sz["window"],
        ssm_state=sz["state"], ssm_conv=sz["conv"],
        ssm_expand=config["assumed"]["expand"], segments=segments(config),
        first_layer=config["layers_held"][0],
        ln_eps=config["layer_norm_eps"],
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
    """Matmul parameters of one layer of each kind (``mlp`` is in every
    layer): what ``body`` counts 6 FLOPs a token for."""
    sz = sizes(config)
    h, E, N, R = sz["hidden"], sz["channels"], sz["state"], sz["dt_rank"]
    q = sz["heads"] * sz["head_dim"]
    kv = sz["kv_heads"] * sz["head_dim"]
    return {"mlp": 3 * h * sz["ffn"],
            "mamba": 2 * h * E + E * (R + 2 * N) + R * E + E * h,
            "swa": 2 * h * q + 2 * h * kv, "full": 2 * h * q + 2 * h * kv,
            "gmu": 2 * h * E, "cross": 2 * h * q}


def parameters(config, vocab_rows=None):
    """All parameters of the layers held and the tied table of
    ``vocab_rows`` rows (default: the rows held)."""
    sz, mm = sizes(config), matmul_parameters(config)
    h, E, d = sz["hidden"], sz["channels"], sz["head_dim"]
    other = {"mamba": sz["conv"] * E + 3 * E + E * sz["state"],
             "gmu": 0, "swa": 6 * d, "full": 6 * d, "cross": 6 * d}
    layers = sum(mm[k] + mm["mlp"] + other[k] + 4 * h
                 for k in kinds_held(config))
    rows = sz["vocab"] if vocab_rows is None else vocab_rows
    return layers + rows * h + 2 * h


def allowed_pairs(seq, window=None):
    """(query, key) pairs one head's mask allows in a sequence: the causal
    triangle, or its band ``t - window < s <= t``."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return seq * window - window * (window - 1) // 2


def flops_per_token(config, traffic):
    """Matmul FLOPs one token of a training step requires, forward and
    backward, with the parts the harness prints:

    * ``body``: 6 x the matmul parameters of the layers held.
    * ``attention``: the score and value matmuls of the full and the
      cross-decoder layers over the pairs the causal mask ALLOWS: per pair
      and query head ``2 d`` (scores) + ``2 * 2d`` (a value head twice as
      wide as a key head), x 3 with the backward.
    * ``window``: the same for the sliding-window layers, over the band.
    * ``head``: the tied vocabulary projection over the rows held.

    The scan's and the convolution's elementwise work is not in ``mfu``
    (``scan_cost`` counts it), nor is anything recomputed."""
    sz, mm = sizes(config), matmul_parameters(config)
    kinds, seq = kinds_held(config), traffic["seq"]
    body = 6.0 * sum(mm[k] + mm["mlp"] for k in kinds)
    per_pair = 3 * (2 * sz["head_dim"] + 4 * sz["head_dim"]) * sz["heads"]
    whole = sum(k in ("full", "cross") for k in kinds)
    attention = float(per_pair * whole * allowed_pairs(seq)) / seq
    window = float(per_pair * kinds.count("swa")
                   * allowed_pairs(seq, sz["window"])) / seq
    head = 6.0 * sz["hidden"] * sz["vocab"]
    return {"body": body, "attention": attention, "window": window,
            "head": head, "total": body + attention + window + head}


def attention_call(config, traffic):
    """The full layer's call (q heads; 20 key heads and 10 value heads of
    twice the width are shared)."""
    sz = sizes(config)
    return {"rows": traffic["micro_batch"], "seq": traffic["seq"],
            "heads": sz["heads"], "head_dim": sz["head_dim"],
            "causal": True, "itemsize": 2}


def scan_cost(config, traffic, direction):
    """(FLOPs, bytes) ONE selective scan of one Mamba layer needs on the
    micro-batch, from shapes alone, whatever implements it.

    Forward: reads ``u`` and ``delta`` [T, E], ``B`` and ``C`` [T, N] in the
    compute dtype and ``A`` [E, N], ``D`` [E] in float32, writes ``y`` [T,
    E]; about 7 elementwise operations per (step, channel, state) — the
    exponent's product, the exponential, two products and a sum for the
    state, a product and a sum for the output.  Backward: reads ``u``,
    ``delta``, ``B``, ``C`` and ``dy``, writes ``du``, ``d delta``, ``dB``,
    ``dC`` (and the small ``dA``, ``dD``); the state is not kept, so it
    runs the forward's recurrence again beside its own: 3 x the forward's
    operations.  The operations are float32 elementwise and exponential
    work for the vector units, not matmul FLOPs: ``ssm_scan_roofline``
    prices the bytes alone."""
    if direction not in ("fwd", "bwd"):
        raise ValueError(f"direction must be 'fwd' or 'bwd', got "
                         f"{direction!r}")
    sz = sizes(config)
    rows, T = traffic["micro_batch"], traffic["seq"]
    E, N, item = sz["channels"], sz["state"], 2
    wide, narrow = rows * T * E * item, rows * T * N * item
    small = 4 * (E * N + E)
    ops = 7.0 * rows * T * E * N
    if direction == "fwd":
        return ops, float(3 * wide + 2 * narrow + small)
    return 3 * ops, float(5 * wide + 4 * narrow + 2 * small)


def _attention_cost(config, traffic, direction, window):
    """(FLOPs, bytes) ONE call of this model's attention (40 query heads of
    64 on 20 key heads of 64 and 10 value heads of 128) needs on the
    micro-batch, over the pairs the mask allows (``allowed_pairs``).

    Forward: scores (``2 d`` a pair and query head) and values (``2 * 2d``);
    reads q, k, v, writes the output (twice a key head wide) and one fp32
    log-sum-exp per query and head.  Backward: the scores again, dQ and dK
    (``3 x 2 d``), dP and dV (``2 x 4 d``); reads q, k, v, the output, its
    gradient and the log-sum-exp, writes dq, dk, dv."""
    if direction not in ("fwd", "bwd"):
        raise ValueError(f"direction must be 'fwd' or 'bwd', got "
                         f"{direction!r}")
    sz = sizes(config)
    rows, T, item = traffic["micro_batch"], traffic["seq"], 2
    n, nk, d = sz["heads"], sz["kv_heads"], sz["head_dim"]
    pairs = rows * n * allowed_pairs(T, window)
    q = rows * T * n * d * item
    kv = 2 * rows * T * nk * d * item          # k, and v: half the heads x 2d
    out = rows * T * n * 2 * d * item
    lse = rows * T * n * 4
    if direction == "fwd":
        return 2.0 * pairs * 3 * d, float(q + kv + out + lse)
    return 2.0 * pairs * 7 * d, float(2 * (q + kv + out) + lse)


def window_attention_cost(config, traffic, direction):
    """``_attention_cost`` of ONE call of sliding-window attention: the
    in-window pairs only."""
    return _attention_cost(config, traffic, direction,
                           config["sliding_window"])


def full_attention_cost(config, traffic, direction):
    """``_attention_cost`` of ONE call of the full or of a cross-decoder
    layer: the whole causal triangle (the two kinds of layer make the same
    call; a cross layer's keys and values are another layer's)."""
    return _attention_cost(config, traffic, direction, None)


def loss_ceiling(config):
    """``common.loss_ceiling`` over the rows of the vocabulary held."""
    return common.loss_ceiling({"vocab_rows": config["vocab_held"]})


def to_reference(params, config):
    """The program's parameter tree in ``reference.phi4flash``'s layout: the
    segments' stacked periods unstacked into one dict per layer, Mamba's two
    input matrices side by side and its taps ``[E, K]``, and the query
    heads from the program's order (group, sub-head, pair in group) into
    the reference's (pair, sub-head)."""
    sz = sizes(config)
    d = sz["head_dim"]
    common_names = {"ln1_g": "ln1_s", "ln1_b": "ln1_b", "ln2_g": "ln2_s",
                    "ln2_b": "ln2_b", "w_gate": "gate_w", "w_up": "up_w",
                    "w_down": "down_w"}
    attn_names = {"wo": "o_w", "lq1": "lam_q1", "lk1": "lam_k1",
                  "lq2": "lam_q2", "lk2": "lam_k2", "subln_g": "subln_s"}

    def one(kind, p):
        out = {theirs: p[ours] for theirs, ours in common_names.items()}
        if kind == "mamba":
            out.update(
                in_proj=jnp.concatenate([p["in_u_w"], p["in_z_w"]], axis=1),
                conv_w=p["conv_w"].T, conv_b=p["conv_b"], x_proj=p["x_w"],
                dt_w=p["dt_w"], dt_b=p["dt_b"], A_log=p["A_log"], D=p["D"],
                out_proj=p["out_w"])
        elif kind == "gmu":
            out.update(w1=p["w1"], w2=p["w2"])
        else:
            h = p["q_w"].shape[0]
            out.update({theirs: p[ours] for theirs, ours in
                        attn_names.items()})
            out["wq"] = (p["q_w"].reshape(h, -1, 2, 2, d)
                         .transpose(0, 1, 3, 2, 4).reshape(h, -1))
            if kind != "cross":
                out.update(wk=p["k_w"], wv=p["v_w"])
        return out

    layers = []
    for (kinds, repeats), stacked in zip(segments(config), params["blocks"],
                                         strict=True):
        for r in range(repeats):
            layers.extend(
                one(kind, {k: v[r] for k, v in stacked[f"l{j}"].items()})
                for j, kind in enumerate(kinds))
    return {"embed": params["wte"], "norm_g": params["lnf_s"],
            "norm_b": params["lnf_b"], "layers": layers}


def reference_loss(params, batch, config, **precision):
    """``reference.phi4flash.loss`` on the program's parameters (jit-safe)."""
    sz = sizes(config)
    return reference.loss(
        to_reference(params, config), batch, kinds=kinds_held(config),
        first_layer=config["layers_held"][0],
        heads=(sz["heads"] // 2, sz["kv_heads"] // 2, sz["head_dim"]),
        window=sz["window"], eps=config["layer_norm_eps"], **precision)
