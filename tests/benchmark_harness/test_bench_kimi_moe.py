"""The ``kimi_moe`` family and its cell ``kimi-vl-a3b.ep8-seq8192``: the
configuration file against the published keys, parameter counts, required
FLOPs and the two cost functions by hand, the five readers on a hand-made
record, the CPU rehearsal of the cell, and the plain reference's own
contract.  (The reference against the program's model, loss and every
gradient: tests/test_latent_moe_model.py; the expert layer and its shares:
tests/test_moe_dropless.py.)"""

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cell as cells
from benchmark import flops
from benchmark import trace_reduce as tr

CELL = "kimi-vl-a3b.ep8-seq8192"
G = 1e9
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
T, H = 8192, 2048
NEW = ["moe_ms_per_step", "moe_route_ms_per_step", "expert_matmul_roofline",
       "mla_ms_per_step", "mla_attn_roofline"]


def run(args):
    """``python -m benchmark.run`` in a child process (it sets the platform
    before it imports jax)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # the rehearsal asks for its own devices
    return subprocess.run([sys.executable, "-m", "benchmark.run", *args],
                          cwd=cells.ROOT, env=env, capture_output=True,
                          text=True, timeout=600)


@pytest.fixture(scope="module")
def cell():
    return cells.load(CELL)


def test_configuration_keeps_every_published_key(cell):
    """The catalog's ``config`` of Kimi-VL-A3B-Instruct, key for key; the
    cuts are ``layers_held``, ``n_routed_held`` and ``vocab_held``,
    the published counts stay, and every assumption is written down."""
    config = cell.config
    published = {
        "vocab_size": 163840, "max_position_embeddings": 131072,
        "hidden_size": 2048, "intermediate_size": 11264,
        "moe_intermediate_size": 1408, "num_hidden_layers": 27,
        "num_attention_heads": 16, "n_shared_experts": 2,
        "n_routed_experts": 64, "ep_size": 1,
        "routed_scaling_factor": 2.446, "kv_lora_rank": 512,
        "q_lora_rank": None, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "topk_method": "noaux_tc", "n_group": 1,
        "topk_group": 1, "num_experts_per_tok": 6, "moe_layer_freq": 1,
        "first_k_dense_replace": 1, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "seq_aux": True,
        "num_key_value_heads": 16, "hidden_act": "silu",
        "rms_norm_eps": 1e-05, "rope_theta": 800000, "rope_scaling": None,
        "attention_bias": False, "tie_word_embeddings": False}
    assert {k: config[k] for k in published} == published
    assert list(config["reduced"]) == ["layers_held",
                                       "n_routed_held", "vocab_held"]
    held = config["layers_held"]
    assert held == list(range(len(held))) and len(held) in (5, 6)
    assert config["n_routed_held"] == 64 // 8 == 8
    assert config["first_routed_held"] == 0
    assert config["vocab_held"] == 163840 // 8 == 20480
    for key in ("aux_loss_alpha", "initializer_range", "rotary_layout",
                "e_score_correction_bias", "router_precision", "dropout",
                "vision_tower", "absent_experts"):
        assert key in config["assumed"], key
    assert "not run" in config["assumed"]["e_score_correction_bias"].lower()
    assert "eight chips" in config["deployment"].lower()
    assert "learning_rate_why" in config["job"]
    assert "activation_checkpointing_why" in config["job"]
    man = cells.manifest()
    entry = next(c for c in man["configs"] if c["name"] == cell.config_name)
    assert entry["reduced"] == ["layers_held", "n_routed_held",
                                "vocab_held"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct/blob/main/"
        "config.json")
    workload = next(w for w in man["workloads"] if w["name"] == CELL)
    assert workload == {"name": CELL, "config": "kimi-vl-a3b-ep8",
                        "traffic": "lm-seq8192-mb2", "chips": 1,
                        "why": workload["why"]}
    assert sum(w["config"] == "kimi-vl-a3b-ep8"
               for w in man["workloads"]) == 1
    traffic = cell.traffic
    assert (traffic["kind"], traffic["api"], traffic["seq"],
            traffic["micro_batch"], traffic["gas"], traffic["batch_pool"],
            traffic["warmup_steps"]) == ("train_steps_update", "fused", 8192,
                                         2, 1, 8, 3)
    assert cell.layout["name"] == "1chip"
    model = cell.family.build_model(config, traffic).config
    assert (model.hidden_size, model.num_heads, model.latent_rank,
            model.nope_dim, model.rope_dim, model.v_dim, model.qk_head_dim,
            model.dense_ffn_size, model.expert_ffn_size, model.num_experts,
            model.experts_per_token, model.shared_experts,
            model.experts_held, model.vocab_size, model.route_scale,
            model.rope_theta, model.norm_eps, model.balance_alpha) == (
                2048, 16, 512, 128, 64, 128, 192, 11264, 1408, 64, 6, 2,
                (0, 8), 20480, 2.446, 800000.0, 1e-5, 0.001)
    assert model.kinds == ("dense",) + ("moe",) * (len(held) - 1)
    # ids come from the slice, every position carries a label
    tokens, labels = cell.family.make_batch(
        np.random.default_rng(0), 2, config, traffic)
    assert tokens.shape == labels.shape == (2, 8192)
    assert np.array_equal(tokens[:, 1:], labels[:, :-1])
    assert 20480 * 0.99 < tokens.max() < 20480 and labels.min() >= 0


def test_parameter_counts_by_hand(cell):
    """The issue's count: attention 13.76M a layer, the dense MLP 69.21M, an
    expert 8.65M, the shared experts 17.30M, the router 0.13M; the file's
    count equals the model's; the whole model by the same count is the
    published 16B."""
    fam, config = cell.family, cell.config
    mm = fam.matmul_parameters(config)
    assert mm == {"mla": 6_291_456 + 1_179_648 + 2_097_152 + 4_194_304,
                  "dense": 69_206_016, "expert": 8_650_752,
                  "shared": 17_301_504, "router": 131_072}
    norms = 2 * H + 512
    dense = mm["mla"] + norms + mm["dense"]
    moe = mm["mla"] + norms + 8 * mm["expert"] + mm["shared"] + mm[
        "router"] + 64
    n_moe = len(config["layers_held"]) - 1
    by_hand = dense + n_moe * moe + 2 * 20480 * H + H
    assert fam.parameters(config) == by_hand
    assert by_hand == {5: 668_890_432, 4: 568_490_368}[n_moe]
    shapes = jax.eval_shape(
        fam.build_model(config, cell.traffic).init_params,
        jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in
               jax.tree_util.tree_leaves(shapes)) == by_hand
    whole = {**config, "layers_held": list(range(27))}
    total = fam.parameters(whole, vocab_rows=163840, experts=64)
    assert 15.9e9 < total < 16.1e9                  # "16B-A2.8B"
    # active a token: six of the 64 experts a layer; 2.91B with the table's
    # 0.34B counted, 2.58B without ("A2.8B")
    active = total - 26 * 58 * mm["expert"]
    assert 2.5e9 < active - 163840 * H < 2.8e9 < active < 3.0e9
    assert fam.kinds_held(whole) == ("dense",) + ("moe",) * 26
    assert fam.segments(whole) == ((("dense",), 1), (("moe",), 26))
    assert fam.with_depth(config, 5)["layers_held"] == [0, 1, 2, 3, 4]


def test_flops_per_token_by_hand(cell):
    """~2.6 GFLOP a token; the parts add up; the routed experts count by
    expectation (0.75 applications a token), nothing recomputed counts."""
    fam, config, traffic = cell.family, cell.config, cell.traffic
    got = fam.flops_per_token(config, traffic)
    layers = len(config["layers_held"])
    n_moe = layers - 1
    assert fam.routed_share(config) == 0.75
    assert got["mla"] == 6 * layers * 13_762_560
    assert got["attention"] == (3 * (2 * 192 + 2 * 128) * 16 * layers
                                * (T * (T + 1) // 2) / T)
    assert got["dense"] == 6 * 69_206_016
    assert got["routed"] == 6 * n_moe * 8_650_752 * 0.75
    assert got["shared"] == 6 * n_moe * (17_301_504 + 131_072)
    assert got["head"] == 6 * H * 20480
    parts = [v for k, v in got.items() if k != "total"]
    assert got["total"] == pytest.approx(sum(parts), rel=1e-12)
    if n_moe == 5:
        assert got["total"] / G == pytest.approx(2.635, abs=0.001)
        share = {k: got[k] / got["total"] for k in got}
        assert share["mla"] + share["attention"] == pytest.approx(0.475,
                                                                  abs=0.005)
        assert share["routed"] + share["shared"] == pytest.approx(0.272,
                                                                  abs=0.005)
        assert share["dense"] == pytest.approx(0.158, abs=0.005)
        assert share["head"] == pytest.approx(0.096, abs=0.005)
    full = {**config, "job": {**config["job"],
                              "activation_checkpointing": "selective"}}
    assert fam.flops_per_token(full, traffic) == got
    call = fam.attention_call(config, traffic)
    assert (call["seq"], call["heads"], call["head_dim"], call["causal"],
            call["rows"]) == (8192, 16, 192, True, 2)


def test_the_two_costs_by_hand(cell):
    fam, config, traffic = cell.family, cell.config, cell.traffic
    peaks = cells.peaks("TPU v5 lite")
    pairs = 2 * 16 * (T * (T + 1) // 2)
    qk, v, lse = 2 * 2 * T * 16 * 192 * 2, 2 * T * 16 * 128 * 2, 2 * T * 16 * 4
    assert fam.latent_attention_cost(config, traffic, "fwd") == (
        2.0 * pairs * (192 + 128), float(qk + 2 * v + lse))
    assert fam.latent_attention_cost(config, traffic, "bwd") == (
        2.0 * pairs * (3 * 192 + 2 * 128), float(2 * (qk + v) + 2 * v + lse))
    least = {d: flops.roofline_seconds(
        *fam.latent_attention_cost(config, traffic, d), peaks)
        for d in ("fwd", "bwd")}
    assert least["fwd"][1] == least["bwd"][1] == "compute"
    assert 1e3 * least["fwd"][0] == pytest.approx(3.488, abs=0.001)
    assert least["bwd"][0] / least["fwd"][0] == pytest.approx(2.6, abs=1e-9)
    # the experts: 12,288 rows by expectation, 8 experts' three matrices
    rows = 2 * T * 0.75
    ops = 2.0 * rows * 3 * H * 1408
    weights = 3 * 8 * H * 1408 * 2
    acts = rows * (2 * H + 3 * 1408) * 2
    assert fam.expert_matmul_cost(config, traffic, "fwd") == (
        ops, float(weights + acts))
    assert fam.expert_matmul_cost(config, traffic, "bwd") == (
        2 * ops, float(2 * (weights + acts)))
    assert ops / 1e12 == pytest.approx(0.2126, abs=1e-4)
    seconds, bound = flops.roofline_seconds(
        *fam.expert_matmul_cost(config, traffic, "fwd"), peaks)
    assert bound == "compute" and 1e3 * seconds == pytest.approx(1.079,
                                                                 abs=1e-3)
    for cost in (fam.latent_attention_cost, fam.expert_matmul_cost):
        with pytest.raises(ValueError, match="direction"):
            cost(config, traffic, "both")


# ------------------------------------------------------------- rehearsal

@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_of_the_cell_is_correct_and_well_formed(trace):
    proc = run(["--workload", CELL, "--seed", "3300000007", "--seconds", "1",
                "--trace", trace, "--rehearse-cpu"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert LINE_KEYS <= set(line)
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["correct"] is True, proc.stdout[-3000:]
    for check in ("reference", "first_update", "warmup_loss_drop"):
        assert f"check {check}:" in proc.stdout and "'ok': True" in \
            proc.stdout.split(f"check {check}:")[1].splitlines()[0], check
    assert "check no_compile_in_window: {'compile_requests': 0, 'ok': True}" \
        in proc.stdout


# ---------------------------------------------- the readers, by hand
# benchmark/testdata/two_steps.xplane.pb (test_bench_trace.py draws it): one
# step on chip 0, self times in microseconds, under a map that places the
# instructions in this stack's scopes (closed_call.3 and checkpoint.5 are
# Pallas calls):
#
#     fusion.1             100   dstpu/mla      forward
#     while.2               20   dstpu/route    forward
#     closed_call.3    2 x 100   dstpu/attn     forward   Pallas (the core)
#     fusion.4         2 x 190   dstpu/route    replay
#     checkpoint.5          50   dstpu/experts  backward  Pallas (a product)
#     all-gather-start.6    10   dstpu/moe      forward
#     fusion.7              40   dstpu/mla      backward
#     all-gather-done.6     50   dstpu/ffn      forward   (the shared experts)
#     all-reduce.8         100   dstpu/experts  backward  (150 on chip 1)

PB = os.path.join(cells.ROOT, "benchmark", "testdata", "two_steps.xplane.pb")
SCOPE_MAP = {
    "fusion.1": ("dstpu/mla", "forward"),
    "while.2": ("dstpu/route", "forward"),
    "closed_call.3": ("dstpu/attn", "forward"),
    "fusion.4": ("dstpu/route", "replay"),
    "checkpoint.5": ("dstpu/experts", "backward"),
    "all-gather-start.6": ("dstpu/moe", "forward"),
    "fusion.7": ("dstpu/mla", "backward"),
    "all-gather-done.6": ("dstpu/ffn", "forward"),
    "all-reduce.8": ("dstpu/experts", "backward"),
}


def least_ms(cell, cost):
    peaks = cells.peaks("TPU v5 lite")
    return 1e3 * sum(flops.roofline_seconds(
        *cost(cell.config, cell.traffic, d), peaks)[0] for d in ("fwd", "bwd"))


def expected(cell):
    fam = cell.family
    layers = len(cell.config["layers_held"])
    return {
        # chip 1: while.2 + fusion.4 twice (route), checkpoint.5 +
        # all-reduce.8 at its 150 us (experts), all-gather-start.6 (moe)
        "moe_ms_per_step": 0.02 + 0.38 + 0.05 + 0.15 + 0.01,
        "moe_route_ms_per_step": 0.40,
        # first chip: 50 + 100 us a step under experts, every expert layer's
        # forward + backward products
        "expert_matmul_roofline": 100 * (layers - 1) * least_ms(
            cell, fam.expert_matmul_cost) / 0.15,
        # fusion.1 + fusion.7
        "mla_ms_per_step": 0.14,
        # the core's two calls of 100 us a step, one forward and one
        # backward call a layer
        "mla_attn_roofline": 100 * layers * least_ms(
            cell, fam.latent_attention_cost) / 0.20,
    }


@pytest.fixture(scope="module")
def record(cell):
    trace = tr.load(PB)
    return types.SimpleNamespace(
        cell=cell, peaks=cells.peaks("TPU v5 lite"), steps=2, trace=trace,
        steady=tr.steady(trace, 2), scope_map=SCOPE_MAP)


@pytest.mark.parametrize("name", NEW)
def test_reader_by_hand(cell, record, name):
    read = cells.plugin(cells.ROOT, "metrics", name).read
    assert read(record) == pytest.approx(expected(cell)[name], rel=1e-9)
    # a program of another model has the map but no such scope: 0 for a
    # time, nothing for a share; a run without a trace says nothing
    other = {k: ("dstpu/block", p) for k, (_, p) in SCOPE_MAP.items()}
    elsewhere = read(types.SimpleNamespace(
        **{**vars(record), "scope_map": other}))
    assert elsewhere == (None if name.endswith("roofline") else 0.0)
    assert read(types.SimpleNamespace(**{**vars(record), "steady": []})) \
        is None
    assert read(types.SimpleNamespace(**{**vars(record), "steady": [],
                                         "scope_map": None})) is None


def test_the_grouped_matmuls_are_not_read_as_attention(cell, record):
    """Both are Pallas calls; the scope map tells them apart.  With the
    product's call moved under ``dstpu/attn`` the attention share would
    read its 50 us too."""
    read = cells.plugin(cells.ROOT, "metrics", "mla_attn_roofline").read
    both = {**SCOPE_MAP, "checkpoint.5": ("dstpu/attn", "backward")}
    got = read(types.SimpleNamespace(**{**vars(record), "scope_map": both}))
    assert got == pytest.approx(expected(cell)["mla_attn_roofline"]
                                * 0.20 / 0.25, rel=1e-9)


def test_the_five_entries_belong_to_the_cell_alone(cell):
    man = cells.manifest()
    entries = [m for m in man["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in entries] == NEW
    assert [m["name"] for m in man["per_layer"][-5:]] == NEW
    for entry in entries:
        share = entry["name"].endswith("roofline")
        assert entry["workloads"] == [CELL]
        assert (entry["source"], entry["moves"], entry["unit"],
                entry["better"], entry["layer"]) == (
            "program_span", "tokens_per_s_per_chip", "%" if share else "ms",
            "higher" if share else "lower", "kernels" if share else "model")
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= reported
    assert {"head_ms_per_step", "norm_ms_per_step", "scoped_share",
            "remat_replay_share", "attn_kernel_ms_per_step", "peak_hbm_gb",
            "optimizer_ms_per_step", "device_ops_per_step"} <= reported
    assert not {"attn_kernel_roofline", "ssm_ms_per_step",
                "full_attn_roofline", "rope_ms_per_step"} & reported
    for old in ("gpt2-xl.1chip", "phi4-mini-flash.seq8192"):
        assert not set(NEW) & {m["name"] for m in cells.load(old).per_layer}
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s_per_chip", "mfu", "setup_s"}


# ------------------------------------------------------------ the reference

def test_reference_imports_nothing_of_the_program():
    path = os.path.join(cells.ROOT, "benchmark", "reference", "kimi_moe.py")
    source = open(path).read()
    code = source.split('"""', 2)[2]              # past the module docstring
    imports = [line for line in code.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["import functools", "import jax",
                       "import jax.numpy as jnp",
                       "from benchmark.reference.ops import round_mantissa"]
    for word in ("deepspeed_tpu", "argsort", "ragged", "pallas", "jnp.sort"):
        assert word not in code, word


def test_lower_precision_moves_the_reference_loss(cell):
    """What ``--probe-reference`` prints, at the tiny size: bf16 storage and
    fp8-wide operands move the loss, and more than float32 noise does; the
    share counts the pairs the same way at every precision's own routing."""
    fam = cell.family
    config = fam.tiny(cell.config)
    model = fam.build_model(config, {"seq": 128})
    params = model.init_params(jax.random.PRNGKey(0))
    batch = fam.make_batch(np.random.default_rng(0), 2, config, {"seq": 128})
    exact = fam.reference_parts(params, batch, config)
    again = fam.reference_parts(params, batch, config)
    assert float(exact[0]) == float(again[0])
    stored = fam.reference_parts(params, batch, config, dtype=jnp.bfloat16)
    coarse = fam.reference_parts(params, batch, config, operand_bits=3)
    assert abs(float(stored[0]) - float(exact[0])) > 1e-6
    assert abs(float(coarse[0]) - float(exact[0])) > abs(
        float(stored[0]) - float(exact[0]))
    for parts in (exact, stored, coarse):
        assert 0.15 < int(parts[2]) / (2 * 128 * 3 * 2) < 0.35
        assert 1e-3 < float(parts[1]) < 4e-3         # two layers of ~alpha
