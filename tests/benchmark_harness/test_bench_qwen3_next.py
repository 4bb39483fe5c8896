"""The ``qwen3_next`` family and its cell ``qwen3-next.ep16-seq16384``: the
configuration file against the catalog row, parameter counts, required FLOPs
and the three cost functions by hand, the four readers on a hand-made
record, the CPU rehearsal of the cell, a wrong share of the experts caught by
``first_update``, and the plain reference's own contract.  (The reference
against the program's model, loss and every gradient:
tests/test_delta_moe_model.py; the layers and the shares:
tests/test_delta_moe_layers.py; the rule: tests/test_delta_rule.py.)"""

import json
import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from benchmark import cell as cells
from benchmark import flops
from benchmark import trace_reduce as tr

CELL = "qwen3-next.ep16-seq16384"
G = 1e9
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
T, H = 16384, 2048
NEW = ["gdn_ms_per_step", "delta_rule_ms_per_step", "delta_rule_roofline",
       "gated_attn_roofline"]
#: the catalog row's ``config``, key for key
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


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


def test_configuration_keeps_every_key_of_the_catalog_row(cell):
    """Every published key unchanged; the cuts are ``layers_held``,
    ``n_routed_held`` and ``vocab_held``, the published counts stay, and
    every assumption is written down."""
    config = cell.config
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert list(config["reduced"]) == ["layers_held", "n_routed_held",
                                       "vocab_held"]
    assert config["layers_held"] == [0, 1, 2, 3]
    assert config["n_routed_held"] in (32, 16)       # EP-16, or the fallback
    assert config["first_routed_held"] == 0
    assert config["vocab_held"] == 151936 // 8 == 18992
    for key in ("router_aux_loss_coef", "initializer_range",
                "projection_layout", "rotary_layout", "router_precision",
                "delta_rule_precision", "dropout", "mtp", "absent_experts"):
        assert key in config["assumed"], key
    assert "left out" in config["assumed"]["mtp"]
    assert "sixteen chips" in config["deployment"].lower()
    assert "learning_rate_why" in config["job"]
    assert "activation_checkpointing_why" in config["job"]
    man = cells.manifest()
    entry = next(c for c in man["configs"] if c["name"] == cell.config_name)
    assert entry == man["configs"][-1]
    assert entry["reduced"] == ["layers_held", "n_routed_held", "vocab_held"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
        "config.json")
    workload = man["workloads"][-1]
    assert workload == {"name": CELL, "config": "qwen3-next-ep16",
                        "traffic": "lm-seq16384-mb1", "chips": 1,
                        "why": workload["why"]}
    assert len(workload["why"]) <= 200 and len(entry["why"]) <= 200
    assert sum(w["config"] == "qwen3-next-ep16"
               for w in man["workloads"]) == 1
    traffic = cell.traffic
    assert (traffic["kind"], traffic["api"], traffic["seq"],
            traffic["micro_batch"], traffic["gas"], traffic["batch_pool"],
            traffic["warmup_steps"]) == ("train_steps_update", "fused",
                                         16384, 1, 1, 8, 3)
    assert cell.layout["name"] == "1chip"
    model = cell.family.build_model(config, traffic).config
    assert (model.hidden_size, model.num_heads, model.num_kv_heads,
            model.head_dim, model.rotary_dim, model.rope_theta,
            model.key_heads, model.value_heads, model.key_dim,
            model.value_dim, model.conv_kernel, model.expert_ffn_size,
            model.shared_ffn_size, model.num_experts,
            model.experts_per_token, model.experts_held, model.vocab_size,
            model.norm_eps, model.balance_alpha) == (
                2048, 16, 2, 256, 64, 1e7, 16, 32, 128, 128, 4, 512, 512,
                512, 10, (0, config["n_routed_held"]), 18992, 1e-6, 0.001)
    assert model.kinds == ("gdn", "gdn", "gdn", "full")
    assert cell.family.kinds_held(config) == ["gdn", "gdn", "gdn", "full"]
    # ids come from the slice, every position carries a label
    tokens, labels = cell.family.make_batch(
        np.random.default_rng(0), 1, config, traffic)
    assert tokens.shape == labels.shape == (1, 16384)
    assert np.array_equal(tokens[:, 1:], labels[:, :-1])
    assert 18992 * 0.99 < tokens.max() < 18992 and labels.min() >= 0


def test_parameter_counts_by_hand(cell):
    """The issue's count: a DeltaNet mixer 33.72M, an attention mixer
    27.26M, an expert 3.146M, router + shared expert + its gate 4.20M; the
    file's count equals the model's; the whole model by the same count is
    the published 80B, about 3B of it active a token."""
    fam, config = cell.family, cell.config
    mm = fam.matmul_parameters(config)
    assert mm == {"gdn": 2048 * 12288 + 2048 * 64 + 4096 * 2048,
                  "full": 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048,
                  "expert": 3 * 2048 * 512, "shared": 3 * 2048 * 512,
                  "router": 2048 * 512 + 2048}
    held = config["n_routed_held"]
    moe = 2 * H + held * mm["expert"] + mm["shared"] + mm["router"]
    gdn = mm["gdn"] + 4 * 8192 + 2 * 32 + 128
    full = mm["full"] + 2 * 256
    by_hand = 3 * (gdn + moe) + (full + moe) + 2 * 18992 * H + H
    assert fam.parameters(config) == by_hand
    assert by_hand == {32: 625_667_136, 16: 424_340_544}[held]
    shapes = jax.eval_shape(
        fam.build_model(config, cell.traffic).init_params,
        jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in
               jax.tree_util.tree_leaves(shapes)) == by_hand
    whole = {**config, "layers_held": list(range(48))}
    total = fam.parameters(whole, vocab_rows=151936, experts=512)
    assert 79e9 < total < 81e9                          # "80B-A3B"
    active = total - 48 * 502 * mm["expert"] - 2 * 151936 * H
    assert 2.9e9 < active < 3.4e9
    assert fam.kinds_held(whole) == ["gdn", "gdn", "gdn", "full"] * 12
    assert fam.segments(whole) == ((("gdn", "gdn", "gdn", "full"), 12),)
    assert fam.with_depth(config, 8)["layers_held"] == list(range(8))
    with pytest.raises(ValueError, match="whole periods"):
        fam.segments({**config, "layers_held": [0, 1, 2]})


def test_flops_per_token_by_hand(cell):
    """1.59 GFLOP a token at 32 experts held; the parts add up; the rule
    counts the recurrence's three products, not the chunked form's; the
    routed experts count by expectation (0.625 applications a token);
    nothing recomputed counts."""
    fam, config, traffic = cell.family, cell.config, cell.traffic
    got = fam.flops_per_token(config, traffic)
    held = config["n_routed_held"]
    assert fam.routed_share(config) == 10 * held / 512
    assert got["gdn"] == 6 * 3 * 33_685_504
    assert got["delta"] == 3 * 4 * (3 * 2 * 128 * 128) * 32
    assert got["full"] == 6 * 27_262_976
    assert got["attention"] == 3 * 4 * 256 * 16 * (T * (T + 1) // 2) / T
    assert got["routed"] == 6 * 4 * 3_145_728 * 10 * held / 512
    assert got["shared"] == 6 * 4 * (3_145_728 + 1_050_624)
    assert got["head"] == 6 * H * 18992
    parts = [v for k, v in got.items() if k != "total"]
    assert got["total"] == pytest.approx(sum(parts), rel=1e-12)
    if held == 32:
        assert got["total"] / G == pytest.approx(1.5916, abs=0.0005)
        share = {k: got[k] / got["total"] for k in got}
        assert share["gdn"] + share["delta"] == pytest.approx(0.405,
                                                              abs=0.005)
        assert share["full"] + share["attention"] == pytest.approx(
            0.356, abs=0.005)
        assert share["routed"] + share["shared"] == pytest.approx(
            0.093, abs=0.005)
        assert share["head"] == pytest.approx(0.147, abs=0.005)
    selective = {**config, "job": {**config["job"],
                                   "activation_checkpointing": "selective"}}
    assert fam.flops_per_token(selective, traffic) == got
    call = fam.attention_call(config, traffic)
    assert (call["seq"], call["heads"], call["head_dim"], call["causal"],
            call["rows"]) == (16384, 16, 256, True, 1)


def test_the_three_costs_by_hand(cell):
    fam, config, traffic = cell.family, cell.config, cell.traffic
    peaks = cells.peaks("TPU v5 lite")
    # the rule: 3 products of 2 x 128 x 128 a token and value head
    ops = 3.0 * 2 * 128 * 128 * 32 * T
    qkvo = T * (2 * 16 * 128 + 2 * 32 * 128) * 2
    gates, states = T * 2 * 32 * 4, 256 * 32 * 128 * 128 * 4
    assert fam.delta_rule_cost(config, traffic, "fwd") == (
        ops, float(qkvo + gates + states))
    assert fam.delta_rule_cost(config, traffic, "bwd") == (
        3 * ops, float(2 * qkvo + 2 * gates + states))
    assert ops / G == pytest.approx(51.54, abs=0.01)
    assert states == 536_870_912                        # 537 MB a layer
    least = {d: flops.roofline_seconds(
        *fam.delta_rule_cost(config, traffic, d), peaks)
        for d in ("fwd", "bwd")}
    assert least["fwd"][1] == least["bwd"][1] == "memory"
    assert 1e3 * least["fwd"][0] == pytest.approx(1.152, abs=0.001)
    assert 1e3 * least["bwd"][0] == pytest.approx(1.649, abs=0.001)
    # the gated attention's core: 16 query heads of 256 on 2 shared heads
    pairs = 16 * (T * (T + 1) // 2)
    q, k_v, lse = T * 16 * 256 * 2, 2 * T * 2 * 256 * 2, T * 16 * 4
    assert fam.gated_attention_cost(config, traffic, "fwd") == (
        2.0 * pairs * 2 * 256, float(2 * q + k_v + lse))
    assert fam.gated_attention_cost(config, traffic, "bwd") == (
        2.0 * pairs * 5 * 256, float(4 * q + 2 * k_v + lse))
    seconds, bound = flops.roofline_seconds(
        *fam.gated_attention_cost(config, traffic, "fwd"), peaks)
    assert bound == "compute" and 1e3 * seconds == pytest.approx(11.163,
                                                                 abs=0.001)
    # the experts: 10,240 rows by expectation at 32 held, three matrices each
    held = config["n_routed_held"]
    rows = T * 10 * held / 512
    e_ops = 2.0 * rows * 3 * H * 512
    weights = 3 * held * H * 512 * 2
    acts = rows * (2 * H + 3 * 512) * 2
    assert fam.expert_matmul_cost(config, traffic, "fwd") == (
        e_ops, float(weights + acts))
    assert fam.expert_matmul_cost(config, traffic, "bwd") == (
        2 * e_ops, float(2 * (weights + acts)))
    for cost in (fam.delta_rule_cost, fam.gated_attention_cost,
                 fam.expert_matmul_cost):
        with pytest.raises(ValueError, match="direction"):
            cost(config, traffic, "both")


# ------------------------------------------------------------- rehearsal

def test_rehearsal_of_the_cell_is_correct_and_well_formed():
    proc = run(["--workload", CELL, "--seed", "3700000007", "--seconds", "1",
                "--trace", "0", "--rehearse-cpu"])
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
    assert "'leaves': 70" in proc.stdout
    assert "check no_compile_in_window: {'compile_requests': 0, 'ok': True}" \
        in proc.stdout


# ---------------------------------------------- the readers, by hand
# benchmark/testdata/two_steps.xplane.pb (test_bench_trace.py draws it): one
# step on chip 0, self times in microseconds, under a map that places the
# instructions in this stack's scopes (closed_call.3 and checkpoint.5 are
# Pallas calls):
#
#     fusion.1             100   dstpu/gdn      forward
#     while.2               20   dstpu/delta    forward
#     closed_call.3    2 x 100   dstpu/attn     forward   Pallas (the core)
#     fusion.4         2 x 190   dstpu/delta    replay
#     checkpoint.5          50   dstpu/experts  backward  Pallas (a product)
#     all-gather-start.6    10   dstpu/conv     forward
#     fusion.7              40   dstpu/gdn      backward
#     all-gather-done.6     50   dstpu/ffn      forward   (the shared expert)
#     all-reduce.8         100   dstpu/delta    backward  (150 on chip 1)

PB = os.path.join(cells.ROOT, "benchmark", "testdata", "two_steps.xplane.pb")
SCOPE_MAP = {
    "fusion.1": ("dstpu/gdn", "forward"),
    "while.2": ("dstpu/delta", "forward"),
    "closed_call.3": ("dstpu/attn", "forward"),
    "fusion.4": ("dstpu/delta", "replay"),
    "checkpoint.5": ("dstpu/experts", "backward"),
    "all-gather-start.6": ("dstpu/conv", "forward"),
    "fusion.7": ("dstpu/gdn", "backward"),
    "all-gather-done.6": ("dstpu/ffn", "forward"),
    "all-reduce.8": ("dstpu/delta", "backward"),
}


def least_ms(cell, cost):
    peaks = cells.peaks("TPU v5 lite")
    return 1e3 * sum(flops.roofline_seconds(
        *cost(cell.config, cell.traffic, d), peaks)[0] for d in ("fwd", "bwd"))


def expected(cell):
    fam = cell.family
    return {
        # chip 1: fusion.1 + fusion.7 (gdn), while.2 + fusion.4 twice +
        # all-reduce.8 at its 150 us (delta), all-gather-start.6 (conv)
        "gdn_ms_per_step": 0.14 + 0.02 + 0.38 + 0.15 + 0.01,
        "delta_rule_ms_per_step": 0.02 + 0.38 + 0.15,
        # first chip: 20 + 380 + 100 us a step under delta, three layers'
        # forward + backward rule a step
        "delta_rule_roofline": 100 * 3 * least_ms(
            cell, fam.delta_rule_cost) / 0.50,
        # the core's two calls of 100 us a step, one forward and one
        # backward call of the one full layer
        "gated_attn_roofline": 100 * least_ms(
            cell, fam.gated_attention_cost) / 0.20,
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


def test_the_four_entries_belong_to_the_cell_alone(cell):
    man = cells.manifest()
    assert [m["name"] for m in man["per_layer"][-4:]] == NEW
    for entry in man["per_layer"][-4:]:
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
    # Kimi's and phi4's by their lists, which this PR may not touch
    assert not {"attn_kernel_roofline", "ssm_ms_per_step", "moe_ms_per_step",
                "expert_matmul_roofline", "moe_prefix_fill"} & reported
    for old in ("kimi-vl-a3b.ep8-seq8192", "phi4-mini-flash.seq8192"):
        assert not set(NEW) & {m["name"] for m in cells.load(old).per_layer}
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s_per_chip", "mfu", "setup_s"}


# -------------------------------------------------- a wrong share is caught

def test_another_share_passes_the_loss_and_fails_the_update(cell):
    """The program holds experts 4-7 where the reference holds 0-3 (at the
    cell's sizes: 32-63 for 0-31): random labels make the first-step loss
    blind to it, the first update reads above 1."""
    import copy
    import contextlib
    tiny = copy.copy(cell)
    tiny.config = cell.family.tiny(cell.config)
    opts = types.SimpleNamespace(seed=11, probe_reference=False)
    clock = lambda name, counted=True: contextlib.nullcontext()

    def checks():
        return tiny.kind.set_up(tiny, opts, clock, lambda line: None,
                                jax.devices()[:1])[2]

    # (the sound share: the rehearsal above, every check ok)
    build = tiny.family.build_model
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(tiny.family, "build_model", lambda config, traffic:
                      build({**config, "first_routed_held": 4}, traffic))
        wrong = checks()
    assert wrong["reference"]["ok"], wrong["reference"]
    assert wrong["first_update"]["leaves"] == 70
    assert not wrong["first_update"]["ok"]
    assert wrong["first_update"]["worst_leaves"][0][1] > 1.0


# ------------------------------------------------------------ the reference

def test_reference_is_the_recurrence_and_imports_nothing_of_the_program():
    path = os.path.join(cells.ROOT, "benchmark", "reference",
                        "qwen3_next.py")
    code = open(path).read().split('"""', 2)[2]   # past the module docstring
    imports = [line for line in code.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == [
        "import functools", "import jax", "import jax.numpy as jnp",
        "from benchmark.reference.kimi_moe import (first_adam_step, matmul, "
        "rounded,"]
    for word in ("deepspeed_tpu", "argsort", "ragged", "pallas", "jnp.sort",
                 "solve_triangular", "cumsum"):
        assert word not in code, word
    assert "jax.lax.scan(step, S, xs)" in code      # one step at a time
    assert 'jax.default_matmul_precision("highest")' in code
