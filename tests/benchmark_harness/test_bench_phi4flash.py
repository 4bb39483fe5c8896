"""The ``phi4flash`` family and its cell ``phi4-mini-flash.seq8192``: the
configuration file against the published keys, parameter counts, required
FLOPs and the three cost functions by hand, the seven readers on a hand-made
record, the CPU rehearsal of the cell, the vocabulary slice, and the plain
reference's own contract.  (The reference against the program's model, loss
and every gradient: tests/test_hybrid_model.py.)"""

import copy
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmark import cell as cells
from benchmark import flops
from benchmark import trace_reduce as tr
from deepspeed_tpu.parallel.topology import make_mesh

CELL = "phi4-mini-flash.seq8192"
G = 1e9
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
T, H, E, N = 8192, 2560, 5120, 16


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
    """The catalog's ``config`` of Phi-4-mini-flash-reasoning, key for key;
    the cuts are ``layers_held`` and ``vocab_held``, and every assumption
    the issue names is written down with its source."""
    config = cell.config
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    assert {k: config[k] for k in published} == published
    assert list(config["reduced"]) == ["layers_held", "vocab_held"]
    assert config["layers_held"] == [14, 15, 16, 17, 18, 19]
    assert config["vocab_held"] == 200064 // 8 == 25008
    for key in ("d_state", "d_conv", "expand", "dt_rank", "layer_kinds",
                "differential_attention", "positions", "initializer_range",
                "dropout"):
        assert key in config["assumed"], key
    assert "eight chips" in config["deployment"]
    assert "learning_rate_why" in config["job"]
    man = cells.manifest()
    assert man["configs"][-1]["name"] == cell.config_name
    assert man["configs"][-1]["reduced"] == ["layers_held", "vocab_held"]
    assert man["configs"][-1]["source"] == config["source"] == (
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/"
        "main/config.json")
    assert man["workloads"][-1] == {
        "name": CELL, "config": "phi4-mini-flash-l6",
        "traffic": "lm-seq8192-mb1", "chips": 1,
        "why": man["workloads"][-1]["why"]}
    traffic = cell.traffic
    assert (traffic["kind"], traffic["api"], traffic["seq"],
            traffic["micro_batch"], traffic["gas"], traffic["batch_pool"],
            traffic["warmup_steps"]) == ("train_steps", "fused", 8192, 1, 1,
                                         8, 3)
    assert cell.layout["name"] == "1chip"
    model = cell.family.build_model(config, traffic).config
    assert (model.hidden_size, model.num_heads, model.num_kv_heads,
            model.head_dim, model.ffn_size, model.vocab_size, model.window,
            model.ssm_channels, model.ssm_state, model.dt_rank,
            model.ssm_conv, model.first_layer) == (
                2560, 40, 20, 64, 10240, 25008, 512, 5120, 16, 160, 4, 14)
    assert model.kinds == ("mamba", "swa", "mamba", "full", "gmu", "cross")
    # ids come from the slice, every position carries a label
    tokens, labels = cell.family.make_batch(
        np.random.default_rng(0), 2, config, traffic)
    assert tokens.shape == labels.shape == (2, 8192)
    assert np.array_equal(tokens[:, 1:], labels[:, :-1])
    assert 25008 * 0.99 < tokens.max() < 25008 and labels.min() >= 0


def test_published_layout_and_parameter_counts(cell):
    """697.07M parameters at the cut; the whole model by the same layout,
    3.852B against the published 3.8B, bears the layout out."""
    fam, config = cell.family, cell.config
    whole = {**config, "layers_held": list(range(32))}
    kinds = fam.kinds_held(whole)
    assert [kinds.count(k) for k in ("mamba", "swa", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert [i for i, k in enumerate(kinds) if k == "mamba"] == list(
        range(0, 17, 2))
    assert kinds.index("full") == 17 and kinds[18:20] == ("gmu", "cross")
    assert fam.segments(whole) == ((("mamba", "swa"), 8),
                                   (("mamba", "full"), 1),
                                   (("gmu", "cross"), 7))
    mlp = 3 * H * 10240
    mamba = (H * 2 * E + E * (160 + 2 * N) + 160 * E + E * H    # matmuls
             + 4 * E + E + E + E * N + E)          # taps, 2 biases, A, D
    attn = 2 * H * 2560 + 2 * H * 1280 + 4 * 64 + 128
    per_kind = {"mamba": mamba, "swa": attn, "full": attn,
                "gmu": 2 * H * E, "cross": 2 * H * 2560 + 4 * 64 + 128}
    norms = 4 * H
    by_hand = (sum(per_kind[k] + mlp + norms
                   for k in fam.kinds_held(config)) + 25008 * H + 2 * H)
    assert fam.parameters(config) == by_hand == 697_073_792
    assert fam.parameters(whole, 200064) == 3_852_457_984
    assert [round((per_kind[k] + mlp + norms) / 1e6, 1) for k in
            ("mamba", "swa", "gmu", "cross")] == [119.9, 98.3, 104.9, 91.8]
    # and the program's own tree at these sizes, never materialised
    model = fam.build_model(config, cell.traffic)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in
               jax.tree_util.tree_leaves(shapes)) == 697_073_792


def test_flops_per_token_by_hand(cell):
    """Body 6 x 632.75M matmul parameters; full and cross attention 2 layers
    x 3 x (2 x 64 + 2 x 128) x 40 heads over the 8192 x 8193 / 2 causal
    pairs; the window layer over 8192 x 512 - 512 x 511 / 2; head 6 x 2560 x
    25,008."""
    got = cell.family.flops_per_token(cell.config, cell.traffic)
    mm = {"mlp": 3 * H * 10240,
          "mamba": 2 * H * E + E * 192 + 160 * E + E * H,
          "attn": 2 * H * 2560 + 2 * H * 1280, "gmu": 2 * H * E,
          "cross": 2 * H * 2560}
    body = 6 * (2 * mm["mamba"] + 2 * mm["attn"] + mm["gmu"] + mm["cross"]
                + 6 * mm["mlp"])
    assert got["body"] == body
    per_pair = 3 * (2 * 64 + 2 * 128) * 40
    assert got["attention"] == 2 * per_pair * (8192 * 8193 // 2) / 8192
    assert got["window"] == per_pair * (8192 * 512 - 512 * 511 // 2) / 8192
    assert got["head"] == 6 * H * 25008
    assert [round(got[k] / G, 2) for k in ("body", "attention", "window",
                                           "head", "total")] == [
        3.80, 0.38, 0.02, 0.38, 4.58]
    assert got["total"] == sum(got[k] for k in ("body", "attention",
                                                "window", "head"))
    for policy in ("selective", "full", "dots", None):
        other = copy.deepcopy(cell.config)
        other["job"]["activation_checkpointing"] = policy
        assert cell.family.flops_per_token(other, cell.traffic) == got
    assert cell.family.attention_call(cell.config, cell.traffic) == {
        "rows": 1, "seq": 8192, "heads": 40, "head_dim": 64, "causal": True,
        "itemsize": 2}
    assert cell.family.loss_ceiling(cell.config) == pytest.approx(
        1.5 * np.log(25008))


def test_scan_and_window_costs_by_hand(cell):
    fam, config, traffic = cell.family, cell.config, cell.traffic
    wide, narrow, small = T * E * 2, T * N * 2, 4 * (E * N + E)
    ops = 7 * T * E * N
    assert fam.scan_cost(config, traffic, "fwd") == (
        ops, 3 * wide + 2 * narrow + small)
    assert fam.scan_cost(config, traffic, "bwd") == (
        3 * ops, 5 * wide + 4 * narrow + 2 * small)
    # about a quarter of a gigabyte forward, memory-bound by a wide margin
    fwd_ops, fwd_bytes = fam.scan_cost(config, traffic, "fwd")
    assert fwd_bytes / G == pytest.approx(0.2525, abs=5e-4)
    peaks = cells.peaks("TPU v5 lite")
    seconds, bound = flops.roofline_seconds(fwd_ops, fwd_bytes, peaks)
    assert bound == "memory" and seconds == pytest.approx(308.3e-6, rel=1e-3)
    assert flops.roofline_seconds(*fam.scan_cost(config, traffic, "bwd"),
                                  peaks)[1] == "memory"

    pairs = 40 * (T * 512 - 512 * 511 // 2)
    assert fam.allowed_pairs(T, 512) == T * 512 - 512 * 511 // 2
    assert fam.allowed_pairs(T) == T * (T + 1) // 2
    assert fam.allowed_pairs(256, 512) == 256 * 257 // 2
    q, kv, out, lse = T * 40 * 64 * 2, 2 * T * 20 * 64 * 2, T * 40 * 128 * 2, \
        T * 40 * 4
    assert fam.window_attention_cost(config, traffic, "fwd") == (
        2 * pairs * (64 + 128), q + kv + out + lse)
    assert fam.window_attention_cost(config, traffic, "bwd") == (
        2 * pairs * (3 * 64 + 2 * 128), 2 * (q + kv + out) + lse)
    # the kernel visits 31 of the causal triangle's 136 tile pairs; the
    # in-window pairs are an eighth of the causal ones
    assert fam.allowed_pairs(T, 512) / fam.allowed_pairs(T) == pytest.approx(
        0.1211, abs=1e-3)
    with pytest.raises(ValueError, match="direction"):
        fam.scan_cost(config, traffic, "both")
    with pytest.raises(ValueError, match="direction"):
        fam.window_attention_cost(config, traffic, "both")
    # the full and the cross-decoder layers' call: the same tensors, the
    # whole causal triangle — 8.26 times the window's operations
    causal = 40 * (T * (T + 1) // 2)
    assert fam.full_attention_cost(config, traffic, "fwd") == (
        2 * causal * (64 + 128), q + kv + out + lse)
    assert fam.full_attention_cost(config, traffic, "bwd") == (
        2 * causal * (3 * 64 + 2 * 128), 2 * (q + kv + out) + lse)
    assert fam.full_attention_cost(config, traffic, "fwd")[0] / G == \
        pytest.approx(515.5, abs=0.1)
    assert causal / pairs == pytest.approx(8.26, abs=0.01)


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_of_the_cell_ends_in_a_well_formed_line(trace):
    proc = run(["--workload", CELL, "--seed", "3100000007", "--seconds", "1",
                "--trace", trace, "--rehearse-cpu"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert LINE_KEYS <= set(line)
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert "check reference:" in proc.stdout and "'ok': True" in \
        proc.stdout.split("check reference:")[1].splitlines()[0]
    assert "check warmup_loss_drop:" in proc.stdout
    assert "check no_compile_in_window: {'compile_requests': 0, 'ok': True}" \
        in proc.stdout


# ---------------------------------------------- the readers, by hand
# benchmark/testdata/two_steps.xplane.pb (test_bench_trace.py draws it): one
# step on chip 0, self times in microseconds, under a map that places the
# instructions in a hybrid stack's scopes (closed_call.3 and checkpoint.5
# are Pallas calls):
#
#     fusion.1             100   dstpu/ssm     forward
#     while.2               20   dstpu/scan    forward   (the loop's own)
#     closed_call.3    2 x 100   dstpu/swa     forward   Pallas
#     fusion.4         2 x 190   dstpu/scan    replay
#     checkpoint.5          50   dstpu/swa     backward  Pallas
#     all-gather-start.6    10   dstpu/swa     forward   (a layout copy)
#     fusion.7              40   dstpu/conv    backward
#     all-gather-done.6     50   dstpu/gmu     forward
#     all-reduce.8         100   dstpu/gmu     backward  (150 on chip 1)

PB = os.path.join(cells.ROOT, "benchmark", "testdata", "two_steps.xplane.pb")
HYBRID_MAP = {
    "fusion.1": ("dstpu/ssm", "forward"),
    "while.2": ("dstpu/scan", "forward"),
    "closed_call.3": ("dstpu/swa", "forward"),
    "fusion.4": ("dstpu/scan", "replay"),
    "checkpoint.5": ("dstpu/swa", "backward"),
    "all-gather-start.6": ("dstpu/swa", "forward"),
    "fusion.7": ("dstpu/conv", "backward"),
    "all-gather-done.6": ("dstpu/gmu", "forward"),
    "all-reduce.8": ("dstpu/gmu", "backward"),
}


def least_ms(cell, cost):
    peaks = cells.peaks("TPU v5 lite")
    return 1e3 * sum(flops.roofline_seconds(
        *cost(cell.config, cell.traffic, d), peaks)[0] for d in ("fwd", "bwd"))


def expected(cell):
    fam = cell.family
    return {
        # fusion.1 + while.2 + fusion.4 twice + fusion.7
        "ssm_ms_per_step": 0.54,
        "ssm_scan_ms_per_step": 0.40,
        # two Mamba layers' forward + backward scans over 0.40 ms a step
        "ssm_scan_roofline": 100 * 2 * least_ms(cell, fam.scan_cost) / 0.40,
        # the two kernels and the copy
        "window_attn_ms_per_step": 0.26,
        # one windowed layer over the kernels' 0.25 ms a step, copy left out
        "window_attn_roofline": 100 * least_ms(
            cell, fam.window_attention_cost) / 0.25,
        # chip 1: all-gather-done.6 50 us + all-reduce.8 150 us
        "gmu_ms_per_step": 0.20,
    }


@pytest.fixture(scope="module")
def record(cell):
    trace = tr.load(PB)
    return types.SimpleNamespace(
        cell=cell, peaks=cells.peaks("TPU v5 lite"), steps=2, trace=trace,
        steady=tr.steady(trace, 2), scope_map=HYBRID_MAP)


@pytest.mark.parametrize("name", [
    "ssm_ms_per_step", "ssm_scan_ms_per_step", "ssm_scan_roofline",
    "window_attn_ms_per_step", "window_attn_roofline", "gmu_ms_per_step"])
def test_hybrid_reader_by_hand(cell, record, name):
    read = cells.plugin(cells.ROOT, "metrics", name).read
    assert read(record) == pytest.approx(expected(cell)[name], rel=1e-9)
    # a program of another model has the map but no such scope: 0 for a
    # time, nothing for a share; a run without a trace says nothing
    other = {k: ("dstpu/block", p) for k, (_, p) in HYBRID_MAP.items()}
    elsewhere = read(types.SimpleNamespace(
        **{**vars(record), "scope_map": other}))
    assert elsewhere == (None if name.endswith("roofline") else 0.0)
    assert read(types.SimpleNamespace(**{**vars(record), "steady": []})) \
        is None


def test_full_attention_reader_by_hand(cell, record):
    """The Pallas calls whose innermost scope is ``dstpu/attn`` (the full
    layer's) or ``dstpu/xattn`` (a cross-decoder layer's), and not the
    windowed ones: closed_call.3 under ``attn`` and checkpoint.5 under
    ``xattn`` are 0.25 ms a step for two layers' forward and backward
    calls; with checkpoint.5 under ``swa`` the 0.20 ms that are left."""
    fam = cell.family
    read = cells.plugin(cells.ROOT, "metrics", "full_attn_roofline").read
    assert read(record) is None            # both calls are windowed there
    least = 2 * least_ms(cell, fam.full_attention_cost)
    for xattn, spent in ((("dstpu/xattn", "backward"), 0.25),
                         (("dstpu/swa", "backward"), 0.20)):
        names = {**HYBRID_MAP, "closed_call.3": ("dstpu/attn", "forward"),
                 "checkpoint.5": xattn}
        got = read(types.SimpleNamespace(**{**vars(record),
                                            "scope_map": names}))
        assert got == pytest.approx(100 * least / spent, rel=1e-9)
    # compute-bound: 2 x 40 x 33.6M pairs x (192 + 448) over the bf16 peak
    assert least / 2 == pytest.approx(
        2 * 40 * (T * (T + 1) // 2) * 640 / 197e12 * 1e3, rel=1e-9)
    assert read(types.SimpleNamespace(**{**vars(record), "steady": []})) \
        is None


def test_the_scan_reads_a_third_of_a_millisecond_forward(cell):
    """The numerators in plain numbers: a forward scan 0.308 ms, a backward
    0.514 ms (memory); a windowed forward 0.317 ms (compute), a backward
    7 / 3 of it."""
    fam, peaks = cell.family, cells.peaks("TPU v5 lite")
    least = {(cost.__name__, d): 1e3 * flops.roofline_seconds(
        *cost(cell.config, cell.traffic, d), peaks)[0]
        for cost in (fam.scan_cost, fam.window_attention_cost)
        for d in ("fwd", "bwd")}
    assert least["scan_cost", "fwd"] == pytest.approx(0.3083, abs=1e-3)
    assert least["scan_cost", "bwd"] == pytest.approx(0.5143, abs=1e-3)
    assert least["window_attention_cost", "fwd"] == pytest.approx(
        2 * 40 * (T * 512 - 512 * 511 // 2) * 192 / 197e12 * 1e3, rel=1e-9)
    assert 0.3 < least["window_attention_cost", "bwd"] / least[
        "window_attention_cost", "fwd"] / 7 < 0.4       # 448 / 192 = 2.33


def test_the_seven_entries_belong_to_the_cell_alone(cell):
    """The issue's six readers and, after review, ``full_attn_roofline``
    for the kernels that take most of the Pallas time."""
    man = cells.manifest()
    names = ["ssm_ms_per_step", "ssm_scan_ms_per_step", "ssm_scan_roofline",
             "window_attn_ms_per_step", "window_attn_roofline",
             "gmu_ms_per_step", "full_attn_roofline"]
    assert [m["name"] for m in man["per_layer"][-7:]] == names
    for entry in man["per_layer"][-7:]:
        share = entry["name"].endswith("roofline")
        assert entry["workloads"] == [CELL]
        assert (entry["source"], entry["moves"], entry["unit"],
                entry["better"], entry["layer"]) == (
            "program_span", "tokens_per_s_per_chip", "%" if share else "ms",
            "higher" if share else "lower", "kernels" if share else "model")
    reported = {m["name"] for m in cell.per_layer}
    assert set(names) <= reported
    assert {"head_ms_per_step", "norm_ms_per_step", "scoped_share",
            "remat_replay_share", "attn_kernel_ms_per_step",
            "peak_hbm_gb"} <= reported
    assert "attn_kernel_roofline" not in reported
    for old in ("gpt2-xl.1chip", "ouro-2.6b.loop4-seq4096"):
        assert not set(names) & {m["name"] for m in
                                 cells.load(old).per_layer}


# ------------------------------------------------- a slice of the vocabulary

def test_a_slice_of_the_vocabulary_is_a_smaller_vocabulary(cell):
    """Eight chips share the table by rows: their logits side by side are
    the whole table's, and the loss a chip's slice trains on is the
    cross-entropy over that slice's rows alone."""
    fam = cell.family
    config = {**fam.tiny(cell.config), "rehearsal_seq": 32}
    rows = config["vocab_held"]
    assert config["vocab_size"] == 8 * rows
    model = fam.build_model(config, {"seq": 32})
    params = model.init_params(jax.random.PRNGKey(0))
    whole = 0.02 * jax.random.normal(jax.random.PRNGKey(1),
                                     (8 * rows, config["hidden_size"]))
    hidden = jax.random.normal(jax.random.PRNGKey(2),
                               (2, 32, config["hidden_size"]))
    side_by_side = jnp.concatenate(
        [hidden @ whole[i * rows:(i + 1) * rows].T for i in range(8)], -1)
    np.testing.assert_allclose(side_by_side, hidden @ whole.T, rtol=1e-5,
                               atol=1e-6)
    # the model on slice 3 of the table: ids of the slice, its rows' logits
    params = {**params, "wte": whole[3 * rows:4 * rows]}
    batch = fam.make_batch(np.random.default_rng(0), 2, config, {"seq": 32})
    assert batch[0].max() < rows
    mesh = make_mesh(devices=jax.devices()[:1])
    loss = jax.jit(jax.shard_map(
        lambda p, *b: model.apply(p, *b), mesh=mesh, in_specs=(P(),) * 3,
        out_specs=P(), check_vma=False))(params, *batch)
    ref = jax.jit(lambda p: fam.reference_loss(p, batch, config))(params)
    assert float(loss) == pytest.approx(float(ref), rel=1e-5)
    assert abs(float(loss) - np.log(rows)) < 0.1       # not ln(8 x rows)


# ------------------------------------------------------- the reference

def test_reference_imports_nothing_of_the_program():
    path = os.path.join(cells.ROOT, "benchmark", "reference", "phi4flash.py")
    with open(path) as f:
        source = f.read()
    imports = [line for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports and not any("deepspeed_tpu" in line for line in imports)
    assert 'default_matmul_precision("highest")' in source
    for stated in ("NO positional encoding", "lambda_init(i) =",
                   "h_t =", "before\n  the gate", "t - 512 < s <= t"):
        assert stated in source, stated
    family = os.path.join(cells.ROOT, "benchmark", "families", "phi4flash.py")
    with open(family) as f:
        top = [line for line in f.read().splitlines()
               if line.startswith(("import ", "from "))]
    assert not any("deepspeed_tpu" in line for line in top)


def test_lower_precision_moves_the_reference_loss(cell):
    fam = cell.family
    config = {**fam.tiny(cell.config), "rehearsal_seq": 32}
    model = fam.build_model(config, {"seq": 32})
    params = model.init_params(jax.random.PRNGKey(0))
    batch = fam.make_batch(np.random.default_rng(0), 2, config, {"seq": 32})
    loss = jax.jit(lambda p, **kw: fam.reference_loss(p, batch, config, **kw),
                   static_argnames=("operand_bits", "dtype"))
    exact = float(loss(params))
    d7 = abs(float(loss(params, operand_bits=7)) - exact)
    d3 = abs(float(loss(params, operand_bits=3)) - exact)
    assert 0 < d7 < d3
    assert abs(float(loss(params, dtype=jnp.bfloat16)) - exact) > 0
