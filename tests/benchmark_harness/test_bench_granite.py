"""The ``granite_hybrid`` family and its cell ``granite-4.0-h-micro.seq8192``:
the configuration file against the catalog row, its cut and assumptions
written out, parameter counts, required FLOPs and ``ssd_cost`` by hand, the
three readers on a hand-made record (asked for BY NAME, not by their place
in ``BENCHMARK.json``, and listed for this cell alone), the CPU rehearsal
of the cell, and the plain reference's own contract.  (The reference
against the program's model, loss and every gradient:
tests/test_ssd_hybrid_model.py; the op against the recurrence:
tests/test_ssd.py.)"""

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

CELL = "granite-4.0-h-micro.seq8192"
CONFIG = "granite-4.0-h-micro-l10"
G = 1e9
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
NEW = {"mamba2_ms_per_step": ("ms", "lower", "model"),
       "ssd_ms_per_step": ("ms", "lower", "model"),
       "ssd_roofline": ("%", "higher", "kernels")}
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
#: the catalog row's ``config``, key for key
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": PERIOD * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352}


@pytest.fixture(scope="module")
def cell():
    return cells.load(CELL)


def entry(man, key, name):
    found = [x for x in man[key] if x["name"] == name]
    assert len(found) == 1, name
    return found[0]


# ------------------------------------------------------- the configuration

def test_configuration_keeps_every_key_of_the_catalog_row(cell):
    assert {k: cell.config[k] for k in PUBLISHED} == PUBLISHED
    got = entry(cells.manifest(), "configs", CONFIG)
    assert got["source"] == ("https://huggingface.co/ibm-granite/"
                             "granite-4.0-h-micro/blob/main/config.json")
    assert cell.config["source"] == got["source"]
    assert got["reduced"] == ["layers_held", "vocab_held"]


def test_the_cut_and_the_assumptions_are_written_out(cell):
    """``reduced`` names the two cuts and nothing else, each with the
    published value it keeps; the deployment says how the chips share the
    model; ``assumed`` has everything the row does not give."""
    c = cell.config
    assert c["layers_held"] == list(range(10))
    assert c["vocab_held"] == 12544 == c["vocab_size"] // 8 == 98 * 128
    assert set(c["reduced"]) == {"layers_held", "vocab_held"}
    assert "num_hidden_layers stays 40" in c["reduced"]["layers_held"]
    assert "vocab_size stays 100352" in c["reduced"]["vocab_held"]
    assert "eight chips share the tied table by rows" in c["deployment"]
    assert "772.16M" in c["deployment"]
    assert {"initializer_range", "ssm_init", "ssd_chunk", "dropout",
            "gated_norm", "column_order"} <= set(c["assumed"])
    assert c["assumed"]["initializer_range"] == 0.02
    job = c["job"]
    assert (job["precision"], job["activation_checkpointing"],
            job["gradient_clipping"]) == ("bf16", "full", 1.0)
    assert job["optimizer"] == {"type": "Adam", "params": {"lr": 1e-05}}
    for name in ("loss_tolerance", "first_update_limit",
                 "warmup_loss_drop_share"):
        assert c["checks"][name] > 0 and c["checks"][name + "_why"]


def test_the_layout_is_read_as_published(cell):
    fam = cell.family
    assert fam.kinds_held(cell.config) == (("mamba2",) * 5 + ("attention",)
                                           + ("mamba2",) * 4)
    assert fam.segments(cell.config) == ((("mamba2",), 5),
                                         (("attention",), 1),
                                         (("mamba2",), 4))
    assert fam.multipliers(cell.config) == (12.0, 0.22, 0.015625, 8.0)
    model = fam.build_model(cell.config, cell.traffic)
    cfg = model.config
    assert (cfg.vocab_size, cfg.hidden_size, cfg.ffn_size, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim) == (12544, 2048, 8192, 32, 8, 64)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv, cfg.norm_eps) == (64, 64, 128, 1, 4, 1e-5)
    with pytest.raises(ValueError, match="no positions"):
        fam.build_model({**cell.config, "position_embedding_type": "rope"},
                        cell.traffic)


def test_parameter_counts_by_hand(cell):
    """A Mamba-2 layer 76,182,976 (in_proj 2048 x 8512, the convolution 4 x
    4352 with its bias, A_log / D / dt_bias, the gated norm, out_proj 4096 x
    2048, the MLP 3 x 2048 x 8192, two norms); the attention layer
    60,821,504; the period 746.47M; with the eighth of the table 772.16M,
    13.90 GB at 18 B/param; with the whole table 951.99M."""
    fam = cell.family
    mamba = (2048 * 8512 + 5 * 4352 + 3 * 64 + 4096 + 4096 * 2048
             + 3 * 2048 * 8192 + 2 * 2048)
    attention = (2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192
                 + 2 * 2048)
    assert (mamba, attention) == (76_182_976, 60_821_504)
    period = 9 * mamba + attention
    assert period == 746_468_288
    assert fam.parameters(cell.config) == period + 12544 * 2048 + 2048 \
        == 772_160_448
    assert 18 * fam.parameters(cell.config) / G == pytest.approx(13.90,
                                                                 abs=0.005)
    assert fam.parameters(cell.config, vocab_rows=100352) / G == \
        pytest.approx(0.95199, abs=1e-5)


def test_flops_per_token_by_hand(cell):
    """4.79 GFLOP a token: the Mamba-2 projections 1.394, the MLPs 3.020,
    the attention projections 0.063, the head 0.154, the causal core 0.101
    at T 8,192, the recurrence 0.057 (4 N P a head and token, x 3)."""
    parts = cell.family.flops_per_token(cell.config, cell.traffic)
    want = {"mamba2": 6 * 9 * (2048 * 8512 + 4096 * 2048),
            "mlp": 6 * 10 * 3 * 2048 * 8192,
            "projections": 6 * (2 * 2048 * 2048 + 2 * 2048 * 512),
            "head": 6 * 2048 * 12544,
            "attention": 12 * 64 * 32 * (8192 + 1) / 2,
            "ssd": 3 * 9 * 4 * 128 * 64 * 64}
    for name, value in want.items():
        assert parts[name] == pytest.approx(value, rel=1e-12), name
    assert parts["total"] == pytest.approx(sum(want.values()), rel=1e-12)
    assert parts["total"] / G == pytest.approx(4.789, abs=5e-4)
    assert (parts["mamba2"] / G, parts["mlp"] / G) == pytest.approx(
        (1.394, 3.020), abs=5e-4)


def test_ssd_cost_by_hand(cell):
    """At the published chunk of 256, per token: the group's scores ``2 C
    N`` and per head the masked scores ``2 C P``, the write and the read
    ``4 N P``; backward twice that.  Bytes: x and y in bf16 (4,096 wide),
    B and C (128 each), dt in float32 (64); backward x, y, dy, dx; B, C and
    their gradients; dt and its gradient."""
    fam, T = cell.family, 8192
    per_token = 2 * 256 * 128 + 64 * (2 * 256 * 64 + 4 * 128 * 64)
    fwd, fwd_bytes = fam.ssd_cost(cell.config, cell.traffic, "fwd")
    bwd, bwd_bytes = fam.ssd_cost(cell.config, cell.traffic, "bwd")
    assert fwd == T * per_token and bwd == 2 * fwd
    assert fwd_bytes == T * (2 * 4096 * 2 + 2 * 128 * 2 + 64 * 4)
    assert bwd_bytes == T * (4 * 4096 * 2 + 4 * 128 * 2 + 2 * 64 * 4)
    peaks = cells.peaks("TPU v5 lite")
    seconds, bound = flops.roofline_seconds(fwd + bwd, fwd_bytes + bwd_bytes,
                                            peaks)
    assert bound == "compute"
    assert 9 * seconds * 1e3 == pytest.approx(4.78, abs=0.01)  # a step
    with pytest.raises(ValueError, match="direction"):
        fam.ssd_cost(cell.config, cell.traffic, "both")


# ---------------------------------------------- the readers, by hand
# benchmark/testdata/two_steps.xplane.pb (test_bench_trace.py draws it):
# self times a step in microseconds, on chip 0, under a map that places the
# instructions in this stack's scopes:
#
#     fusion.1             100   dstpu/mamba2   forward   (a projection)
#     while.2               20   dstpu/ssd      forward   (the chunk walk)
#     closed_call.3    2 x 100   dstpu/attn     forward   Pallas (the core)
#     fusion.4         2 x 190   dstpu/ssd      replay
#     checkpoint.5          50   dstpu/conv     backward
#     all-gather-start.6    10   dstpu/ssd      backward
#     fusion.7              40   dstpu/ffn      backward
#     all-gather-done.6     50   dstpu/norm     forward
#     all-reduce.8         100   dstpu/mamba2   backward  (150 on chip 1)
#
# and a program whose gauges say nine Mamba-2 layers of 64 chunks a row.

PB = os.path.join(cells.ROOT, "benchmark", "testdata", "two_steps.xplane.pb")
SCOPE_MAP = {
    "fusion.1": ("dstpu/mamba2", "forward"),
    "while.2": ("dstpu/ssd", "forward"),
    "closed_call.3": ("dstpu/attn", "forward"),
    "fusion.4": ("dstpu/ssd", "replay"),
    "checkpoint.5": ("dstpu/conv", "backward"),
    "all-gather-start.6": ("dstpu/ssd", "backward"),
    "fusion.7": ("dstpu/ffn", "backward"),
    "all-gather-done.6": ("dstpu/norm", "forward"),
    "all-reduce.8": ("dstpu/mamba2", "backward"),
}
GAUGES = {"layers_mamba2": 9, "layers_attention": 1,
          "layer_applications": 10, "kv_group": 4, "ssd_chunk": 128,
          "ssd_chunks_per_row": 64}


def expected(cell):
    peaks = cells.peaks("TPU v5 lite")
    least_ms = 1e3 * sum(flops.roofline_seconds(
        *cell.family.ssd_cost(cell.config, cell.traffic, d), peaks)[0]
        for d in ("fwd", "bwd"))
    return {
        # chip 1: the two mamba2 instructions (100 + 150), the three ssd
        # ones (20 + 380 + 10) and the convolution (50)
        "mamba2_ms_per_step": 0.71,
        "ssd_ms_per_step": 0.41,
        # first chip: 0.41 ms a step under ssd for nine layers' ops
        "ssd_roofline": 100 * 9 * least_ms / 0.41,
    }


@pytest.fixture(scope="module")
def record(cell):
    trace = tr.load(PB)
    return types.SimpleNamespace(
        cell=cell, peaks=cells.peaks("TPU v5 lite"), steps=2, trace=trace,
        steady=tr.steady(trace, 2), scope_map=SCOPE_MAP, gauges=GAUGES)


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_by_hand(cell, record, name):
    read = cells.plugin(cells.ROOT, "metrics", name).read
    assert read(record) == pytest.approx(expected(cell)[name], rel=1e-9)
    # a program of another model has the map but no such scope: 0 for a
    # time, nothing for a share
    other = {k: ("dstpu/block", p) for k, (_, p) in SCOPE_MAP.items()}
    elsewhere = read(types.SimpleNamespace(
        **{**vars(record), "scope_map": other}))
    assert elsewhere == (None if name == "ssd_roofline" else 0.0)
    # a run without a trace: nothing
    bare = read(types.SimpleNamespace(**{**vars(record), "steady": [],
                                         "scope_map": None}))
    assert bare is None


@pytest.mark.parametrize("gauges", [{}, {"layers_mamba2": 9},
                                    {"ssd_chunks_per_row": 64}])
def test_the_roofline_says_nothing_without_the_programs_gauges(record,
                                                               gauges):
    """A program with no gauges (the parent's, laid under these files), or
    one that holds no Mamba-2 layer or walks no chunk: nothing, no error.
    The layers are the gauge's, not this family's count of kind names."""
    read = cells.plugin(cells.ROOT, "metrics", "ssd_roofline").read
    assert read(types.SimpleNamespace(**{**vars(record),
                                         "gauges": gauges})) is None
    half = read(types.SimpleNamespace(**{**vars(record), "gauges": {
        **GAUGES, "layers_mamba2": 3}}))
    assert half == pytest.approx(read(record) / 3, rel=1e-12)


def test_the_three_entries_are_asked_for_by_name(cell):
    """Each entry found by its name, with its unit, direction, source and
    layer, listed for this cell alone; the cell reports the end-to-end
    metrics every cell reports and the accepted readers with no list."""
    man = cells.manifest()
    for name, (unit, better, layer) in NEW.items():
        got = entry(man, "per_layer", name)
        assert got == {"name": name, "unit": unit, "better": better,
                       "source": "program_span", "layer": layer,
                       "moves": "tokens_per_s_per_chip",
                       "workloads": [CELL]}
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= reported
    assert {"head_ms_per_step", "norm_ms_per_step", "scoped_share",
            "remat_replay_share", "attn_kernel_ms_per_step", "peak_hbm_gb",
            "optimizer_ms_per_step", "device_ops_per_step"} <= reported
    assert not {"ssm_ms_per_step", "delta_rule_roofline",
                "gdn_ms_per_step"} & reported
    for other in man["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW) & {
                m["name"] for m in cells.load(other["name"]).per_layer}
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s_per_chip", "mfu", "setup_s"}
    work = entry(man, "workloads", CELL)
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, "lm-seq8192-mb1-update", 1)
    assert (cell.traffic["kind"], cell.traffic["seq"],
            cell.traffic["micro_batch"], cell.traffic["gas"]) == (
        "train_steps_update", 8192, 1, 1)


# ------------------------------------------------------------- rehearsal

def test_rehearsal_of_the_cell_is_correct_and_well_formed():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # the rehearsal asks for its own devices
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL, "--seed",
         "4300000007", "--seconds", "1", "--trace", "0", "--rehearse-cpu"],
        cwd=cells.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
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
    assert "'leaves': 34" in proc.stdout     # 2 + 2 x 12 + 8


# ------------------------------------------------------------ the reference

def test_reference_has_a_plain_recurrence_and_imports_nothing_of_the_program():
    path = os.path.join(cells.ROOT, "benchmark", "reference",
                        "granite_hybrid.py")
    code = open(path).read().split('"""', 2)[2]   # past the module docstring
    imports = [line for line in code.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == [
        "import functools", "import jax", "import jax.numpy as jnp",
        "from benchmark.reference.kimi_moe import first_adam_step, matmul, "
        "rounded"]
    for word in ("deepspeed_tpu", "pallas", "chunk", "_pair_decay"):
        assert word not in code, word
    assert "jax.lax.scan(step, S, xs)" in code        # one step at a time
    assert "key_pos[None, :] <=" in code               # the causal triangle
    assert 'jax.default_matmul_precision("highest")' in code


def test_lower_precision_moves_the_reference_loss(cell):
    """What ``--probe-reference`` prints, at the tiny size: bf16 storage and
    fp8-wide operands move the loss, and more than float32 noise does."""
    import jax.numpy as jnp
    fam = cell.family
    config = fam.tiny(cell.config)
    model = fam.build_model(config, {"seq": 128})
    params = model.init_params(jax.random.PRNGKey(0))
    batch = fam.make_batch(np.random.default_rng(0), 2, config, {"seq": 128})
    loss = jax.jit(lambda p, **kw: fam.reference_loss(p, batch, config, **kw),
                   static_argnames=("dtype", "operand_bits"))
    exact = float(loss(params))
    assert exact == float(loss(params))
    stored = float(loss(params, dtype=jnp.bfloat16))
    coarse = float(loss(params, operand_bits=3))
    assert abs(stored - exact) > 1e-6
    assert abs(coarse - exact) > abs(stored - exact)
    assert abs(exact - np.log(512)) < 0.1
