"""The ``ouro`` family and its cell ``ouro-2.6b.loop4-seq4096``: the plain
reference against the program's looped model (loss AND gradients, tiny
preset, float32, CPU), the configuration file against the published keys,
required FLOPs by hand, the CPU rehearsal of the cell, and the three readers
over the loop's scopes on a synthetic record."""

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
from benchmark import trace_reduce as tr
from deepspeed_tpu.parallel.topology import make_mesh

CELL = "ouro-2.6b.loop4-seq4096"
G = 1e9
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


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


@pytest.fixture(scope="module")
def case(cell):
    """(family, tiny config, params with every leaf moved, batch, the
    program's loss and gradients through the model's own ``apply``)."""
    family, config = cell.family, cell.family.tiny(cell.config)
    traffic = {"seq": 64}
    model = family.build_model(config, {"seq": 64})
    assert (model.config.loop_passes, model.config.num_layers) == (4, 2)
    params = model.init_params(jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = treedef.unflatten(
        [x + 0.05 * jax.random.normal(k, x.shape) for x, k in
         zip(leaves, keys)])
    config = {**config, "rehearsal_seq": traffic["seq"]}
    batch = family.make_batch(np.random.default_rng(0), 4, config, traffic)
    assert batch[0].shape == (4, 64)
    mesh = make_mesh(devices=jax.devices()[:1])

    @jax.jit
    def program(p):
        return jax.shard_map(
            lambda p, *b: model.apply(p, *b), mesh=mesh,
            in_specs=(P(),) * (1 + len(batch)), out_specs=P(),
            check_vma=False)(p, *batch)

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(program)(params)
    return family, config, params, batch, float(loss), grads


def test_reference_loss_agrees_with_the_model(case):
    family, config, params, batch, loss, _ = case
    ref = float(jax.jit(lambda p: family.reference_loss(p, batch, config))(
        params))
    assert ref == pytest.approx(loss, rel=1e-5)


def test_reference_gradients_agree_with_the_model(case):
    family, config, params, batch, _, grads = case
    ref = jax.jit(jax.grad(
        lambda p: family.reference_loss(p, batch, config)))(params)
    worst = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-30)), grads, ref)
    # float32 sums in another order: 1e-5 of a leaf's largest gradient
    assert max(jax.tree_util.tree_leaves(worst)) < 1e-5, worst
    # every leaf takes part: the gate, the untied head and all four norms
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree_util.tree_leaves(ref))


def test_lower_precision_moves_the_reference_loss(case):
    family, config, params, batch, _, _ = case
    loss = jax.jit(lambda p, **kw: family.reference_loss(
        p, batch, config, **kw), static_argnames=("operand_bits", "dtype"))
    exact = float(loss(params))
    d7 = abs(float(loss(params, operand_bits=7)) - exact)
    d3 = abs(float(loss(params, operand_bits=3)) - exact)
    assert 0 < d7 < d3
    assert abs(float(loss(params, dtype=jnp.bfloat16)) - exact) > 0


def test_reference_imports_nothing_of_the_program():
    path = os.path.join(cells.ROOT, "benchmark", "reference", "ouro.py")
    with open(path) as f:
        source = f.read()
    imports = [line for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports and not any("deepspeed_tpu" in line for line in imports)
    assert 'default_matmul_precision("highest")' in source


def test_configuration_keeps_every_published_key(cell):
    """The catalog's ``config`` of Ouro-2.6B, key for key; the one cut is
    ``layers_held``, and every assumption the issue names is written down."""
    config = cell.config
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_hidden_layers": 48,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4,
        "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": 49152}
    assert {k: config[k] for k in published} == published
    assert config["layer_types"] == ["full_attention"] * 48
    assert list(config["reduced"]) == ["layers_held"]
    assert 6 <= config["layers_held"] <= 9
    for key in ("sandwich_norm", "exit_gate", "exit_entropy_weight",
                "dropout", "loop_carry", "initializer_range"):
        assert key in config["assumed"], key
    assert "learning_rate_why" in config["job"]
    entry = next(c for c in cells.manifest()["configs"]
                 if c["name"] == cell.config_name)
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json")
    assert (cell.traffic["seq"], cell.traffic["micro_batch"],
            cell.traffic["gas"], cell.traffic["batch_pool"],
            cell.traffic["warmup_steps"]) == (4096, 1, 1, 8, 3)
    model = cell.family.build_model(cell.config, cell.traffic).config
    assert (model.hidden_size, model.num_heads, model.head_dim,
            model.ffn_size, model.vocab_size, model.loop_passes) == (
                2048, 16, 128, 5632, 49152, 4)
    assert model.num_layers == config["layers_held"]
    # ids come from the whole vocabulary, every position carries a label
    tokens, labels = cell.family.make_batch(
        np.random.default_rng(0), 2, config, cell.traffic)
    assert tokens.shape == labels.shape == (2, 4096)
    assert np.array_equal(tokens[:, 1:], labels[:, :-1])
    assert tokens.max() > 49152 * 0.99 and labels.min() >= 0


def test_flops_per_token_by_hand_at_eight_layers(cell):
    """L = 8: body 6 x 51.38M x 32 applications, attention 12 x 32 x 4096 x
    2048 / 2, head 6 x 100.66M x 4 exits."""
    config = cell.family.with_depth(cell.config, 8)
    got = cell.family.flops_per_token(config, cell.traffic)
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert layer == 51_380_224
    assert got["body"] == 6 * layer * 32
    assert got["attention"] == 12 * 32 * 4096 * 2048 / 2
    assert got["head"] == 6 * 2048 * 49152 * 4
    assert got["body"] / G == pytest.approx(9.865, abs=5e-4)
    assert got["attention"] / G == pytest.approx(1.611, abs=5e-4)
    assert got["head"] / G == pytest.approx(2.416, abs=5e-4)
    assert got["total"] == got["body"] + got["attention"] + got["head"]
    # recomputed work never reaches the numerator
    for policy in ("selective", "full", "dots", None):
        other = copy.deepcopy(config)
        other["job"]["activation_checkpointing"] = policy
        assert cell.family.flops_per_token(other, cell.traffic) == got
    # the depth the cell runs scales body and attention, not the head
    held = cell.family.flops_per_token(cell.config, cell.traffic)
    assert held["head"] == got["head"]
    assert held["body"] == got["body"] * cell.config["layers_held"] / 8
    att = cell.family.attention_call(cell.config, cell.traffic)
    assert att == {"rows": 1, "seq": 4096, "heads": 16, "head_dim": 128,
                   "causal": True, "itemsize": 2}


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_of_the_cell_ends_in_a_well_formed_line(trace):
    proc = run(["--workload", CELL, "--seed", "2600000007", "--seconds", "1",
                "--trace", trace, "--rehearse-cpu"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert LINE_KEYS <= set(line)
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] >= 1 and line["failed"] == 0
    # the checks ran (whether the tiny preset's loss falls by the share the
    # real size's file asks for is not the rehearsal's to say)
    assert "check reference:" in proc.stdout and "'ok': True" in \
        proc.stdout.split("check reference:")[1].splitlines()[0]
    assert "check warmup_loss_drop:" in proc.stdout
    assert "check no_compile_in_window: {'compile_requests': 0, 'ok': True}" \
        in proc.stdout


# ---------------------------------------------- the readers, by hand
# benchmark/testdata/two_steps.xplane.pb (test_bench_trace.py draws it): one
# step on chip 0, self times in microseconds, under a map that places the
# instructions in a looped model's scopes:
#
#     fusion.1             100   dstpu/embed   forward
#     while.2               20   dstpu/loop    backward   (the pass loop's own)
#     closed_call.3    2 x 100   dstpu/attn    forward
#     fusion.4         2 x 190   dstpu/rope    replay
#     checkpoint.5          50   dstpu/exit    backward
#     all-gather-start.6    10   dstpu/head    forward
#     fusion.7              40   dstpu/norm    forward
#     all-gather-done.6     50   dstpu/exit    forward
#     all-reduce.8         100   dstpu/loop    forward    (150 on chip 1)

PB = os.path.join(cells.ROOT, "benchmark", "testdata", "two_steps.xplane.pb")
LOOP_MAP = {
    "fusion.1": ("dstpu/embed", "forward"),
    "while.2": ("dstpu/loop", "backward"),
    "closed_call.3": ("dstpu/attn", "forward"),
    "fusion.4": ("dstpu/rope", "replay"),
    "checkpoint.5": ("dstpu/exit", "backward"),
    "all-gather-start.6": ("dstpu/head", "forward"),
    "fusion.7": ("dstpu/norm", "forward"),
    "all-gather-done.6": ("dstpu/exit", "forward"),
    "all-reduce.8": ("dstpu/loop", "forward"),
}
READERS = {
    # chip 1: the while's own 20 us + all-reduce.8's 150 us per step
    "loop_carry_ms_per_step": 0.17,
    # fusion.4, twice 190 us per step
    "rope_ms_per_step": 0.38,
    # checkpoint.5 50 us + all-gather-done.6 50 us per step
    "exit_ms_per_step": 0.10,
}


@pytest.fixture(scope="module")
def record():
    trace = tr.load(PB)
    return types.SimpleNamespace(steps=2, trace=trace,
                                 steady=tr.steady(trace, 2),
                                 scope_map=LOOP_MAP)


@pytest.mark.parametrize("name", sorted(READERS))
def test_loop_reader_by_hand(record, name):
    read = cells.plugin(cells.ROOT, "metrics", name).read
    assert read(record) == pytest.approx(READERS[name], rel=1e-9)
    # a program of another model has the map but no such scope: 0; a run
    # without a trace, or a program without a map, says nothing
    other = {k: ("dstpu/block", p) for k, (_, p) in LOOP_MAP.items()}
    assert read(types.SimpleNamespace(
        **{**vars(record), "scope_map": other})) == 0.0
    assert read(types.SimpleNamespace(**{**vars(record), "steady": []})) \
        is None


def test_the_three_entries_belong_to_the_cell_alone(cell):
    per_layer = {m["name"]: m for m in cells.manifest()["per_layer"]}
    for name in READERS:
        entry = per_layer[name]
        assert entry["workloads"] == [CELL]
        assert (entry["source"], entry["moves"], entry["unit"],
                entry["better"], entry["layer"]) == (
            "program_span", "tokens_per_s_per_chip", "ms", "lower", "model")
    reported = {m["name"] for m in cell.per_layer}
    assert set(READERS) <= reported
    # the metrics without a list apply to the new cell as they are
    assert {"head_ms_per_step", "norm_ms_per_step", "scoped_share",
            "remat_replay_share", "attn_kernel_ms_per_step",
            "peak_hbm_gb"} <= reported
    assert "attn_kernel_roofline" not in reported
    old = {m["name"] for m in cells.load("gpt2-xl.1chip").per_layer}
    assert not set(READERS) & old
