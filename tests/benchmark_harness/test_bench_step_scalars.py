"""The three readers of the expert stack's step scalars
(``benchmark/metrics/moe_overflow_passes_per_step.py``,
``moe_prefix_fill.py``, ``moe_max_expert_load.py`` over
``benchmark/step_scalars.py``): by hand on a made-up snapshot, on the
program's own snapshot after real steps of the cell's tiny preset, silent
where the program counts no ``moe/*`` scalar or has no channel at all — and
their three entries in ``BENCHMARK.json``, asked for BY NAME."""

import types

import jax
import numpy as np
import pytest

from benchmark import cell as cells
from benchmark import step_scalars

CELL = "kimi-vl-a3b.ep8-seq8192"
NEW = {"moe_overflow_passes_per_step": ("passes/step", "lower"),
       "moe_prefix_fill": ("%", "higher"),
       "moe_max_expert_load": ("x", "lower")}

#: the cell's numbers: 5 expert layers, 98,304 pairs a pass, a prefix of a
#: quarter; 19 optimizer steps at gas 1 (3 warm-up + 16 measured)
GAUGES = {"layers_moe": 5, "experts_total": 64, "experts_held": 8,
          "routed_rows_prefix": 24576, "routed_rows_all": 98304}


def snapshot(steps=19, overflows=0.0, held=19 * 5 * 12100.0, busiest=1790.0,
             **over):
    return {"steps": steps, "micro_steps": steps, "batch_shards": 1,
            "model_shards": 1, "gauges": dict(GAUGES),
            "values": {"moe/overflow_passes": overflows,
                       "moe/held_pairs": held,
                       "moe/max_expert_rows": busiest}, **over}


def read(name, snap):
    plugin = cells.plugin(cells.ROOT, "metrics", name)
    return plugin.read(types.SimpleNamespace(step_scalars=snap))


def test_readers_by_hand():
    snap = snapshot()
    assert read("moe_overflow_passes_per_step", snap) == 0.0
    # 12,100 of the prefix's 24,576 rows a pass
    assert read("moe_prefix_fill", snap) == pytest.approx(
        100 * 12100 / 24576, rel=1e-12)
    # times the prefix's quarter: the share of all pairs that landed
    assert read("moe_prefix_fill", snap) * 0.25 == pytest.approx(
        100 * 12100 / 98304, rel=1e-12)
    # the even load is 98,304 / 64 = 1,536 rows an expert
    assert read("moe_max_expert_load", snap) == pytest.approx(
        1790 / 1536, rel=1e-12)
    # every layer of every step overflowing reads the number of layers
    assert read("moe_overflow_passes_per_step",
                snapshot(overflows=19 * 5.0)) == 5.0
    # passes: layers x micro-steps x the shards that ran their own
    wide = snapshot(steps=4, micro_steps=8, batch_shards=2, model_shards=2,
                    held=5 * 8 * 2 * 2 * 3000.0)
    assert read("moe_prefix_fill", wide) == pytest.approx(
        100 * 3000 / 24576, rel=1e-12)
    assert read("moe_overflow_passes_per_step", wide) == 0.0


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_reader_says_nothing_without_an_expert_stacks_counts(
        name, monkeypatch):
    looped = snapshot()
    looped["values"] = {"loop/exit_ce": [1.0, 2.0], "loop/exit_prob": [.5, .5]}
    assert read(name, looped) is None
    assert read(name, snapshot(steps=0)) is None      # no step, no rate
    # a program whose model declares nothing, or a commit before the
    # channel: no snapshot to ask for
    monkeypatch.setattr(step_scalars, "_snapshot", None)
    plugin = cells.plugin(cells.ROOT, "metrics", name)
    assert plugin.read(types.SimpleNamespace()) is None


def test_readers_on_the_programs_own_snapshot(monkeypatch):
    """Two real steps of the family's tiny preset through ``initialize`` ->
    ``train_batch``: the readers ask the program itself
    (``observability.scalars.snapshot()``), and the gauges they divide by
    are there under the names they use."""
    import deepspeed_tpu
    from deepspeed_tpu.parallel.topology import make_mesh
    cell = cells.load(CELL)
    config = cell.family.tiny(cell.config)
    traffic = {**cell.traffic, "seq": 64, "micro_batch": 2}
    model = cell.family.build_model(config, traffic)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, mesh=make_mesh(devices=jax.devices()[:1]),
        config={"train_batch_size": 2, "steps_per_print": 10 ** 9,
                "bf16": {"enabled": True},
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})
    batch = cell.family.make_batch(np.random.default_rng(0), 2, config,
                                   traffic)
    for _ in range(2):
        engine.train_batch(batch)
    monkeypatch.setattr(step_scalars, "_snapshot", False)
    record = types.SimpleNamespace()
    got = {name: cells.plugin(cells.ROOT, "metrics", name).read(record)
           for name in NEW}
    snap = step_scalars.snapshot(record)
    assert snap["steps"] == 2 and snap["gauges"]["layers_moe"] >= 1
    assert got["moe_overflow_passes_per_step"] == 0.0
    assert 0 < got["moe_prefix_fill"] <= 100
    assert got["moe_max_expert_load"] >= 1.0
    share = snap["gauges"]["experts_held"] / snap["gauges"]["experts_total"]
    landed = got["moe_prefix_fill"] / 100 * (
        snap["gauges"]["routed_rows_prefix"]
        / snap["gauges"]["routed_rows_all"])
    assert 0.5 * share < landed < 2 * share


def test_the_three_entries_by_name():
    man = cells.manifest()
    by_name = {m["name"]: m for m in man["per_layer"]}
    assert set(NEW) <= set(by_name)
    for name, (unit, better) in NEW.items():
        entry = by_name[name]
        assert entry == {
            "name": name, "unit": unit, "better": better,
            "source": "program_counter", "layer": "model",
            "moves": "tokens_per_s_per_chip", "workloads": [CELL]}
    assert set(NEW) <= {m["name"] for m in cells.load(CELL).per_layer}
    for other in (w["name"] for w in man["workloads"]):
        if other != CELL:
            assert not set(NEW) & {m["name"]
                                   for m in cells.load(other).per_layer}
