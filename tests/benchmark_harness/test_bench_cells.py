"""``BENCHMARK.json`` and the data files behind it: every name resolves, the
contract's limits hold, traffic comes from the seed, and a cell, a
configuration, a traffic mix and a per-layer metric can each be added by new
files and new entries alone."""

import json
import os
import shutil
import types

import numpy as np
import pytest

from benchmark import cell as cells
from benchmark import run as bench_run
from benchmark import trace_reduce
from benchmark.traffic_kinds import train_steps

MANIFEST = cells.manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_manifest_has_exactly_the_contracts_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "-m", "benchmark.run"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(cells.ROOT, "BENCHMARK.json")) \
        < 64 * 1024
    for path in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(cells.ROOT, path))


def test_every_name_is_plain_and_used_once():
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in MANIFEST[key]]
    for name in names:
        assert cells.NAME.match(name), name
    assert len(names) == len(set(names))
    for x in MANIFEST["configs"] + MANIFEST["workloads"]:
        assert len(x["why"]) <= 200, x["name"]
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_file_under_paths_has_a_plain_name():
    plain = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
                "0123456789_.-/")
    for path in MANIFEST["paths"]:
        for folder, dirs, files in os.walk(os.path.join(cells.ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), cells.ROOT)
                assert set(rel) <= plain, rel


def test_metrics_follow_the_contract():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("higher", "lower")
    for m in MANIFEST["per_layer"]:
        assert "bound" not in m
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        # a kernel's roofline share is named <kernel>_roofline, in %
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"
    four_chip = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four_chip) <= max(1, len(CELLS) // 4)
    assert {w["chips"] for w in MANIFEST["workloads"]} <= {1, 4}


def test_every_configuration_is_used_and_keeps_its_widths():
    used = {w["config"] for w in MANIFEST["workloads"]}
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    width = ("hidden", "intermediate", "n_embd", "_dim", "_rank", "head",
             "expert")
    for c in MANIFEST["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("benchmark/configs/")
        data = cells.read_json(cells.ROOT, c["file"])
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert not any(w in key for w in width), key
    # both GPT-2 files are the published widths; only the depth differs
    a, b = (cells.read_json(cells.ROOT, f"benchmark/configs/{n}.json")
            for n in ("gpt2-xl-l20", "gpt2-xl-l24"))
    assert (a["n_embd"], a["n_head"], a["vocab_size"], a["n_positions"]) == (
        1600, 25, 50257, 1024)
    assert {k for k in a if a[k] != b[k]} == {"n_layer", "reduced"}


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_files_that_exist(name):
    cell = cells.load(name)
    assert cell.layout["chips"] == cell.chips
    assert cell.traffic["kind"] == "train_steps"
    reported = {m["name"] for m in cell.end_to_end}
    assert {"setup_s", "tokens_per_s_per_chip", "mfu"} <= reported
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(cells.plugin(cells.ROOT, "metrics", m["name"]).read)
    rows = cell.traffic["micro_batch"] * cell.traffic["gas"] \
        * cell.layout["mesh"]["data"]
    assert [r.stop - r.start for r in train_steps.reported_rows(cell)] == [
        cell.traffic["micro_batch"]] * cell.layout["mesh"]["data"]
    assert train_steps.reported_rows(cell)[-1].stop == rows
    config = train_steps.engine_config(cell, None)
    assert config["train_batch_size"] == rows
    assert "compile_cache" not in config
    assert train_steps.engine_config(cell, "/x")["compile_cache"] == {
        "dir": "/x"}


def test_unknown_names_are_errors():
    with pytest.raises(cells.CellError, match="no workload"):
        cells.load("no-such-cell")
    with pytest.raises(cells.CellError, match="does not exist"):
        cells.plugin(cells.ROOT, "metrics", "no_such_metric")
    with pytest.raises(cells.CellError, match="not a module name"):
        cells.plugin(cells.ROOT, "metrics", "../run")


@pytest.mark.parametrize("name", ["bert-large.seq128", "gpt2-xl.dp4-zero1"])
def test_batch_pool_comes_from_the_seed(name):
    cell = cells.load(name)
    cell.config = cell.family.tiny(cell.config)
    a, b, c = (train_steps.batch_pool(cell, s) for s in (7, 7, 8))
    assert len(a) == cell.traffic["batch_pool"]
    for x, y in zip(a, b):
        assert all(np.array_equal(p, q) for p, q in zip(x, y))
    assert not all(np.array_equal(p, q) for x, y in zip(a, c)
                   for p, q in zip(x, y))
    # the pool's batches differ from each other, and ids stay in the
    # published vocabulary
    assert not np.array_equal(a[0][0], a[1][0])
    assert a[0][0].max() < cell.config["vocab_size"]
    rows = cell.traffic["micro_batch"] * cell.traffic["gas"] \
        * cell.layout["mesh"]["data"]
    assert all(leaf.shape[0] == rows for leaf in a[0])


def test_mlm_batch_masks_distinct_positions_and_labels_them():
    cell = cells.load("bert-large.seq128")
    ids, mask, types_, positions, labels, weights = cell.family.make_batch(
        np.random.default_rng(0), 16, cell.config, cell.traffic)
    assert positions.shape == (16, 20) and mask.all() and not types_.any()
    assert all(len(set(row)) == 20 for row in positions.tolist())
    assert np.array_equal(labels, np.take_along_axis(ids, positions, 1))
    assert weights.sum() == 16 * 20


def test_lm_batch_labels_are_the_next_tokens():
    cell = cells.load("gpt2-xl.1chip")
    tokens, labels = cell.family.make_batch(
        np.random.default_rng(0), 4, cell.config, cell.traffic)
    assert tokens.shape == labels.shape == (4, 1024)
    assert np.array_equal(tokens[:, 1:], labels[:, :-1])


def test_a_cell_is_added_by_files_and_entries_alone(tmp_path):
    """A throw-away checkout: the benchmark's files copied, then ONLY new
    files and new ``BENCHMARK.json`` entries — a configuration, a traffic
    mix, a cell and a per-layer metric — and the loader and the metric
    collection find them with no edit to a file that was there."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(cells.ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for folder, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            with open(os.path.join(folder, f), "rb") as fh:
                before[os.path.join(folder, f)] = fh.read()

    def new(rel, text):
        path = os.path.join(root, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(text)

    config = cells.read_json(cells.ROOT, "benchmark/configs/bert-large.json")
    config["num_hidden_layers"] = 12
    config["reduced"] = {"num_hidden_layers": "a test's cut"}
    new("benchmark/configs/bert-large-l12.json", json.dumps(config))
    new("benchmark/traffic/pretrain-seq256.json", json.dumps(
        {"kind": "train_steps", "api": "fused", "seq": 256,
         "masked_positions": 40, "micro_batch": 16, "gas": 2,
         "batch_pool": 2, "warmup_steps": 3}))
    new("benchmark/workloads/bert-l12.seq256.json",
        json.dumps({"layout": "1chip"}))
    new("benchmark/metrics/steps_traced.py",
        "def read(record):\n    return float(record.steps)\n")
    manifest = cells.manifest()
    manifest["configs"].append(
        {"name": "bert-large-l12", "source": "test",
         "file": "benchmark/configs/bert-large-l12.json",
         "reduced": ["num_hidden_layers"], "why": "test"})
    manifest["workloads"].append(
        {"name": "bert-l12.seq256", "config": "bert-large-l12",
         "traffic": "pretrain-seq256", "chips": 1, "why": "test"})
    manifest["per_layer"].append(
        {"name": "steps_traced", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "entry",
         "moves": "tokens_per_s_per_chip", "workloads": ["bert-l12.seq256"]})
    new("BENCHMARK.json", json.dumps(manifest))

    cell = cells.load("bert-l12.seq256", root=root)
    assert cell.config["num_hidden_layers"] == 12
    assert cell.traffic["seq"] == 256 and cell.layout["name"] == "1chip"
    assert cell.family.flops_per_token(cell.config, cell.traffic)["body"] \
        == 6 * 12 * 12 * 1024 ** 2
    assert len(train_steps.batch_pool(cell, 0)) == 2
    assert "steps_traced" in {m["name"] for m in cell.per_layer}
    # ... and the old cells do not report the new cell's metric
    assert "steps_traced" not in {
        m["name"] for m in cells.load("bert-large.seq128",
                                      root=root).per_layer}

    record = types.SimpleNamespace(
        cell=cell, steps=3, steady=[], spans=[trace_reduce.Event(
            "dispatch", 0.0, 0.002)], memory_peak_bytes=None,
        setup_cache_misses=0)
    metrics = bench_run.per_layer_metrics(cell, record)
    assert metrics["steps_traced"] == {"value": 3.0, "unit": "steps"}
    assert metrics["dispatch_ms_per_step"]["value"] == pytest.approx(2.0)
    # readers that found nothing to read are left out of the line
    assert "device_idle_share" not in metrics and "peak_hbm_gb" not in metrics

    for path, content in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == content, f"{path} was edited"


def test_setup_s_leaves_out_only_the_phases_not_counted(monkeypatch):
    """``setup_s`` runs from process start to the first dispatch less the
    span that opens the chip; every phase, counted or not, is reported."""
    now = [100.0]
    monkeypatch.setattr(bench_run.time, "perf_counter", lambda: now[0])
    monkeypatch.setattr(bench_run, "process_age_s", lambda: 4.0)
    clock = bench_run.SetupClock()
    with clock("load"):
        now[0] += 3.0
    with clock("open_chip", counted=False):
        now[0] += 8.5
    now[0] += 0.25                      # between phases: counted all the same
    with clock("warmup"):
        now[0] += 5.0
    clock.mark_setup_done()
    assert dict(clock.phases)["open_chip"] == pytest.approx(8.5)
    assert clock.not_counted == {"open_chip": pytest.approx(8.5)}
    assert clock.setup_s == pytest.approx(4.0 + 3.0 + 0.25 + 5.0)
