"""``benchmark/scopes.py`` and the six readers over it, on the small trace
``benchmark/testdata/two_steps.xplane.pb`` (test_bench_trace.py draws it)
with a scope map written by hand, against answers worked out by hand.

One step on chip 0, self times in microseconds, under the map below:

    fusion.1             100   dstpu/embed            forward
    while.2               20   (no scope)             600 less the 580 inside
    closed_call.3    2 x 100   dstpu/attn             forward
    fusion.4         2 x 190   dstpu/ffn              replay
    checkpoint.5          50   dstpu/attn             backward
    all-gather-start.6    10   dstpu/boundary/gather
    fusion.7              40   dstpu/boundary/update
    all-gather-done.6     50   dstpu/boundary/gather
    all-reduce.8         100   dstpu/boundary/reduce  (150 on chip 1)

950 us of instructions on chip 0, 1000 on chip 1; two steps of 1000 us, 100
apart: a window of 2100 us on either chip.
"""

import os
import types

import pytest

from benchmark import cell as cells
from benchmark import scopes
from benchmark import trace_reduce as tr

US = 1e-6
PB = os.path.join(cells.ROOT, "benchmark", "testdata", "two_steps.xplane.pb")
FWD, BWD, REPLAY = "forward", "backward", "replay"
SCOPE_MAP = {
    "fusion.1": ("dstpu/embed", FWD),
    "closed_call.3": ("dstpu/attn", FWD),
    "fusion.4": ("dstpu/ffn", REPLAY),
    "checkpoint.5": ("dstpu/attn", BWD),
    "all-reduce.8": ("dstpu/boundary/reduce", FWD),
    "all-gather-start.6": ("dstpu/boundary/gather", FWD),
    "all-gather-done.6": ("dstpu/boundary/gather", FWD),
    "fusion.7": ("dstpu/boundary/update", FWD),
    "while.2": ("", FWD),
    # in the program, never in this trace
    "fusion.99": ("dstpu/head", FWD),
}
NEW = ["scoped_share", "boundary_ms_per_step", "optimizer_ms_per_step",
       "remat_replay_share", "head_ms_per_step", "norm_ms_per_step"]


@pytest.fixture(scope="module")
def record():
    trace = tr.load(PB)
    return types.SimpleNamespace(steps=2, trace=trace,
                                 steady=tr.steady(trace, 2),
                                 scope_map=SCOPE_MAP)


def read(name, record):
    return cells.plugin(cells.ROOT, "metrics", name).read(record)


def test_by_scope_sums_self_times_per_chip(record):
    chip0, chip1 = scopes.by_scope(record)
    assert chip0 == {
        ("dstpu/embed", FWD): pytest.approx(2 * 100 * US),
        ("", FWD): pytest.approx(2 * 20 * US),          # the while's own
        ("dstpu/attn", FWD): pytest.approx(2 * 200 * US),
        ("dstpu/ffn", REPLAY): pytest.approx(2 * 380 * US),
        ("dstpu/attn", BWD): pytest.approx(2 * 50 * US),
        ("dstpu/boundary/gather", FWD): pytest.approx(2 * 60 * US),
        ("dstpu/boundary/update", FWD): pytest.approx(2 * 40 * US),
        ("dstpu/boundary/reduce", FWD): pytest.approx(2 * 100 * US),
    }
    assert sum(chip0.values()) == pytest.approx(2 * 950 * US)
    assert chip1[("dstpu/boundary/reduce", FWD)] == pytest.approx(300 * US)
    assert sum(chip1.values()) == pytest.approx(2 * 1000 * US)
    # an instruction the map does not know counts under no scope
    bare = types.SimpleNamespace(**{**vars(record), "scope_map": {}})
    assert scopes.by_scope(bare)[0] == {
        scopes.UNSCOPED: pytest.approx(2 * 950 * US)}


# what each reader must say, each on the chip where it is largest
EXPECTED = {
    # all but the while's 20 us: 930 / 950 on chip 0, 980 / 1000 on chip 1
    "scoped_share": 98.0,
    # chip 1: gather 10 + 50, update 40, reduce 150 per step
    "boundary_ms_per_step": 0.25,
    # fusion.7: 40 us per step
    "optimizer_ms_per_step": 0.04,
    # fusion.4, twice 190 us per step: 760 of the window's 2100 us
    "remat_replay_share": 100 * 760 / 2100,
    # the map names a head instruction, the trace ran none; no norm at all
    "head_ms_per_step": 0.0,
    "norm_ms_per_step": 0.0,
}


@pytest.mark.parametrize("name", NEW)
def test_reader_by_hand(record, name, capsys):
    assert read(name, record) == pytest.approx(EXPECTED[name], rel=1e-9)
    if name != "scoped_share":
        assert capsys.readouterr().out == ""


def test_the_table_is_printed_once_with_the_two_identities(record, capsys,
                                                           monkeypatch):
    monkeypatch.setattr(scopes, "_table_printed", False)
    read("scoped_share", record)
    read("scoped_share", record)
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("device time by scope and phase (first chip")
    rows = {tuple(line.split()[:2]): float(line.split()[2])
            for line in out[1:9]}
    assert len(out) == 11                       # 8 rows, once, + 2 lines
    assert rows[("dstpu/ffn", "replay")] == pytest.approx(0.38)
    assert rows[("(none)", "forward")] == pytest.approx(0.02)
    # chip 0: both Pallas calls lie under dstpu/attn (the first identity,
    # = attn_kernel_ms_per_step), every collective under dstpu/boundary
    # (the second, = collective_ms_per_step where they are synchronous)
    assert out[9].split(":")[1].split()[:3] == ["0.250", "of", "0.250"]
    assert out[10].split(":")[1].split()[:3] == ["0.160", "of", "0.160"]


def test_readers_say_nothing_without_a_trace_or_without_a_map(record,
                                                              monkeypatch):
    empty = types.SimpleNamespace(**{**vars(record), "steady": []})
    assert scopes.by_scope(empty) is None
    # a program that hands out no map (a commit before the scopes, or no
    # step program built): the program's map is asked for once, and the
    # metrics are left out of the line
    program = pytest.importorskip("deepspeed_tpu.observability.scopes")
    asked = []
    monkeypatch.setattr(scopes, "_program_map", False)
    monkeypatch.setattr(program, "step_scope_map", lambda: asked.append(1))
    no_map = types.SimpleNamespace(**{**vars(record), "scope_map": None})
    for name in NEW:
        assert read(name, empty) is None
        assert read(name, no_map) is None
    assert asked == [1]


def test_the_new_entries_are_the_six_readers():
    per_layer = {m["name"]: m for m in cells.manifest()["per_layer"]}
    assert list(per_layer)[-6:] == NEW
    for name in NEW:
        entry = per_layer[name]
        assert entry["source"] == "program_span"
        assert entry["moves"] == "tokens_per_s_per_chip"
        assert "workloads" not in entry             # all four cells
        assert (entry["unit"], entry["better"]) == (
            ("%", "higher") if name == "scoped_share" else
            ("%", "lower") if name.endswith("_share") else ("ms", "lower"))
