"""``benchmark/trace_reduce.py`` and the per-layer readers on the small trace
``benchmark/testdata/two_steps.xplane.pb``, against answers worked out by
hand.

The trace (microseconds): two chips; a warm-up program at 500..600; two runs
of the step program ``jit_local(42)`` at 1000..2000 and 2100..3100.  One step
from its start ``t``:

    fusion.1             t+0   .. t+100
    while.2              t+100 .. t+700   encloses the next four
      closed_call.3      t+100 .. t+200   Pallas forward
      fusion.4           t+200 .. t+390
      closed_call.3      t+400 .. t+500
      fusion.4           t+500 .. t+690
    checkpoint.5         t+700 .. t+750   Pallas fused backward
    all-gather-start.6   t+750 .. t+760
    fusion.7             t+760 .. t+800   hides 40 of the all-gather
    all-gather-done.6    t+800 .. t+850
    all-reduce.8         t+850 .. t+950 on chip 0, t+850 .. t+1000 on chip 1
"""

import os
import types

import pytest

from benchmark import cell as cells
from benchmark import run as bench_run
from benchmark import trace_reduce as tr

US = 1e-6
TESTDATA = os.path.join(cells.ROOT, "benchmark", "testdata")
PB = os.path.join(TESTDATA, "two_steps.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return tr.load(PB)


@pytest.fixture(scope="module")
def record(trace):
    cell = cells.load("bert-large.seq512")
    # the trace's kernels work on [16, 128, 64]: one row of 16 heads, seq 128
    cell.family = types.SimpleNamespace(attention_call=lambda c, t: dict(
        rows=1, seq=128, heads=16, head_dim=64, causal=False, itemsize=2))
    rec = types.SimpleNamespace(
        cell=cell, peaks=cells.peaks("TPU v5 lite"), steps=2, trace=trace,
        spans=[tr.Event("dispatch", 0.0, 0.002), tr.Event("loss_wait", 0, 1),
               tr.Event("dispatch", 1.0, 1.004)],
        memory_peak_bytes=12_500_000_000, setup_cache_misses=7)
    rec.steady = tr.steady(trace, rec.steps)
    return rec


def test_the_pb_is_the_textproto(trace, tmp_path):
    """The committed ``.pb`` holds what the readable ``.textproto`` says
    (serialisation does not fix the order of the name table, so the events
    are compared, not the bytes)."""
    from jax.profiler import ProfileData
    with open(os.path.join(TESTDATA, "two_steps.xplane.textproto")) as f:
        built = ProfileData.text_proto_to_serialized_xspace(f.read())
    (tmp_path / "built.xplane.pb").write_bytes(built)
    again = tr.load(str(tmp_path))
    assert again.host_spans == trace.host_spans
    for a, b in zip(again.devices, trace.devices, strict=True):
        assert (a.index, a.ops, a.modules) == (b.index, b.ops, b.modules)


def test_load_finds_devices_lines_and_bench_spans(trace):
    assert [d.index for d in trace.devices] == [0, 1]
    d0 = trace.devices[0]
    assert len(d0.ops) == 23 and len(d0.modules) == 3   # async line left out
    assert [e.name for e in d0.modules] == ["jit_init(1)", "jit_local(42)",
                                            "jit_local(42)"]
    # only the benchmark's spans, by start; PjitFunction is not one
    assert [e.name for e in trace.host_spans] == [
        "bench/batch_prep", "bench/dispatch", "bench/loss_wait",
        "bench/batch_prep", "bench/dispatch", "bench/loss_wait"]
    assert trace.host_spans[1].start == pytest.approx(990 * US)
    assert tr.find_xplane(TESTDATA) == PB


@pytest.mark.parametrize("name,instr,opcode,shape", [
    ("%fusion.394 = (bf16[6400]{0:T(1024)(128)(2,1)}, bf16[4,1024,6400]"
     "{2,1,0:T(8,128)(2,1)}) fusion(bf16[4]{0} %p), kind=kLoop",
     "fusion.394", "fusion", "(bf16[6400], bf16[4,1024,6400])"),
    ("%while.6 = (s32[]{:T(128)}, bf16[4,8]{1,0:T(8,128)(2,1)S(1)}) "
     "while((s32[]{:T(128)}) %t), body=%b", "while.6", "while",
     "(s32[], bf16[4,8])"),
    ("%slice-done.60 = bf16[1024,400]{0,1:T(8,128)(2,1)S(1)} "
     "async-done(((bf16[1024,1600]{0,1}), bf16[1024,400]{0,1}) %s)",
     "slice-done.60", "async-done", "bf16[1024,400]"),
    ("all-reduce.5", "all-reduce.5", "all-reduce", ""),
])
def test_hlo_text_is_parsed(name, instr, opcode, shape):
    assert tr.instr(name) == instr
    assert tr.opcode(name) == opcode
    assert tr.result_shape(name) == shape
    assert tr.label(name) == f"{instr} {opcode} {shape}".strip()


def test_collectives_are_found_by_opcode_or_wrapped_name():
    assert tr.collective("%all-gather-start.3 = (f32[1]{0}, f32[4]{0}) "
                         "all-gather-start(f32[1]{0} %p)") == (
        "all-gather", "-start", "3")
    # an asynchronous reduce-scatter is an async-start/-done pair
    assert tr.collective("%reduce-scatter-done.7 = f32[1]{0} async-done("
                         "(f32[4]{0}) %s)") == ("reduce-scatter", "-done",
                                                "7")
    assert tr.collective("%all-reduce.8 = f32[4]{0} all-reduce(f32[4]{0} "
                         "%x)") == ("all-reduce", None, "8")
    assert tr.collective("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %x)") is None
    assert tr.collective("%all-reduce_fusion = f32[4]{0} fusion()") is None
    assert tr.is_control_flow("%while.2 = (s32[]) while((s32[]) %t)")
    assert not tr.is_control_flow("%fusion.1 = f32[4]{0} fusion()")


def test_interval_arithmetic():
    assert tr.merge([(3, 4), (0, 2), (1, 2.5), (5, 5)]) == [(0, 2.5), (3, 4)]
    assert tr.length([(0, 2), (1, 3), (10, 11)]) == 4
    assert tr.clip([(0, 2), (3, 9)], 1, 4) == [(1, 2), (3, 4)]
    assert tr.subtract([(0, 10)], [(1, 2), (3, 4), (9, 12)]) == [
        (0, 1), (2, 3), (4, 9)]
    assert tr.subtract([(0, 2), (5, 8)], [(1, 6)]) == [(0, 1), (6, 8)]
    assert tr.subtract([(0, 2), (3, 4)], [(0, 5)]) == []
    assert tr.gaps([(1, 2), (4, 6)], 0, 5) == [(0, 1), (2, 4)]


def test_step_window_is_the_step_programs_last_runs(trace):
    d0 = trace.devices[0]
    assert tr.step_window(d0, 2) == pytest.approx((1000 * US, 3100 * US))
    assert tr.step_window(d0, 1) == pytest.approx((2100 * US, 3100 * US))
    assert tr.step_window(tr.Device(9, [], []), 2) is None


def test_self_times_take_enclosed_events_out(trace):
    d0 = trace.devices[0]
    timed = tr.self_times(tr.in_window(d0.ops, 1000 * US, 3100 * US))
    assert len(timed) == 22                       # warm-up fusion.9 is out
    sums = {k.split()[0]: v for k, v in tr.sum_by_label(timed).items()}
    # while.2 lasts 600 and encloses 100 + 190 + 100 + 190: 20 of its own
    assert sums["while.2"] == (pytest.approx(2 * 20 * US), 2)
    assert sums["fusion.4"] == (pytest.approx(4 * 190 * US), 4)
    assert sums["closed_call.3"] == (pytest.approx(4 * 100 * US), 4)
    assert sums["all-reduce.8"] == (pytest.approx(2 * 100 * US), 2)
    # self times add up to the busy time: nothing counted twice
    assert sum(s for _, s in timed) == pytest.approx(2 * 950 * US)


def test_busy_and_idle_by_hand(trace):
    d0, d1 = trace.devices
    # chip 0: busy t..t+950 in each step; idle 50 + 100 (between) + 50
    assert tr.busy_seconds(d0.ops, 1000 * US, 3100 * US) == pytest.approx(
        1900 * US)
    assert tr.busy_seconds(d1.ops, 1000 * US, 3100 * US) == pytest.approx(
        2000 * US)
    assert [s.busy for s in tr.steady(trace, 2)] == [
        pytest.approx(1900 * US), pytest.approx(2000 * US)]
    idle = tr.gaps([(e.start, e.end) for e in d0.ops], 1000 * US, 3100 * US)
    assert idle == [pytest.approx((1950 * US, 2100 * US)),
                    pytest.approx((3050 * US, 3100 * US))]
    # the long gap spans the step boundary, where the host was dispatching
    # (2010..2095); the last one falls in the final loss_wait
    assert tr.attribute_gaps(idle, trace.host_spans) == [
        ("bench/dispatch", pytest.approx(150 * US)),
        ("bench/loss_wait", pytest.approx(50 * US))]
    assert tr.attribute_gaps([(0.0, 1 * US)], trace.host_spans) == [
        ("none", pytest.approx(1 * US))]


def test_collectives_and_their_exposed_part_by_hand(trace):
    step = tr.in_window(trace.devices[0].ops, 1000 * US, 2000 * US)
    # the all-gather runs start..done = 750..850, the all-reduce 850..950
    assert tr.collective_intervals(step) == [
        pytest.approx((1750 * US, 1850 * US)),
        pytest.approx((1850 * US, 1950 * US))]
    # fusion.7 hides 40 of the all-gather; while.2 is control flow and hides
    # nothing; the all-reduce is synchronous: 60 + 100 exposed
    assert tr.exposed_collective_seconds(step) == pytest.approx(160 * US)


# what each reader must say of this trace, by hand
EXPECTED = {
    # chip 0 idles 200 of the 2100 us window, chip 1 100: the worst
    "device_idle_share": 100 * 200 / 2100,
    # 11 events per step on either chip
    "device_ops_per_step": 11.0,
    # 100 + 100 + 50 us of Pallas kernels per step
    "attn_kernel_ms_per_step": 0.25,
    # chip 1: all-gather 100 + all-reduce 150 per step
    "collective_ms_per_step": 0.25,
    # chip 1: (60 + 150) exposed per step, 420 of 2100
    "collective_exposed_share": 20.0,
    "warm_cache_misses": 7,
    "peak_hbm_gb": 12.5,
    # median of the dispatch spans 2 ms and 4 ms
    "dispatch_ms_per_step": 3.0,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_by_hand(record, name):
    got = cells.plugin(cells.ROOT, "metrics", name).read(record)
    assert got == pytest.approx(EXPECTED[name], rel=1e-9)


def test_attention_roofline_by_hand(record):
    # [16 heads, 128 x 128 pairs, 64 wide] in bf16 on a v5e: both directions
    # are bound by memory.  forward: 4 tensors of 16*128*64*2 B + 16*128 fp32
    # log-sum-exps = 1,056,768 B / 819 GB/s; backward: 8 tensors + the same
    fwd = 1_056_768 / 819e9
    bwd = 2_105_344 / 819e9
    assert 2 * 2 * 16 * 128 * 128 * 64 / 197e12 < fwd       # compute is less
    # chip 0, two steps: four forward calls (two outputs, one of them fp32)
    # and two fused backward calls (three bf16 outputs) in 500 us of kernels
    want = 100 * (4 * fwd + 2 * bwd) / (500 * US)
    got = cells.plugin(cells.ROOT, "metrics",
                       "attn_kernel_roofline").read(record)
    assert got == pytest.approx(want, rel=1e-9)
    direction = cells.plugin(cells.ROOT, "metrics",
                             "attn_kernel_roofline").direction
    assert direction("%dq = bf16[16,128,64]{2,1,0} custom-call()") is None
    assert direction("%dkv = (bf16[1,2]{1,0}, bf16[1,2]{1,0}) "
                     "custom-call()") == "bwd"


def test_device_times_and_breakdown(record):
    times, breakdown = bench_run.device_times(record)
    # averaged over the two chips
    assert times["busy_s"] == pytest.approx(1950 * US)
    assert times["window_s"] == pytest.approx(2100 * US)
    ops = breakdown["device_ops"]
    assert len(ops) == 9 <= bench_run.BREAKDOWN_ENTRIES
    assert ops[0] == ["fusion.4 fusion bf16[8,128]", pytest.approx(760 * US)]
    assert ops[1][0].startswith("closed_call.3 custom-call (bf16[16,128,64]")
    assert [g[0] for g in breakdown["idle_gaps"]] == ["bench/dispatch",
                                                      "bench/loss_wait"]


def test_readers_say_nothing_without_a_device_trace(record):
    empty = types.SimpleNamespace(**{**vars(record), "steady": [],
                                     "spans": [],
                                     "memory_peak_bytes": None})
    for name in EXPECTED:
        if name != "warm_cache_misses":
            assert cells.plugin(cells.ROOT, "metrics",
                                name).read(empty) is None
    assert bench_run.device_times(empty) == ({}, None)
