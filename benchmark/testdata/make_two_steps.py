"""Writes ``two_steps.xplane.textproto`` and ``two_steps.xplane.pb`` beside
itself: a trace in the format a v5e writes, small enough to work out every
answer by hand.  ``python benchmark/testdata/make_two_steps.py`` after a
change; the tests check that the two files agree."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
L = "{1,0:T(8,128)(2,1)}"
NAMES = {
 "fusion.1": f"%fusion.1 = bf16[8,128]{L} fusion(bf16[8,128]{L} %param.0), kind=kLoop, calls=%fused_computation.1",
 "while.2": "%while.2 = (s32[]{:T(128)}, bf16[8,128]" + L + ") while((s32[]{:T(128)}, bf16[8,128]" + L + ") %tuple.1), condition=%cond, body=%body",
 "closed_call.3": "%closed_call.3 = (bf16[16,128,64]{2,1,0:T(8,128)(2,1)S(1)}, f32[16,1,128]{2,1,0:T(1,128)}) custom-call(bf16[16,128,64]{2,1,0:T(8,128)(2,1)} %bitcast.1), custom_call_target=\\\"tpu_custom_call\\\", frontend_attributes={kernel_metadata={}}",
 "fusion.4": f"%fusion.4 = bf16[8,128]{L} fusion(bf16[8,128]{L} %get-tuple-element.1), kind=kOutput, calls=%fused_computation.4",
 "checkpoint.5": "%checkpoint.5 = (bf16[16,128,64]{2,1,0:T(8,128)(2,1)}, bf16[16,128,64]{2,1,0:T(8,128)(2,1)}, bf16[16,128,64]{2,1,0:T(8,128)(2,1)}) custom-call(bf16[16,128,64]{2,1,0:T(8,128)(2,1)} %bitcast.2), custom_call_target=\\\"tpu_custom_call\\\", frontend_attributes={kernel_metadata={}}",
 "all-gather-start.6": "%all-gather-start.6 = (f32[10]{0:T(1024)}, f32[40]{0:T(1024)}) all-gather-start(f32[10]{0:T(1024)} %param.1), replica_groups={{0,1}}, dimensions={0}",
 "fusion.7": f"%fusion.7 = bf16[8,128]{L} fusion(bf16[8,128]{L} %fusion.4), kind=kLoop, calls=%fused_computation.7",
 "all-gather-done.6": "%all-gather-done.6 = f32[40]{0:T(1024)} all-gather-done((f32[10]{0:T(1024)}, f32[40]{0:T(1024)}) %all-gather-start.6)",
 "all-reduce.8": "%all-reduce.8 = f32[40]{0:T(1024)} all-reduce(f32[40]{0:T(1024)} %all-gather-done.6), replica_groups={{0,1}}, to_apply=%add",
 "fusion.9": f"%fusion.9 = bf16[8,128]{L} fusion(), kind=kLoop, calls=%fused_computation.9",
 "copy-start.11": "%copy-start.11 = (bf16[8,128]" + L + ", bf16[8,128]" + L + ", u32[]{:S(2)}) copy-start(bf16[8,128]" + L + " %fusion.1)",
 "jit_init(1)": "jit_init(1)", "jit_local(42)": "jit_local(42)",
 "bench/batch_prep": "bench/batch_prep", "bench/dispatch": "bench/dispatch", "bench/loss_wait": "bench/loss_wait",
 "PjitFunction(local)": "PjitFunction(local)", "0": "0", "1": "1",
}
IDS = {k: i + 1 for i, k in enumerate(NAMES)}

def step(t, all_reduce):
    """One step's XLA Ops events, microseconds from its start."""
    return [("fusion.1", t, 100), ("while.2", t + 100, 600),
            ("closed_call.3", t + 100, 100), ("fusion.4", t + 200, 190),
            ("closed_call.3", t + 400, 100), ("fusion.4", t + 500, 190),
            ("checkpoint.5", t + 700, 50), ("all-gather-start.6", t + 750, 10),
            ("fusion.7", t + 760, 40), ("all-gather-done.6", t + 800, 50),
            ("all-reduce.8", t + 850, all_reduce)]

def line(name, events, comment=""):
    out = [f"  lines {{ name: \"{name}\"{comment}"]
    for key, start_us, dur_us in events:
        out.append(f"    events {{ metadata_id: {IDS[key]} offset_ps: {start_us * 1000000} duration_ps: {dur_us * 1000000} }}  # {key} {start_us}..{start_us + dur_us} us")
    out.append("  }")
    return "\n".join(out)

def metadata(keys):
    return "\n".join(f"  event_metadata {{ key: {IDS[k]} value {{ id: {IDS[k]} name: \"{NAMES[k]}\" }} }}" for k in keys)

def device(index, all_reduce):
    ops = [("fusion.9", 500, 100)] + step(1000, all_reduce) + step(2100, all_reduce)
    mods = [("jit_init(1)", 500, 100), ("jit_local(42)", 1000, 1000), ("jit_local(42)", 2100, 1000)]
    keys = sorted({k for k, _, _ in ops + mods} | {"copy-start.11", "0", "1"}, key=IDS.get)
    return "\n".join([f"planes {{ name: \"/device:TPU:{index}\"",
        line("Steps", [("0", 1000, 1000), ("1", 2100, 1000)]),
        line("XLA Modules", mods), line("XLA Ops", ops),
        line("Async XLA Ops", [("copy-start.11", 1000, 300), ("copy-start.11", 2100, 300)]),
        metadata(keys), "}"])

host_events = [("bench/batch_prep", 988, 2), ("bench/dispatch", 990, 20), ("PjitFunction(local)", 992, 15),
               ("bench/loss_wait", 1010, 998), ("bench/batch_prep", 2008, 2), ("bench/dispatch", 2010, 85),
               ("PjitFunction(local)", 2012, 80), ("bench/loss_wait", 2095, 1105)]
host = "\n".join(["planes { name: \"/host:CPU\"", line("python3", host_events),
                  metadata(sorted({k for k, _, _ in host_events}, key=IDS.get)), "}"])
header = """# A trace in the format jax.profiler writes on a TPU v5e (PERF.md, "Reading a
# trace"), small enough to work out every answer by hand; the answers are in
# tests/benchmark_harness/test_bench_trace.py.  Two chips, one warm-up program
# and two runs of the step program `jit_local(42)`, 1000 us each, 100 us apart.
# Chip 0's all-reduce takes 100 us and leaves 50 us of the step idle; chip 1's
# takes 150 us.  Written by a script; two_steps.xplane.pb is this file through
# jax.profiler.ProfileData.text_proto_to_serialized_xspace.
"""
text = header + "\n".join([device(0, 100), device(1, 150), host]) + "\n"
out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "two_steps.xplane")
open(out + ".textproto", "w").write(text)
os.environ["JAX_PLATFORMS"] = "cpu"
from jax.profiler import ProfileData
open(out + ".pb", "wb").write(ProfileData.text_proto_to_serialized_xspace(text))
print(len(text), os.path.getsize(out + ".pb"))
