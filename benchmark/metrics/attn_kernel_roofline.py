"""The attention kernels' share of their roofline, in %: the least time the
chip could take for the calls that ran — for each, the larger of FLOPs over
the bf16 peak and bytes over the HBM peak, from ``flops.attention_kernel_cost``
on the cell's shapes — over the time the kernels took (first chip).

Which call is which is read off the result: a streaming forward returns the
output and an fp32 log-sum-exp; a backward returns gradients only — three
from the fused kernel, two (dK, dV) from the first half of a split one, whose
dQ half (one result) adds its time and no further required work.  Nothing
where no kernel ran."""

from benchmark import flops, trace_reduce
from benchmark.metrics.attn_kernel_ms_per_step import kernel_events


def direction(name):
    """``"fwd"``, ``"bwd"`` or None (the second half of a split backward)."""
    results = trace_reduce.result_shape(name)
    if "f32[" in results:
        return "fwd"
    return "bwd" if results.count("[") >= 2 else None


def read(record):
    if not record.steady:
        return None
    kernels = kernel_events(record.steady[0].timed)
    spent = sum(sec for _, sec in kernels)
    if not spent:
        return None
    call = record.cell.family.attention_call(record.cell.config,
                                             record.cell.traffic)
    least = {d: flops.roofline_seconds(
        *flops.attention_kernel_cost(direction=d, **call), record.peaks)[0]
        for d in ("fwd", "bwd")}
    directions = (direction(ev.name) for ev, _ in kernels)
    needed = sum(least[d] for d in directions if d)
    return 100.0 * needed / spent
