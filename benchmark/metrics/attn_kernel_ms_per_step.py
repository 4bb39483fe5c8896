"""Milliseconds per optimizer step the device spends in Pallas kernels — the
events on ``XLA Ops`` whose instruction is a ``tpu_custom_call`` (the
streaming attention forward, its replay under recomputation, and the
backward) — on the chip where that is longest.  0 where the step runs none
(``attention_plan`` chose XLA)."""

from benchmark import trace_reduce


def kernel_events(timed):
    return [(ev, sec) for ev, sec in timed if trace_reduce.PALLAS in ev.name]


def read(record):
    if not record.steady:
        return None
    return 1e3 * max(sum(sec for _, sec in kernel_events(s.timed))
                     for s in record.steady) / record.steps
