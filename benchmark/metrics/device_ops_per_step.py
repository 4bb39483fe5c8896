"""Operations the device runs per optimizer step: events on the busiest
chip's ``XLA Ops`` line inside the traced steps, over the steps.  A count:
it repeats exactly for one program, and moves when a PR changes how the
step is fused or how often a loop body runs."""


def read(record):
    if not record.steady:
        return None
    return max(len(s.timed) for s in record.steady) / record.steps
