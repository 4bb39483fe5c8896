"""Milliseconds per optimizer step in which a collective (all-reduce,
reduce-scatter, all-gather, all-to-all, collective-permute) was under way on
the chip where that is longest — an asynchronous one from its ``-start`` to
the end of its ``-done``.  0 where the step has none (one chip)."""

from benchmark import trace_reduce


def read(record):
    if not record.steady:
        return None
    return 1e3 * max(
        trace_reduce.length(trace_reduce.collective_intervals(
            [ev for ev, _ in s.timed]))
        for s in record.steady) / record.steps
