"""Share of the traced steps in which a collective was under way and no
other operation ran on the device — the part of ``collective_ms_per_step``
that compute does not hide — on the chip where it is largest."""

from benchmark import trace_reduce


def read(record):
    if not record.steady:
        return None
    return 100.0 * max(
        trace_reduce.exposed_collective_seconds([ev for ev, _ in s.timed])
        / (s.t1 - s.t0) for s in record.steady)
