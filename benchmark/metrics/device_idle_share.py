"""Share of the traced steps in which no operation ran on the device, on
the chip that idled most: 1 - union of its operation intervals / window."""


def read(record):
    if not record.steady:
        return None
    return 100.0 * max(1.0 - s.busy / (s.t1 - s.t0) for s in record.steady)
