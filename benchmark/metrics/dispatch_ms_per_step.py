"""Host milliseconds one un-fenced ``engine.train_batch`` call takes (median
over the stretch): the benchmark's own ``dispatch`` span — staging the host
batch, the program's bookkeeping, the enqueue.  It costs tokens only where
the device waits for it (``device_idle_share``)."""

import statistics


def read(record):
    calls = [e.end - e.start for e in record.spans if e.name == "dispatch"]
    return 1e3 * statistics.median(calls) if calls else None
