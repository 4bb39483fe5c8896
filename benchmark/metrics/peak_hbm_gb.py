"""Peak device memory of the process on its fullest chip, in GB (10^9
bytes, the unit of the chip's 16): ``memory_stats()["peak_bytes_in_use"]``.
Each run is its own process, so this is the cell's own peak — set-up
(weights, reference, the engine's placement copies) included."""


def read(record):
    peak = record.memory_peak_bytes
    return peak / 1e9 if peak else None
