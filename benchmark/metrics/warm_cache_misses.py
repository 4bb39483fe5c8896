"""Programs compiled during set-up because the persistent compilation
cache did not hold them (``deepspeed_tpu.resilience.COUNTERS``): the whole
set of the cell's programs on a checkout's first run, 0 on every run after
it."""


def read(record):
    return record.setup_cache_misses
