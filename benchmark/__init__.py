"""The benchmark of deepspeed_tpu: the yardstick later PRs are measured with.

``python -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once, on the TPU the
process is started on, and prints one JSON object as its last line.

Everything that belongs to one configuration, one traffic mix, one layout
or one per-layer metric is a file of its own, found by the name the data
files give (``cell.py``); PERF.md says which files a new cell, configuration,
traffic mix or metric needs.  The fixed core is ``run.py`` (the command),
``cell.py`` (name → files), ``trace_reduce.py`` (profiler trace → intervals
and sums), ``flops.py`` (operations and bytes from shapes) and
``peaks.json`` (the chip's published peaks).
"""
