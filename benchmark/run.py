"""``python -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one process, one cell of ``BENCHMARK.json``, once.

Requires a TPU whose ``device_kind`` is in ``benchmark/peaks.json`` and at
least the cell's ``chips`` devices, and uses exactly the first ``chips`` of
them; anything else ends the process non-zero before a result is printed —
there is no CPU fallback.  Earlier lines are for people; the last line of
stdout is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device`` and, traced, ``breakdown``.

``--rehearse-cpu`` debugs the harness: the same code at the family's tiny
sizes on four virtual CPU devices.  It says ``platform: cpu`` and reports no
metric: a number from the CPU is never printed under a device metric's name.
"""

import time

_T_ENTRY = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

from benchmark import cell as cells  # noqa: E402
from benchmark import trace_reduce  # noqa: E402

BREAKDOWN_ENTRIES = 10


def log(msg=""):
    print(msg, flush=True)


def process_age_s():
    """Seconds since this process was started, from ``/proc`` (interpreter
    start-up and the imports above belong to set-up); 0 where ``/proc`` does
    not say."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return (time.clock_gettime(time.CLOCK_BOOTTIME)
                - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return 0.0


class SetupClock:
    """Set-up phases on the host clock, from process start to the first
    dispatch of the window.  ``setup_s`` is that stretch less the phases
    entered with ``counted=False``: the machine's own time, in which no code
    of the repo runs (opening the chip, see ``require_devices``)."""

    def __init__(self):
        self.start = time.perf_counter() - process_age_s()
        self.phases = [("process_start_and_imports", _T_ENTRY - self.start)]
        self.not_counted = {}
        self.setup_s = None

    @contextlib.contextmanager
    def __call__(self, name, counted=True):
        t = time.perf_counter()
        yield
        self.phases.append((name, time.perf_counter() - t))
        if not counted:
            self.not_counted[name] = self.phases[-1][1]

    def mark_setup_done(self):
        self.setup_s = (time.perf_counter() - self.start
                        - sum(self.not_counted.values()))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="debug the harness at tiny sizes on four virtual "
                         "CPU devices (never a result)")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="with --trace 1: keep the profiler's files there "
                         "(python -m benchmark.trace_reduce DIR shows them)")
    ap.add_argument("--probe-reference", action="store_true",
                    help="also run the plain reference at lower precisions "
                         "and print its losses (prices the loss tolerance)")
    return ap.parse_args(argv)


def require_devices(cell, rehearse, clock):
    """The device description for the result line; exits non-zero unless the
    machine holds what the cell needs.

    ``jax.devices()`` opens the chip: 5 to 15 s of the TPU runtime's own
    start-up, which drift by seconds from one set of runs to the next on one
    machine while every other phase repeats to 0.2 s (PERF.md, section 2).
    It is called here, before any module of ``deepspeed_tpu`` is imported,
    so no work of the program can move into it; ``setup_s`` leaves it out
    and the phases report it."""
    import jax
    with clock("open_chip", counted=False):
        devices = jax.devices()
    found = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices)}
    log(f"jax {jax.__version__}  platform: {found['platform']}  "
        f"device_kind: {found['kind']}  device_count: {found['count']}")
    if rehearse:
        return found, None
    if found["platform"] != "tpu":
        raise SystemExit(f"benchmark.run needs a TPU; jax found platform="
                         f"{found['platform']!r} (--rehearse-cpu debugs the "
                         f"harness on the CPU and reports no metric)")
    if found["count"] < cell.chips:
        raise SystemExit(f"cell {cell.name!r} needs {cell.chips} chips; jax "
                         f"found {found['count']}")
    try:
        return found, cells.peaks(found["kind"], cell.root)
    except cells.CellError as e:
        raise SystemExit(str(e)) from None


def per_layer_metrics(cell, record):
    """Every per-layer metric of the cell whose reader finds what it reads."""
    metrics = {}
    for entry in cell.per_layer:
        value = cells.plugin(cell.root, "metrics", entry["name"]).read(record)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return metrics


def device_times(record):
    """``busy_s`` and ``window_s`` averaged over the chips, and the
    breakdown of the first chip's window."""
    if not record.steady:
        return {}, None
    n = len(record.steady)
    first = record.steady[0]
    ops = sorted(((name, sec) for name, (sec, _) in
                  trace_reduce.sum_by_label(first.timed).items()),
                 key=lambda kv: -kv[1])
    idle = trace_reduce.gaps([(ev.start, ev.end) for ev, _ in first.timed],
                             first.t0, first.t1)
    gaps = trace_reduce.attribute_gaps(idle, record.trace.host_spans)
    return ({"busy_s": sum(s.busy for s in record.steady) / n,
             "window_s": sum(s.t1 - s.t0 for s in record.steady) / n},
            {"device_ops": [list(x) for x in ops[:BREAKDOWN_ENTRIES]],
             "idle_gaps": [list(x) for x in gaps[:BREAKDOWN_ENTRIES]]})


def main(argv=None):
    opts = parse(argv)
    if opts.rehearse_cpu:
        # must precede the first jax import
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
    clock = SetupClock()
    try:
        with clock("load_cell_import_jax"):
            cell = cells.load(opts.workload)
    except cells.CellError as e:
        raise SystemExit(str(e)) from None
    if opts.rehearse_cpu:
        cell.config = cell.family.tiny(cell.config)
    log(f"cell {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name}, layout {cell.layout['name']}, "
        f"{cell.chips} chip(s)")
    device, opts.peaks = require_devices(cell, opts.rehearse_cpu, clock)

    with clock("import_deepspeed_tpu"):
        from deepspeed_tpu.utils import compile_cache
    if not opts.rehearse_cpu:
        # before the first jit, so that the weights' and the reference's
        # programs are cached with the engine's (which enables the same
        # directory again); a rehearsal keeps no cache
        log(f"compile cache: "
            f"{compile_cache.enable(compile_cache.checkout_dir(cell.root))}")

    result = cell.kind.run(cell, opts, clock, log)
    record = types.SimpleNamespace(cell=cell, peaks=opts.peaks,
                                   **result["record"])
    record.steady = (trace_reduce.steady(record.trace, record.steps)
                     if record.trace else [])

    log("set-up phases (s): " + ", ".join(f"{n} {s:.2f}"
                                          for n, s in clock.phases)
        + f"; setup_s {clock.setup_s:.2f}, which leaves out "
        + " and ".join(clock.not_counted))
    for name, check in result["checks"].items():
        log(f"check {name}: {check}")

    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": {}, "device": device}
    device["memory_peak_bytes"] = record.memory_peak_bytes
    if opts.rehearse_cpu:
        line["rehearsal"] = True
    elif opts.trace:
        line["metrics"] = per_layer_metrics(cell, record)
        times, breakdown = device_times(record)
        if not times:
            raise SystemExit("the traced stretch holds no run of a program "
                             "on a device plane: nothing to report")
        device.update(times)
        line["breakdown"] = breakdown
    else:
        values = dict(result["end_to_end"], setup_s=clock.setup_s)
        for entry in cell.end_to_end:
            line["metrics"][entry["name"]] = {
                "value": values[entry["name"]], "unit": entry["unit"]}
    line["setup_phases_s"] = dict(clock.phases)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
