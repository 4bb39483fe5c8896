"""From a profiler trace (``.xplane.pb``) to intervals and sums.

``jax.profiler`` writes one ``XSpace``.  What a trace of this program on a
v5e holds (libtpu 0.0.34, looked at by hand in PR 22): a plane per chip,
``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per run of a
compiled program, ``jit_local(<fingerprint>)`` for the step), ``XLA Ops`` (one
event per executed HLO instruction of the TensorCore's stream), ``Async XLA
Ops`` (the spans of asynchronous copies and slices, start to done) and
``Steps``; and the host plane ``/host:CPU`` with a line per thread.  An
event on ``XLA Ops`` is named by the instruction's whole HLO text —
``%fusion.394 = (bf16[6400]{0:T(1024)...}, ...) fusion(...)`` — so the
instruction's name, its opcode and its result shape are parsed out of it
(``instr``, ``opcode``, ``label``).  A Pallas kernel is a ``custom-call``
whose text holds ``custom_call_target="tpu_custom_call"``, under a name XLA
took from the jax scope (``%closed_call.10``, ``%checkpoint.10``).
``while`` instructions enclose the events of their bodies, so sums over names
use SELF time — an event's duration less what its enclosed events cover.
The benchmark's own host spans are ``TraceAnnotation``s whose names start
with ``bench/``; they sit on the host plane on the same clock.

Everything here is arithmetic on ``Event(name, start, end)`` in seconds, so
the tests check it against hand-computed answers on a small trace
(``benchmark/testdata``).  ``python -m benchmark.trace_reduce <file-or-dir>``
prints a trace's structure: look at one by hand before trusting a reader.
"""

import collections
import functools
import glob
import os
import re
import sys

Event = collections.namedtuple("Event", "name start end")

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)\Z")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench/"

#: HLO collectives; ``-start``/``-done`` are the two ends of an asynchronous
#: one
COLLECTIVE = re.compile(
    r"(all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute|"
    r"collective-broadcast)(-start|-done)?\Z")
#: instructions that only enclose others; their self time is loop overhead
CONTROL_FLOW = ("while", "conditional", "call")
PALLAS = 'custom_call_target="tpu_custom_call"'

_INSTR = re.compile(r"%?([^\s=]+)")
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
_NUMBER = re.compile(r"\.(\d+)\Z")
_LAYOUT = re.compile(r"\{[^}]*\}")


def instr(name):
    """The instruction's name: ``fusion.394`` of ``%fusion.394 = ...``."""
    return _INSTR.match(name).group(1)


def result_shape(name):
    """The result's shape without layouts, ``(bf16[6400], bf16[4,1024])``;
    empty where the name is not HLO text."""
    head, eq, rest = name.partition(" = ")
    m = _OPCODE.search(" " + rest) if eq else None
    return _LAYOUT.sub("", rest[:m.start()].strip()) if m else ""


@functools.lru_cache(maxsize=1 << 16)
def opcode(name):
    """The instruction's opcode (``fusion``, ``while``, ``custom-call``,
    ``all-reduce``); for a name that is not HLO text, the instruction's name
    without its number."""
    head, eq, rest = name.partition(" = ")
    m = _OPCODE.search(" " + rest) if eq else None
    return m.group(1) if m else _NUMBER.sub("", instr(name))


@functools.lru_cache(maxsize=1 << 16)
def label(name):
    """A short form for people: name, opcode and result shape."""
    return f"{instr(name)} {opcode(name)} {result_shape(name)}".strip()[:120]


@functools.lru_cache(maxsize=1 << 16)
def collective(name):
    """``(kind, edge, number)`` of a collective instruction — by its opcode,
    or by its name where it is wrapped in ``async-start``/``async-done`` —
    else None.  ``edge`` is ``-start``, ``-done`` or None (synchronous)."""
    number = _NUMBER.search(instr(name))
    for candidate in (opcode(name), _NUMBER.sub("", instr(name))):
        m = COLLECTIVE.match(candidate)
        if m:
            return m.group(1), m.group(2), number.group(1) if number else ""
    return None


def is_control_flow(name):
    return opcode(name) in CONTROL_FLOW


class Device:
    """One device plane: its operation and program-run events, by start."""

    def __init__(self, index, ops, modules):
        self.index = index
        self.ops = sorted(ops, key=lambda e: (e.start, -e.end))
        self.modules = sorted(modules, key=lambda e: e.start)


class Trace:
    def __init__(self, devices, host_spans):
        self.devices = sorted(devices, key=lambda d: d.index)
        self.host_spans = sorted(host_spans, key=lambda e: e.start)


def find_xplane(path):
    """``path`` itself, or the one ``.xplane.pb`` under a trace directory."""
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {path}, "
                                f"found {found}")
    return found[0]


def _events(line):
    return [Event(e.name, e.start_ns * 1e-9,
                  (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]


def load(path):
    """The ``Trace`` of an ``.xplane.pb`` (or of the directory
    ``jax.profiler`` wrote it under)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(path))
    devices, spans = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            devices.append(Device(
                int(m.group(1)),
                _events(lines[OPS_LINE]) if OPS_LINE in lines else [],
                _events(lines[MODULES_LINE]) if MODULES_LINE in lines
                else []))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e.name.startswith(SPAN_PREFIX)]
    return Trace(devices, spans)


# ------------------------------------------------------------ intervals

def merge(intervals):
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if min(e, t1) > max(s, t0)]


def length(intervals):
    return sum(e - s for s, e in merge(intervals))


def subtract(a, b):
    """The part of the union of ``a`` that no interval of ``b`` covers."""
    out, b, j = [], merge(b), 0
    for s, e in merge(a):
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def gaps(intervals, t0, t1):
    """The parts of ``[t0, t1]`` that no interval covers."""
    return subtract([(t0, t1)], intervals)


# ---------------------------------------------------------- one device

def in_window(events, t0, t1):
    """Events that start inside ``[t0, t1)``."""
    return [e for e in events if t0 <= e.start < t1]


def self_times(events):
    """``[(event, self_seconds)]``: each event's duration less the time its
    enclosed events (same line, nested inside it) cover.  ``events`` sorted by
    (start, -end), as ``Device.ops`` is."""
    out, stack = [], []          # stack of [event, covered_by_children]

    def close(upto):
        while stack and stack[-1][0].end <= upto:
            ev, covered = stack.pop()
            out.append((ev, max(ev.end - ev.start - covered, 0.0)))
            if stack:
                stack[-1][1] += ev.end - ev.start

    for ev in events:
        close(ev.start)
        stack.append([ev, 0.0])
    close(float("inf"))
    return out


def sum_by_label(timed):
    """``{label: (seconds, count)}`` over ``[(event, seconds)]``."""
    sums = collections.defaultdict(lambda: [0.0, 0])
    for ev, seconds in timed:
        key = label(ev.name)
        sums[key][0] += seconds
        sums[key][1] += 1
    return {k: tuple(v) for k, v in sums.items()}


def step_window(device, steps):
    """``(t0, t1)`` spanning the last ``steps`` runs of the program that took
    most of the device's time — the step program — from the start of the
    first to the end of the last; None when the plane has no such runs."""
    by_name = collections.defaultdict(list)
    for ev in device.modules:
        by_name[ev.name].append(ev)
    if not by_name:
        return None
    runs = max(by_name.values(),
               key=lambda evs: sum(e.end - e.start for e in evs))[-steps:]
    return runs[0].start, runs[-1].end


def busy_seconds(events, t0, t1):
    """Seconds of ``[t0, t1]`` in which one of ``events`` ran."""
    return length(clip([(e.start, e.end) for e in events], t0, t1))


#: one chip's traced steps: the window ``[t0, t1]`` of the step program's
#: last runs, the instructions that start in it with their self times, and
#: the seconds of the window in which any of them ran
Steady = collections.namedtuple("Steady", "device t0 t1 timed busy")


def steady(trace, steps):
    """A ``Steady`` for each chip of ``trace`` that ran the step program."""
    out = []
    for device in trace.devices:
        window = step_window(device, steps)
        if window is None:
            continue
        ops = in_window(device.ops, *window)
        out.append(Steady(device, *window, self_times(ops),
                          busy_seconds(ops, *window)))
    return out


def collective_intervals(events):
    """``(start, end)`` of every collective among ``events``: a synchronous
    one is its own event; an asynchronous one runs from the start of its
    ``-start`` event to the end of the ``-done`` event of the same number."""
    out, open_starts = [], {}
    for ev in events:
        found = collective(ev.name)
        if not found:
            continue
        kind, edge, number = found
        if edge == "-start":
            open_starts[(kind, number)] = ev.start
        elif edge == "-done":
            out.append((open_starts.pop((kind, number), ev.start), ev.end))
        else:
            out.append((ev.start, ev.end))
    return out


def exposed_collective_seconds(events):
    """Seconds in which a collective was under way and no other operation
    ran on the device: collective intervals less the intervals of every
    instruction that is neither a collective nor control flow."""
    other = [(e.start, e.end) for e in events
             if not collective(e.name) and not is_control_flow(e.name)]
    return length(subtract(collective_intervals(events), other))


def attribute_gaps(idle, host_spans):
    """``[(span_name, seconds)]`` for each idle gap, longest first: the host
    span that covers most of the gap (``"none"`` when no span overlaps)."""
    out = []
    for s, e in idle:
        best, best_overlap = "none", 0.0
        for span in host_spans:
            overlap = min(e, span.end) - max(s, span.start)
            if overlap > best_overlap:
                best, best_overlap = span.name, overlap
        out.append((best, e - s))
    return sorted(out, key=lambda g: -g[1])


# ----------------------------------------------------------- by hand

def describe(path, top=25):
    """Print what a trace holds: planes, lines, event counts, the stats
    keys, the names that take most time."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(path))
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                print(f"  LINE {line.name!r}: empty")
                continue
            t0 = min(e.start_ns for e in events)
            t1 = max(e.start_ns + e.duration_ns for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{t0 * 1e-9:.6f} .. {t1 * 1e-9:.6f} s")
            sums = collections.defaultdict(lambda: [0.0, 0])
            for e in events:
                sums[e.name][0] += e.duration_ns * 1e-9
                sums[e.name][1] += 1
            for name, (sec, n) in sorted(sums.items(),
                                         key=lambda kv: -kv[1][0])[:top]:
                print(f"    {sec:10.6f} s  x{n:<6d} {name[:100]}")
            stats = dict(events[len(events) // 2].stats)
            print(f"    stats of one event: "
                  f"{ {k: str(v)[:60] for k, v in stats.items()} }")


if __name__ == "__main__":
    describe(sys.argv[1])
