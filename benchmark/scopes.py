"""Device time by the program's own names: the traced steps' instructions
joined, by instruction name, to the scope map the program hands out.

The step program carries ``jax.named_scope("dstpu/<scope>")`` regions
(``deepspeed_tpu/observability/scopes.py`` holds the table); they reach the
compiled program as each instruction's ``op_name``.  ``trace_reduce.load``
keeps an event's name, start and end only, and an ``XLA Ops`` event is named
by its instruction (``%fusion.394 = ...``), so the scope cannot be read off
the trace: ``step_scope_map()`` of the program gives ``{instruction name:
(scope, phase)}`` for the step program the engine last built, and
``by_scope`` sums the self times of ``Steady.timed`` under it.  A fusion
counts whole under its root's scope; ``phase`` is ``forward``, ``backward``
or ``replay`` (the forward run again under recomputation).

A program without that module (any commit before the scopes) has no map:
``by_scope`` then returns None and every reader built on it says nothing.
A map that joins to nothing (a compiler or jax upgrade renamed things) reads
as ``scoped_share`` 0, and voids the other five.
"""

import collections

from benchmark import trace_reduce

BOUNDARY = "dstpu/boundary"
UNSCOPED = ("", "")

#: the program's map, asked for once per process (False: not asked yet)
_program_map = False
_table_printed = False


def scope_map(record):
    """``record.scope_map`` where a test set one, else the program's own;
    None where the program has none to give."""
    given = getattr(record, "scope_map", None)
    if given is not None:
        return given
    global _program_map
    if _program_map is False:
        try:
            from deepspeed_tpu.observability import scopes
        except ImportError:
            _program_map = None
        else:
            _program_map = scopes.step_scope_map()
    return _program_map


def by_scope(record):
    """``[{(scope, phase): seconds}]``, one dict per chip of
    ``record.steady``: self time of the traced steps' instructions under
    each scope and phase (``("", "")``: the map does not know the
    instruction, or the compiler made it and gave it no ``op_name``).  None
    without a device trace or without a map."""
    if not record.steady:
        return None
    names = scope_map(record)
    if names is None:
        return None
    chips = []
    for steady in record.steady:
        sums = collections.defaultdict(float)
        for ev, seconds in steady.timed:
            sums[names.get(trace_reduce.instr(ev.name), UNSCOPED)] += seconds
        chips.append(dict(sums))
    return chips


def seconds(chip, want):
    """Seconds of one chip's dict under the keys ``want(scope, phase)``."""
    return sum(sec for key, sec in chip.items() if want(*key))


def ms_per_step(record, want):
    """Milliseconds per optimizer step under ``want(scope, phase)``, on the
    chip where that is largest; None where ``by_scope`` is."""
    chips = by_scope(record)
    if chips is None:
        return None
    return 1e3 * max(seconds(c, want) for c in chips) / record.steps


def under(scope):
    """``want`` for everything at or below ``scope``."""
    return lambda s, _phase: s == scope or s.startswith(scope + "/")


def print_table(record, chips):
    """The scope x phase table of the first chip, ms per step, and the two
    identities that say the join is right — once per process, as lines for
    people ahead of the result line."""
    global _table_printed
    if _table_printed:
        return
    _table_printed = True
    first, steady, steps = chips[0], record.steady[0], record.steps
    total = sum(first.values())
    print("device time by scope and phase (first chip, self time, ms per "
          "step; share of all instructions):", flush=True)
    for (scope, phase), sec in sorted(first.items(),
                                      key=lambda kv: -kv[1]):
        print(f"  {scope or '(none)':24s} {phase or '-':9s} "
              f"{1e3 * sec / steps:10.3f}  {100 * sec / total:6.2f}%")
    names = scope_map(record)

    def scope_of(ev):
        return names.get(trace_reduce.instr(ev.name), UNSCOPED)[0]

    for what, home, events in (
            ("Pallas calls", "dstpu/attn",
             [(ev, sec) for ev, sec in steady.timed
              if trace_reduce.PALLAS in ev.name]),
            ("collectives", BOUNDARY,
             [(ev, sec) for ev, sec in steady.timed
              if trace_reduce.collective(ev.name)])):
        inside = sum(sec for ev, sec in events
                     if under(home)(scope_of(ev), None))
        print(f"  {what}: {1e3 * inside / steps:.3f} of "
              f"{1e3 * sum(sec for _, sec in events) / steps:.3f} ms per "
              f"step lie under {home}", flush=True)
