"""The windowed attention kernels' share of their roofline, in %: the least
time the chip could take for one forward and one backward call per
sliding-window layer per step over the IN-WINDOW pairs only — the family's
``window_attention_cost`` on the cell's shapes — over the time of the Pallas
calls under ``dstpu/swa`` (first chip).  The scope map tells a windowed call
from the full and the cross-decoder layers' calls, which have the same
result shapes.  A kernel that visits every tile of the causal triangle reads
a small share here.  Nothing where no kernel ran under the scope."""

from benchmark import flops, scopes, trace_reduce


def pallas_seconds(record, innermost):
    """Seconds of the first chip's Pallas calls whose innermost scope is one
    of ``innermost``; None without a trace or a map."""
    names = scopes.scope_map(record)
    if not record.steady or names is None:
        return None
    return sum(
        sec for ev, sec in record.steady[0].timed
        if trace_reduce.PALLAS in ev.name and names.get(
            trace_reduce.instr(ev.name), scopes.UNSCOPED)[0] in innermost)


def share(record, spent, kinds, cost):
    """100 x the least time for one forward and one backward call of
    ``cost`` per layer of ``kinds`` per step, over ``spent`` seconds."""
    cell = record.cell
    held = cell.family.kinds_held(cell.config)
    layers = sum(held.count(k) for k in kinds)
    least = sum(flops.roofline_seconds(
        *cost(cell.config, cell.traffic, d), record.peaks)[0]
        for d in ("fwd", "bwd"))
    return (100.0 * record.steps * cell.traffic["gas"] * layers * least
            / spent)


def read(record):
    spent = pallas_seconds(record, ("dstpu/swa",))
    if not spent:
        return None
    return share(record, spent, ("swa",),
                 record.cell.family.window_attention_cost)
