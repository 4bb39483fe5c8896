"""The gated delta rule's share of its roofline, in %: the least time the
chip could take for one forward and one backward rule per Gated DeltaNet
layer per step — the family's ``delta_rule_cost`` on the cell's shapes (the
recurrence's three products a token and value head, thrice that backward,
against q, k, v, o, the gates and the boundary states; the bf16 peak against
the HBM bytes, whichever is longer) — over the time under ``dstpu/delta``
(first chip, every phase: a forward replayed under recomputation is time the
step spends and no required work).

The numerator prices the RECURRENCE, whatever implements it: a chunked
form's triangular solves and extra products, and the latency of the chunks
that follow each other (256 at T 16,384), are in the denominator alone, so
the share says how far the rule's time is from what its arithmetic and its
traffic need, not how busy the matrix unit is.  Nothing where no
instruction lies under the scope."""

from benchmark import flops, scopes


def read(record):
    chips = scopes.by_scope(record)
    if chips is None:
        return None
    spent = scopes.seconds(chips[0], scopes.under("dstpu/delta"))
    if not spent:
        return None
    cell = record.cell
    layers = cell.family.kinds_held(cell.config).count("gdn")
    least = sum(flops.roofline_seconds(
        *cell.family.delta_rule_cost(cell.config, cell.traffic, d),
        record.peaks)[0] for d in ("fwd", "bwd"))
    return (100.0 * record.steps * cell.traffic["gas"] * layers * least
            / spent)
