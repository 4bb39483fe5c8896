"""The grouped matmuls' share of their roofline, in %: the least time the
chip could take for the three products of every expert layer, forward and
backward, over the rows routed to this chip BY EXPECTATION — the family's
``expert_matmul_cost`` on the cell's shapes, the bf16 peak against the HBM
bytes, whichever is longer — over the time under ``dstpu/experts`` (first
chip, every phase: a product replayed under recomputation is time the step
spends and no required work).  Rows a run's routing sends here beyond or
short of the expectation move the denominator and not the numerator.
Nothing where no instruction lies under the scope."""

from benchmark import flops, scopes


def read(record):
    chips = scopes.by_scope(record)
    if chips is None:
        return None
    spent = scopes.seconds(chips[0], scopes.under("dstpu/experts"))
    if not spent:
        return None
    cell = record.cell
    layers = cell.family.kinds_held(cell.config).count("moe")
    least = sum(flops.roofline_seconds(
        *cell.family.expert_matmul_cost(cell.config, cell.traffic, d),
        record.peaks)[0] for d in ("fwd", "bwd"))
    return (100.0 * record.steps * cell.traffic["gas"] * layers * least
            / spent)
