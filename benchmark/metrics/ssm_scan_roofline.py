"""The selective scan's share of its MEMORY roofline, in %: the time the
chip's HBM needs to move the bytes of one forward and one backward scan per
Mamba layer per step — the family's ``scan_cost`` on the cell's shapes, over
the HBM peak — over the time under ``dstpu/scan`` (first chip, every phase:
a forward replayed under recomputation is time the step spends and no
required work).

The floor is HBM traffic ALONE.  The recurrence is float32 elementwise and
exponential work on the vector and transcendental units (``scan_cost``'s
operations: about 19 G a layer a step at the cell's sizes), whose peak rate
``benchmark/peaks.json`` does not hold; priced at the bf16 matrix peak it
would never bind, so it is left out rather than understated.  A scan bound
by that work cannot reach 100% here: the share is an upper bound on the
headroom, not a time a kernel is known to reach.  Nothing where no
instruction lies under the scope."""

from benchmark import scopes


def read(record):
    chips = scopes.by_scope(record)
    if chips is None:
        return None
    spent = scopes.seconds(chips[0], scopes.under("dstpu/scan"))
    if not spent:
        return None
    cell = record.cell
    layers = cell.family.kinds_held(cell.config).count("mamba")
    nbytes = sum(cell.family.scan_cost(cell.config, cell.traffic, d)[1]
                 for d in ("fwd", "bwd"))
    least = nbytes / record.peaks["hbm_bytes_per_s"]
    return (100.0 * record.steps * cell.traffic["gas"] * layers * least
            / spent)
