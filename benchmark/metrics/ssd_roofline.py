"""The state-space duality's share of its roofline, in %: the least time the
chip could take for one forward and one backward op per Mamba-2 layer per
step — the family's ``ssd_cost`` on the cell's shapes (the chunked form's
products at the PUBLISHED chunk, 256, against what the op must read and
write; the bf16 peak against the HBM bytes, whichever is longer) — over the
time under ``dstpu/ssd`` (first chip, every phase: a forward replayed under
recomputation is time the step spends and no required work).

The work is fixed by the configuration, not by the code that does it: a
walk at another chunk, in another precision or in a kernel is measured
against the same numerator.  The program's float32 products at the highest
precision, its replays and the latency of the chunks that follow each other
are in the denominator alone.  The layers are the program's own count, the
gauge ``layers_mamba2``; ``ssd_chunks_per_row`` says that the op walked
chunks at all.  Nothing without a trace, a map, an instruction under the
scope or those gauges."""

from benchmark import flops, model_gauges, scopes


def read(record):
    chips = scopes.by_scope(record)
    if chips is None:
        return None
    spent = scopes.seconds(chips[0], scopes.under("dstpu/ssd"))
    found = model_gauges.gauges(record) or {}
    layers = found.get("layers_mamba2")
    if not spent or not layers or not found.get("ssd_chunks_per_row"):
        return None
    cell = record.cell
    least = sum(flops.roofline_seconds(
        *cell.family.ssd_cost(cell.config, cell.traffic, d),
        record.peaks)[0] for d in ("fwd", "bwd"))
    return (100.0 * record.steps * cell.traffic["gas"] * layers * least
            / spent)
