"""Milliseconds per optimizer step in instructions whose innermost scope is
``dstpu/ssd`` — the chunked state-space duality inside ``dstpu/mamba2``:
each segment's within-chunk products, the walk that carries the matrix
state from chunk to chunk, the products from the state, and in the backward
a segment run again and differentiated — forward, replay and backward, on
the chip where that is longest."""

from benchmark import scopes


def read(record):
    return scopes.ms_per_step(record, scopes.under("dstpu/ssd"))
