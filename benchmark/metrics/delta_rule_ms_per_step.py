"""Milliseconds per optimizer step in instructions whose innermost scope is
``dstpu/delta`` — the chunked gated delta rule inside ``dstpu/gdn``: the
chunks' triangular solves and products, the loop over the chunks that
carries the matrix state, the states kept at the chunk boundaries, and in
the backward a segment prepared again — forward, replay and backward, on the
chip where that is longest."""

from benchmark import scopes


def read(record):
    return scopes.ms_per_step(record, scopes.under("dstpu/delta"))
