"""Milliseconds per optimizer step under ``dstpu/gmu`` — the Gated Memory
Units: the gate's projection, its product with the memory, the output
projection — forward, replay and backward, on the chip where that is
longest."""

from benchmark import scopes


def read(record):
    return scopes.ms_per_step(record, scopes.under("dstpu/gmu"))
