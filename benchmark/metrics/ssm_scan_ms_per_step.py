"""Milliseconds per optimizer step in instructions whose innermost scope is
``dstpu/scan`` — the selective state-space scan inside ``dstpu/ssm``: the
chunk loops, the states kept at the chunk boundaries, and in the backward
the recurrence run again — forward, replay and backward, on the chip where
that is longest."""

from benchmark import scopes


def read(record):
    return scopes.ms_per_step(record, scopes.under("dstpu/scan"))
