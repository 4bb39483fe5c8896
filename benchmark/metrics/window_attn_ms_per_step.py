"""Milliseconds per optimizer step in instructions whose innermost scope is
``dstpu/swa`` — the core of sliding-window attention inside ``dstpu/attn``:
the windowed kernel calls and the layout copies around them — forward,
replay and backward, on the chip where that is longest."""

from benchmark import scopes


def read(record):
    return scopes.ms_per_step(record, scopes.under("dstpu/swa"))
