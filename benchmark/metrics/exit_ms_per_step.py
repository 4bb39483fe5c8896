"""Milliseconds per optimizer step under ``dstpu/exit`` — the exit gate of a
looped model after every pass, the exit distribution, its entropy and the
weighted sum of the exits' cross-entropies, forward and backward — on the
chip where that is longest.  The exits' vocabulary heads are under
``dstpu/head``."""

from benchmark import scopes


def read(record):
    return scopes.ms_per_step(record, scopes.under("dstpu/exit"))
