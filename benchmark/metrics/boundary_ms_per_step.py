"""Milliseconds per optimizer step under ``dstpu/boundary`` — everything
between the last backward instruction and the next forward: the gradient
flatten, ``boundary/reduce`` (the data-parallel reduction, overflow and norm
agreement), ``boundary/update`` (the optimizer) and ``boundary/gather`` (the
cast to the compute dtype, the all-gather, the write-back to ``params``) —
on the chip where that is longest."""

from benchmark import scopes


def read(record):
    return scopes.ms_per_step(record, scopes.under(scopes.BOUNDARY))
