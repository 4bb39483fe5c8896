"""Milliseconds per optimizer step under ``dstpu/boundary/update``: the
optimizer's update (Adam, or LAMB with its trust-ratio norms), the clipping
factor and the skip-on-overflow select, on the chip where that is longest."""

from benchmark import scopes


def read(record):
    return scopes.ms_per_step(record,
                              scopes.under(scopes.BOUNDARY + "/update"))
