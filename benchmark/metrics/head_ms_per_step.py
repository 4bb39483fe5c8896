"""Milliseconds per optimizer step under ``dstpu/head`` — the final norm or
transform, the gather of the labelled positions, the vocabulary matmul and
the cross-entropy, forward and backward — on the chip where that is
longest."""

from benchmark import scopes


def read(record):
    return scopes.ms_per_step(record, scopes.under("dstpu/head"))
