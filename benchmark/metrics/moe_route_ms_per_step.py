"""Milliseconds per optimizer step under ``dstpu/route`` — what routing costs
that is no matmul of an expert: the router's scores, the top-k, the gates,
the sort of the (token, choice) pairs, the gather into the experts' order
and the weighted gather back, the balance loss — forward, replay and
backward, on the chip where that is longest."""

from benchmark import scopes


def read(record):
    return scopes.ms_per_step(record, scopes.under("dstpu/route"))
