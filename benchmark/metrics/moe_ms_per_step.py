"""Milliseconds per optimizer step in the expert layers' own scopes:
instructions whose innermost scope is ``dstpu/moe`` (the expert layer's own
glue: the sum of the routed and the shared parts) or one of the two scopes
inside it, ``dstpu/route`` (scores, top-k, gates, sort, the two gathers,
balance loss) and ``dstpu/experts`` (the grouped matmuls) — forward, replay
and backward, on the chip where that is longest.  The scope map names an
instruction by its INNERMOST scope, so the three are summed here; the shared
experts run under ``dstpu/ffn`` with the dense layer's MLP and are not in
this number.  Nothing where the program has no such scope."""

from benchmark import scopes

SCOPES = ("dstpu/moe", "dstpu/route", "dstpu/experts")


def read(record):
    return scopes.ms_per_step(record, lambda scope, _phase: scope in SCOPES)
