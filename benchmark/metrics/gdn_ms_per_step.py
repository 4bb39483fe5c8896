"""Milliseconds per optimizer step in the Gated DeltaNet mixers: instructions
whose innermost scope is ``dstpu/gdn`` (the input projections, the q / k
normalisation, the decay and write gates, the output norm, gate and
projection) or one of the two scopes inside it, ``dstpu/delta`` (the chunked
gated delta rule) and ``dstpu/conv`` (the causal convolution) — forward,
replay and backward, on the chip where that is longest.  The scope map names
an instruction by its INNERMOST scope, so the three are summed here; a cell
whose ``dstpu/conv`` lies in another mixer has no ``dstpu/gdn`` and lists
this metric nowhere."""

from benchmark import scopes

SCOPES = ("dstpu/gdn", "dstpu/delta", "dstpu/conv")


def read(record):
    return scopes.ms_per_step(record, lambda scope, _phase: scope in SCOPES)
