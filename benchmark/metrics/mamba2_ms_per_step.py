"""Milliseconds per optimizer step in the Mamba-2 mixers: instructions whose
innermost scope is ``dstpu/mamba2`` (the input projection and its split,
dt's softplus, the gated norm, the output projection) or one of the two
scopes inside it, ``dstpu/ssd`` (the chunked state-space duality) and
``dstpu/conv`` (the causal convolution) — forward, replay and backward, on
the chip where that is longest.  The scope map names an instruction by its
INNERMOST scope, so the three are summed here; a cell whose ``dstpu/conv``
lies in another mixer has no ``dstpu/mamba2`` and lists this metric
nowhere."""

from benchmark import scopes

SCOPES = ("dstpu/mamba2", "dstpu/ssd", "dstpu/conv")


def read(record):
    return scopes.ms_per_step(record, lambda scope, _phase: scope in SCOPES)
