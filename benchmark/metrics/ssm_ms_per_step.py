"""Milliseconds per optimizer step in the Mamba mixers: instructions whose
innermost scope is ``dstpu/ssm`` (the input projections, the projections of
the step size, ``B`` and ``C``, the output gate and projection) or one of
the two scopes inside it, ``dstpu/scan`` (the selective scan) and
``dstpu/conv`` (the causal convolution) — forward, replay and backward, on
the chip where that is longest.  The scope map names an instruction by its
INNERMOST scope, so the three are summed here."""

from benchmark import scopes

SCOPES = ("dstpu/ssm", "dstpu/scan", "dstpu/conv")


def read(record):
    return scopes.ms_per_step(record, lambda scope, _phase: scope in SCOPES)
