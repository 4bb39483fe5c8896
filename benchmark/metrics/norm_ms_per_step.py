"""Milliseconds per optimizer step in instructions whose innermost scope is
``dstpu/norm`` (``layers.layer_norm`` in the blocks, the embedding and the
head), forward, replay and backward, on the chip where that is longest.  A
fusion counts under its root's scope, so a LayerNorm the compiler fused
into a neighbouring matmul is not here, and one that took a residual add or
a cast in is here whole."""

from benchmark import scopes


def read(record):
    return scopes.ms_per_step(record, scopes.under("dstpu/norm"))
