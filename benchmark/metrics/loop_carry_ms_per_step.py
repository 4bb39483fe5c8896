"""Milliseconds per optimizer step in instructions whose innermost scope is
``dstpu/loop`` — under the pass loop of a looped model but under neither
``dstpu/block``, ``dstpu/norm``, ``dstpu/head`` nor ``dstpu/exit``: the pass
loop's own slicing and stacking, and the accumulation of the shared
weights' gradients over the passes — all phases, on the chip where that is
longest.  A program with no such scope (any other model, any commit before
the looped model) reads 0, and one with no scope map says nothing."""

from benchmark import scopes


def read(record):
    return scopes.ms_per_step(record,
                              lambda scope, _phase: scope == "dstpu/loop")
