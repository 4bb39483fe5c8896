"""Milliseconds per optimizer step in instructions whose innermost scope is
``dstpu/rope`` (the rotary tables and the rotation of q and k inside
``dstpu/attn``), forward, replay and backward, on the chip where that is
longest.  A fusion counts under its root's scope, so this is what the
compiler left standing alone of the rotation: a rotation fused into the
projection before it or the kernel's operand copy after it is not here."""

from benchmark import scopes


def read(record):
    return scopes.ms_per_step(record, scopes.under("dstpu/rope"))
