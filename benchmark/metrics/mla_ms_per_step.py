"""Milliseconds per optimizer step under ``dstpu/mla`` — the latent
attention's projections (queries; the down projection to the latent and the
shared rotary key; the up projection to the heads' keys and values) and the
RMSNorm on the latent — forward, replay and backward, on the chip where that
is longest.  The rotation (``dstpu/rope``), the core and the output
projection (``dstpu/attn``) are not in this number."""

from benchmark import scopes


def read(record):
    return scopes.ms_per_step(record, scopes.under("dstpu/mla"))
