"""The latent attention's core kernels' share of their roofline, in %: the
least time the chip could take for one forward and one backward call per
layer per step over the whole causal triangle at the PUBLISHED head sizes —
the family's ``latent_attention_cost`` on the cell's shapes (16 heads, a
192-wide query / key head, a 128-wide value head) — over the time of the
Pallas calls whose innermost scope is ``dstpu/attn`` (first chip).  The
scope map tells them from the grouped matmuls, Pallas calls too, which lie
under ``dstpu/experts``.  A kernel that pads 192 to 256 reads a lower share
here, not more work done.  Nothing where no such kernel ran."""

from benchmark.metrics.window_attn_roofline import pallas_seconds, share


def read(record):
    spent = pallas_seconds(record, ("dstpu/attn",))
    if not spent:
        return None
    return share(record, spent, ("dense", "moe"),
                 record.cell.family.latent_attention_cost)
