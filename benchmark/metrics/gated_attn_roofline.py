"""The gated attention's core kernels' share of their roofline, in %: the
least time the chip could take for one forward and one backward call per
full-attention layer per step over the whole causal triangle at the
PUBLISHED head — the family's ``gated_attention_cost`` on the cell's shapes
(16 query heads of 256 on 2 key/value heads) — over the time of the Pallas
calls whose innermost scope is ``dstpu/attn`` (first chip).  The scope map
tells them from the grouped matmuls, Pallas calls too, which lie under
``dstpu/experts``.  The norms, the rotation and the output gate around the
core are not in the denominator.  Nothing where no such kernel ran."""

from benchmark.metrics.window_attn_roofline import pallas_seconds, share


def read(record):
    spent = pallas_seconds(record, ("dstpu/attn",))
    if not spent:
        return None
    return share(record, spent, ("full",),
                 record.cell.family.gated_attention_cost)
