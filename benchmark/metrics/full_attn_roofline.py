"""The full and the cross-decoder layers' attention kernels' share of their
roofline, in %: the least time the chip could take for one forward and one
backward call per such layer per step over the whole causal triangle — the
family's ``full_attention_cost`` on the cell's shapes (40 query heads of 64
on 20 key heads and a 128-wide value head shared by a pair) — over the time
of the Pallas calls whose innermost scope is ``dstpu/attn`` (the full
layer's) or ``dstpu/xattn`` (the cross-decoder layers'), first chip.  The
windowed calls, under ``dstpu/swa``, are ``window_attn_roofline``'s.
Nothing where no such kernel ran."""

from benchmark.metrics.window_attn_roofline import pallas_seconds, share


def read(record):
    spent = pallas_seconds(record, ("dstpu/attn", "dstpu/xattn"))
    if not spent:
        return None
    return share(record, spent, ("full", "cross"),
                 record.cell.family.full_attention_cost)
