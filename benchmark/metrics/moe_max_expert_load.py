"""The busiest held expert's rows in one expert-layer pass (the step scalar
``moe/max_expert_rows``: the largest group the grouped matmuls saw, over
layers and steps) over the even load ``routed_rows_all / experts_total``: 1
for a perfectly even router, ``HEADROOM`` (2) on EVERY held expert is where
the prefix overflows.  Nothing where the program counts no ``moe/*``
scalar."""

from benchmark import step_scalars


def read(record):
    found = step_scalars.expert_layers(record)
    if found is None:
        return None
    values, gauges, _ = found
    even = gauges["routed_rows_all"] / gauges["experts_total"]
    return values["moe/max_expert_rows"] / even if even else None
