"""Expert-layer forward passes per optimizer step whose held (token, choice)
pairs did not fit the held-row prefix, so that the layer ran its worst case
over every pair: the step scalar ``moe/overflow_passes`` over the optimizer
steps since ``initialize`` (warm-up and the traced stretch).  A guard at 0,
like ``warm_cache_misses``: the speed of the prefix holds only while no layer
overflows, and an overflowing pass costs about three times a prefix pass.
Nothing where the program counts no ``moe/*`` scalar."""

from benchmark import step_scalars


def read(record):
    found = step_scalars.expert_layers(record)
    if found is None:
        return None
    values, _, snap = found
    return values["moe/overflow_passes"] / snap["steps"]
