"""Rows that landed over rows worked on, in %: the step scalar
``moe/held_pairs`` (the (token, choice) pairs on the experts held, summed
over the expert layers and the steps) over ``routed_rows_prefix`` — the
static prefix every gather, mask and activation of a non-overflowing pass
runs over — times the expert-layer passes.  Useful outcomes over attempts: 50
where the prefix is twice the even share and the router is even.  Times
``routed_rows_prefix / routed_rows_all`` it is the share of all pairs that
landed here.  Nothing where the program counts no ``moe/*`` scalar."""

from benchmark import step_scalars


def read(record):
    found = step_scalars.expert_layers(record)
    if found is None:
        return None
    values, gauges, snap = found
    worked = (gauges["routed_rows_prefix"]
              * step_scalars.expert_layer_passes(gauges, snap))
    return 100.0 * values["moe/held_pairs"] / worked if worked else None
