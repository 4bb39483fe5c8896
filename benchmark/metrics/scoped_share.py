"""Share of the traced steps' device time that the program's scope map
places under one of its ``dstpu/`` scopes: self time of the instructions it
names ÷ self time of all instructions, on the chip where that is largest.
The guard of every reader over ``benchmark/scopes.py``: when a compiler or
jax upgrade breaks the join by instruction name it falls to 0, and
``boundary_ms_per_step``, ``optimizer_ms_per_step``, ``remat_replay_share``,
``head_ms_per_step`` and ``norm_ms_per_step`` are then void.  Prints the
whole scope x phase table once, ahead of the result line."""

from benchmark import scopes


def read(record):
    chips = scopes.by_scope(record)
    if chips is None:
        return None
    scopes.print_table(record, chips)
    return 100.0 * max(scopes.seconds(c, lambda scope, _phase: bool(scope))
                       / sum(c.values()) for c in chips)
