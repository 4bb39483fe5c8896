"""Share of the traced steps spent running the forward again under
activation recomputation: self time of the instructions whose ``op_name``
lies inside ``jax.checkpoint``'s ``rematted_computation`` (phase ``replay``,
whatever the scope) ÷ the traced steps, on the chip where that is largest.
Required FLOPs do not count the replay (``mfu``), so this is the part of the
step a recomputation policy can give back."""

from benchmark import scopes


def read(record):
    chips = scopes.by_scope(record)
    if chips is None:
        return None
    return 100.0 * max(
        scopes.seconds(c, lambda _scope, phase: phase == "replay")
        / (s.t1 - s.t0) for c, s in zip(chips, record.steady, strict=True))
