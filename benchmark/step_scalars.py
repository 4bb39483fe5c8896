"""What the model counted on the device: the program's step scalars, for the
readers under ``benchmark/metrics``.

A model may take counts from its data inside the compiled step — an expert
layer: how many (token, choice) pairs landed on the experts held, whether
they fit the held-row prefix — and hand them out beside its loss
(``deepspeed_tpu/observability/scalars.py``).  The engine keeps their totals
on the device and folds them into host-side numbers at a read;
``scalars.snapshot()`` is that read for a caller with no engine in hand: the
totals since ``initialize`` (warm-up and measured steps alike) with the
optimizer steps and micro-steps they cover and the ``model`` gauges of the
step program.  One counted fence, after the run.

A program without that module (any commit before it), or a model that
declares nothing, has no snapshot: every reader built on this says nothing.
"""

#: the program's snapshot, asked for once per process (False: not asked yet)
_snapshot = False


def snapshot(record):
    """``record.step_scalars`` where a test set one, else the program's own;
    None where the program has none to give."""
    given = getattr(record, "step_scalars", None)
    if given is not None:
        return given
    global _snapshot
    if _snapshot is False:
        try:
            from deepspeed_tpu.observability import scalars
        except ImportError:
            _snapshot = None
        else:
            _snapshot = scalars.snapshot()
    return _snapshot


def expert_layers(record):
    """``(values, gauges, snapshot)`` where the snapshot holds an expert
    stack's counts (``moe/*``) over at least one step; None otherwise."""
    snap = snapshot(record)
    if not snap or not snap.get("steps"):
        return None
    values = snap.get("values", {})
    if not any(name.startswith("moe/") for name in values):
        return None
    return values, snap.get("gauges", {}), snap


def expert_layer_passes(gauges, snap):
    """Expert-layer forward passes the totals cover: layers x micro-steps x
    the shards that each ran their own (of the batch and of ``model``)."""
    return (gauges["layers_moe"] * snap["micro_steps"]
            * snap.get("batch_shards", 1) * snap.get("model_shards", 1))
