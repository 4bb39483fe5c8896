"""What the program says its step is made of: the ``model`` gauges of the
fused step program the engine traced last (``layers_mamba2``,
``ssd_chunks_per_row`` ...), for the readers under ``benchmark/metrics``.

``deepspeed_tpu.observability.scalars.step_gauges()`` hands them out after
the run, also where the model counts no step scalar (``step_scalars``'
snapshot carries them only beside such counts).  A program without that
function (any commit before it) has none: every reader built on this says
nothing.
"""


def gauges(record):
    """``record.gauges`` where a test set them, else the program's own;
    None where the program has none to give."""
    given = getattr(record, "gauges", None)
    if given is not None:
        return given
    try:
        from deepspeed_tpu.observability.scalars import step_gauges
    except ImportError:
        return None
    return step_gauges()
