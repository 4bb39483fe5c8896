"""Host-fence accounting — the choke point every deliberate device sync
goes through.

A "fence" is any host-side wait on device data: ``block_until_ready``,
``device_get``/``np.asarray`` of a device array, or a ``bool()``/``float()``
read of a device scalar.  Each one serializes host dispatch with device
execution — a fixed per-step cost gradient accumulation cannot amortize
(WALLCLOCK §7) — so the telemetry layer's whole design goal is to keep them
off the per-step path: metrics spool through a device ring buffer and drain
once per report window (observability/spool.py).

Every fence the engine takes ON PURPOSE routes through this module
(``utils.timer._fence``, the boundary overflow read, the spool flush), so
the regression contract "zero fences off report steps" is a COUNTER the
tests pin (tests/test_observability.py), not a code-review convention.
"""

from __future__ import annotations

#: process-wide count of deliberate host fences (monotonic; tests snapshot
#: around a region and assert the delta)
FENCE_COUNT = 0


def count_fence(n: int = 1) -> None:
    """Record ``n`` deliberate host fences (called by the sites that wait)."""
    global FENCE_COUNT
    FENCE_COUNT += n


def fence_on(sync_on) -> None:
    """``block_until_ready`` every array leaf of ``sync_on`` (None = no-op),
    counting ONE fence for the whole pytree — it is one host wait, however
    many leaves drain behind it."""
    if sync_on is None:
        return
    import jax
    leaves = [l for l in jax.tree_util.tree_leaves(sync_on)
              if hasattr(l, "block_until_ready")]
    if not leaves:
        return
    count_fence()
    for leaf in leaves:
        leaf.block_until_ready()


def read_scalar(x):
    """Fetch one device scalar to host (a fence) and return the Python
    value.  The engine's boundary overflow read routes through here."""
    import numpy as np
    if hasattr(x, "block_until_ready") or hasattr(x, "addressable_shards"):
        count_fence()
    return np.asarray(x).item()


def read_arrays(*xs):
    """Fetch device arrays to host numpy (one counted fence for the batch).
    The spool's synchronous flush routes through here."""
    import numpy as np
    if any(hasattr(x, "block_until_ready") for x in xs):
        count_fence()
    return tuple(np.asarray(x) for x in xs)


def host_local_view(x):
    """This process's single-device view of a (replicated) global array —
    no transfer, the local shard already lives on an addressable device.
    Identity for host-local arrays (single-process runs, the split-API
    spool state)."""
    if hasattr(x, "is_fully_addressable") and not x.is_fully_addressable:
        return x.addressable_shards[0].data
    return x
