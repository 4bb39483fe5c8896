"""Device mesh construction and multi-host bootstrap.

TPU-native replacement for the reference's distributed-backend plumbing:

* ``torch.distributed.init_process_group('nccl')`` + env:// rendezvous
  (/root/reference/deepspeed/pt/deepspeed_light.py:125-130) becomes
  ``jax.distributed.initialize(coordinator, num_processes, process_id)``.
* The ``mpu`` protocol (get_model/data_parallel_rank/group/world_size, see
  docs/_pages/features.md §"Support for Custom Model Parallelism") becomes a
  2-D ``jax.sharding.Mesh`` with named axes ``('data', 'model')``: the mesh
  *is* the mpu.  Tensor-parallel degree = size of the ``model`` axis; data
  parallelism (and ZeRO-1 partitioning) ride the ``data`` axis.
* ``_mpi_check`` rank discovery (/root/reference/deepspeed/pt/
  deepspeed_light.py:187-223) becomes env-var discovery of OMPI/PMI vars —
  no mpi4py needed for rendezvous, matching the reference's "MPI for
  discovery, not data" stance.

Mesh axis order is (data, model): with the model axis innermost/minor,
tensor-parallel collectives map onto the fastest ICI links while DP gradient
reductions ride the remaining dimensions — same reasoning as the reference
putting NCCL rings within a node for MP.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

logger = logging.getLogger(__name__)

DATA_AXIS = "data"
PIPE_AXIS = "pipe"    # pipeline parallelism (layer stages, GPipe schedule)
SEQ_AXIS = "seq"      # context/sequence parallelism (ring attention)
MODEL_AXIS = "model"

_ACTIVE_MESH: Optional[Mesh] = None


@dataclasses.dataclass
class MeshConfig:
    """Declarative mesh request: model_parallel_size chips per model replica,
    context_parallel_size chips per sequence ring,
    pipeline_parallel_size chips per layer pipeline, the rest of the slice
    becomes the data axis."""
    model_parallel_size: int = 1
    context_parallel_size: int = 1
    pipeline_parallel_size: int = 1
    devices: Optional[Sequence] = None  # default: all visible devices


def make_mesh(model_parallel_size: int = 1,
              context_parallel_size: int = 1,
              devices: Optional[Sequence] = None,
              pipeline_parallel_size: int = 1) -> Mesh:
    """Build the global ('data', 'pipe', 'seq', 'model') mesh.

    The equivalent of constructing DP/MP process groups (reference
    deepspeed_light.py:63-77 and the Megatron mpu) plus context- and
    pipeline-parallel axes the reference lacks (SURVEY.md §2.3 row 22):
    devices are laid out [data, pipe, seq, model] with model innermost so
    tensor-parallel collectives ride the fastest ICI links, the sequence
    ring next (ppermute neighbours adjacent), the pipeline ring outside
    that (stage handoffs are one activation per tick — latency-tolerant),
    and DP gradient reductions across the remaining dimension.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    mp = int(model_parallel_size)
    sp = int(context_parallel_size)
    pp = int(pipeline_parallel_size)
    if mp < 1 or sp < 1 or pp < 1 or n % (mp * sp * pp) != 0:
        raise ValueError(
            f"model_parallel_size {mp} x context_parallel_size {sp} x "
            f"pipeline_parallel_size {pp} must divide device count {n}")
    dp = n // (mp * sp * pp)
    arr = np.asarray(devices).reshape(dp, pp, sp, mp)
    return Mesh(arr, (DATA_AXIS, PIPE_AXIS, SEQ_AXIS, MODEL_AXIS))


def set_mesh(mesh: Mesh) -> None:
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def get_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH


def data_parallel_size(mesh: Mesh) -> int:
    return mesh.shape[DATA_AXIS]


def model_parallel_size(mesh: Mesh) -> int:
    return mesh.shape[MODEL_AXIS]


def context_parallel_size(mesh: Mesh) -> int:
    return mesh.shape.get(SEQ_AXIS, 1)


def pipeline_parallel_size(mesh: Mesh) -> int:
    return mesh.shape.get(PIPE_AXIS, 1)


# ------------------------------------------------------------------ bootstrap

def mpi_discovery() -> dict:
    """Discover rank/world/coordinator from an MPI/PMI launch environment.

    Parity with ``_mpi_check`` (reference deepspeed_light.py:187-223), which
    uses mpi4py to find rank/size/master then exports RANK/WORLD_SIZE/
    MASTER_ADDR/MASTER_PORT.  Process-per-host on TPU, so local_rank is 0.
    """
    def _first_env(*names, default=None):
        for nm in names:
            if nm in os.environ:
                return os.environ[nm]
        return default

    rank = _first_env("OMPI_COMM_WORLD_RANK", "PMI_RANK", "SLURM_PROCID")
    size = _first_env("OMPI_COMM_WORLD_SIZE", "PMI_SIZE", "SLURM_NTASKS")
    if rank is None or size is None:
        raise RuntimeError(
            "MPI discovery requested but no OMPI/PMI/SLURM rank variables found")
    master_addr = _first_env("MASTER_ADDR", default="127.0.0.1")
    master_port = _first_env("MASTER_PORT", default="29500")
    return {
        "rank": int(rank),
        "world_size": int(size),
        "coordinator_address": f"{master_addr}:{master_port}",
    }


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     use_mpi: bool = False) -> None:
    """Multi-host rendezvous.

    Replaces ``dist.init_process_group`` (reference deepspeed_light.py:125-130).
    Env-var contract mirrors the launcher's: the launcher exports
    ``DSTPU_COORDINATOR``, ``DSTPU_NUM_PROCESSES``, ``DSTPU_PROCESS_ID``
    (analogous to MASTER_ADDR/WORLD_SIZE/RANK, reference
    deepspeed_launch.py:92-106).  Single-process runs skip initialization.
    """
    explicit_coordinator = coordinator_address is not None
    if use_mpi:
        info = mpi_discovery()
        coordinator_address = coordinator_address or info["coordinator_address"]
        num_processes = num_processes if num_processes is not None else info["world_size"]
        process_id = process_id if process_id is not None else info["rank"]

    coordinator_address = coordinator_address or os.environ.get("DSTPU_COORDINATOR")
    if num_processes is None:
        num_processes = int(os.environ.get("DSTPU_NUM_PROCESSES", "1"))
    if process_id is None:
        process_id = int(os.environ.get("DSTPU_PROCESS_ID", "0"))

    if jax.distributed.is_initialized():
        # already rendezvoused (e.g. the user called init_distributed before
        # constructing the engine, whose ctor re-runs it off the env
        # contract) — a second jax.distributed.initialize would raise
        logger.info("init_distributed: already initialized, skipping")
        return

    if num_processes <= 1 and not explicit_coordinator:
        # nothing to rendezvous — covers launcher-spawned 1-process runs that
        # export DSTPU_COORDINATOR (jax.distributed.initialize would fail if
        # the XLA backend is already up).  An EXPLICITLY passed coordinator
        # still rendezvouses: the caller asked for it, and skipping would
        # silently split a multi-host job into isolated worlds.
        logger.info("init_distributed: single-process run, skipping rendezvous")
        return

    platforms = [p.strip() for p in
                 os.environ.get("JAX_PLATFORMS", "").split(",") if p.strip()]
    if not platforms or "cpu" in platforms:
        # multi-process runs on the CPU backend need a real collectives
        # implementation; without it every cross-process psum fails with
        # "Multiprocess computations aren't implemented on the CPU
        # backend".  Covers the explicit JAX_PLATFORMS=cpu case (the
        # distributed test tier) AND the unset case, where jax may
        # auto-select CPU on accelerator-less hosts — the flag only
        # configures the CPU client, so it is inert when an accelerator
        # wins the auto-selection.  (Backend auto-detection cannot be
        # queried here: touching it would initialize XLA before the
        # rendezvous below.)
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        # gloo multiplexes every collective of a pair over one TCP
        # connection; concurrent in-flight collectives from the CPU
        # backend's async dispatch interleave frames on it and die
        # with "op.preamble.length <= op.nbytes".  Serialize dispatch
        # on multi-process CPU — a correctness switch for CI rigs,
        # where CPU throughput is irrelevant.
        jax.config.update("jax_cpu_enable_async_dispatch", False)
        logger.info("init_distributed: gloo CPU collectives enabled "
                    "(async dispatch off)")

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    logger.info("init_distributed: process %d/%d via %s",
                process_id, num_processes, coordinator_address)
