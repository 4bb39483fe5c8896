"""Collective wrappers with the reference's communication-tuning knobs.

The reference's engine-level bucketed allreduce
(/root/reference/deepspeed/pt/deepspeed_light.py:819-882) packs grads into
≤500 MB flat buckets, optionally upcasts to fp32 (``fp32_allreduce``), and
either pre-scales grads by 1/world before the reduce (``prescale_gradients``,
with ``gradient_predivide_factor``) or post-scales after.  On TPU the bucketing
is unnecessary — XLA fuses and schedules collectives — but the *semantics*
(reduce dtype, pre/post scaling order) are preserved here as explicit
``lax.psum`` wrappers used inside the shard_mapped train step, so results are
bitwise-controlled the same way the reference controls NCCL.

All functions take pytrees and an axis name; they must be called inside
``jax.shard_map`` (or ``pjit`` with manual axes) over the engine mesh.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _tree_map(f, tree):
    return jax.tree_util.tree_map(f, tree, is_leaf=lambda x: x is None)


def scaled_reduce(g: jnp.ndarray,
                  reduce_fn,
                  world_size: int,
                  fp32_allreduce: bool = False,
                  prescale_gradients: bool = False,
                  gradient_predivide_factor: float = 1.0) -> jnp.ndarray:
    """The reference's allreduce_bucket scaling envelope
    (deepspeed_light.py:819-849) around ANY sum-reduction ``reduce_fn``:

      * ``fp32_allreduce``: upcast before the reduce (reference :822-825).
      * prescale: divide by ``gradient_predivide_factor`` before the reduce,
        then by ``world/predivide`` after (reference :827-838).
      * postscale (default): reduce, then divide by world size.

    Single source of truth for the knob semantics — the dense allreduce, the
    ZeRO reduce-scatter, and the sparse embedding reduction all wrap their
    collective with this."""
    orig_dtype = g.dtype
    if fp32_allreduce:
        g = g.astype(jnp.float32)
    if prescale_gradients:
        if gradient_predivide_factor != 1.0:
            g = g / gradient_predivide_factor
        g = reduce_fn(g)
        if gradient_predivide_factor != world_size:
            g = g / (world_size / gradient_predivide_factor)
    else:
        g = reduce_fn(g)
        g = g / world_size
    if fp32_allreduce and g.dtype != orig_dtype:
        g = g.astype(orig_dtype)
    return g


def allreduce_grads(grads,
                    axis_name: str,
                    world_size: int,
                    fp32_allreduce: bool = False,
                    prescale_gradients: bool = False,
                    gradient_predivide_factor: float = 1.0):
    """Sum-reduce grads over the DP axis and average, one reduction per leaf
    (reference ``allreduce_bucket``, deepspeed_light.py:819-849; knob
    semantics in ``scaled_reduce``).  The reduction lowers to an ICI
    all-reduce."""
    def reduce_one(g):
        if g is None:
            return None
        return scaled_reduce(
            g, lambda x: lax.psum(x, axis_name), world_size,
            fp32_allreduce=fp32_allreduce,
            prescale_gradients=prescale_gradients,
            gradient_predivide_factor=gradient_predivide_factor)

    return _tree_map(reduce_one, grads)


def subgroup_index_groups(world_size: int, group_size: int):
    """Axis-index groups for ZeRO parameter-parallel sub-groups (reference
    deepspeed_light.py:63-77 builds the analogous torch process groups):

      * ``within``: consecutive blocks of ``group_size`` ranks — the
        partition owners (``[[0..g-1], [g..2g-1], ...]``).
      * ``across``: ranks holding the SAME sub-partition in different
        blocks (``[[p, p+g, p+2g, ...] for p in range(g)]``).
    """
    repl = world_size // group_size
    within = [list(range(b * group_size, (b + 1) * group_size))
              for b in range(repl)]
    across = [[p + b * group_size for b in range(repl)]
              for p in range(group_size)]
    return within, across


def reduce_scatter_grads(flat_grad: jnp.ndarray,
                         axis_name: str,
                         world_size: int,
                         fp32_allreduce: bool = False,
                         prescale_gradients: bool = False,
                         gradient_predivide_factor: float = 1.0,
                         partition_group_size: Optional[int] = None,
                         across_subgroups: bool = True) -> jnp.ndarray:
    """Reduce-scatter a flat gradient over the DP axis, returning this rank's
    partition (flat_grad length must be divisible by the partition group).

    The reference's ZeRO-1 reduces the *full* grad then frees non-owned slices
    (zero_optimizer.py:370-384); the reduce-scatter formulation asks for half
    the bytes and was the reference's own roadmap item
    (docs/_posts/2020-03-17-reduce-scatter.md).  On the v5e, libtpu 0.0.34
    lowers it to an all-reduce of the flat buffer and a slice all the same
    (PERF.md, PR 25: the largest row left in the boundary).  Same scaling
    knobs as ``allreduce_grads``.  This is the ONE reduction of the ZeRO-1/2
    boundary, on the contiguous 1-D buffer.

    With ``partition_group_size`` g < world (ZeRO parameter_parallel_size,
    reference deepspeed_light.py:63-77) the scatter runs within each
    consecutive g-rank sub-group and the partial sums then psum across
    sub-groups, so every rank ends with the FULL-DP-reduced gradient of its
    sub-partition (replicated across the world/g sub-groups).
    ``across_subgroups=False`` skips that cross-group psum — callers that
    accumulate several scatters (ZeRO-2's per-micro path) defer the single
    linear psum to the boundary via ``finish_subgroup_reduce``.
    """
    if partition_group_size is None or partition_group_size == world_size:
        reduce_fn = lambda x: lax.psum_scatter(
            x, axis_name, scatter_dimension=0, tiled=True)
    else:
        within, across = subgroup_index_groups(world_size,
                                               partition_group_size)

        def reduce_fn(x):
            part = lax.psum_scatter(x, axis_name, scatter_dimension=0,
                                    tiled=True, axis_index_groups=within)
            if not across_subgroups:
                return part
            return lax.psum(part, axis_name, axis_index_groups=across)

    return scaled_reduce(
        flat_grad,
        reduce_fn,
        world_size,
        fp32_allreduce=fp32_allreduce,
        prescale_gradients=prescale_gradients,
        gradient_predivide_factor=gradient_predivide_factor)


def finish_subgroup_reduce(partition: jnp.ndarray, axis_name: str,
                           world_size: int,
                           partition_group_size: int) -> jnp.ndarray:
    """The deferred cross-sub-group psum of ``reduce_scatter_grads(...,
    across_subgroups=False)`` — run ONCE on the accumulated partition."""
    if partition_group_size == world_size:
        return partition
    _, across = subgroup_index_groups(world_size, partition_group_size)
    return lax.psum(partition, axis_name, axis_index_groups=across)


def allgather_params(partition: jnp.ndarray, axis_name: str,
                     world_size: Optional[int] = None,
                     partition_group_size: Optional[int] = None
                     ) -> jnp.ndarray:
    """Gather updated weight partitions from all DP ranks (flat, tiled) —
    the ZeRO-1 weight allgather (reference zero_optimizer.py:397-432), in
    whatever dtype the caller hands over (the engine: the compute dtype).
    With ``partition_group_size`` the gather stays within each sub-group
    (each block of g ranks already holds all g sub-partitions)."""
    if (partition_group_size is None or world_size is None
            or partition_group_size == world_size):
        return lax.all_gather(partition, axis_name, axis=0, tiled=True)
    within, _ = subgroup_index_groups(world_size, partition_group_size)
    return lax.all_gather(partition, axis_name, axis=0, tiled=True,
                          axis_index_groups=within)


def overflow_any(local_overflow, axis_name: Optional[str]):
    """MAX-allreduce of the overflow flag so all ranks agree
    (reference deepspeed_utils.py:62-75 does this over the MP group; under
    SPMD every axis sees the same global grads after reduction, but the local
    pre-reduction check still needs agreement over DP)."""
    f = jnp.asarray(local_overflow, jnp.float32)
    if axis_name is not None:
        f = lax.pmax(f, axis_name)
    return f > 0
