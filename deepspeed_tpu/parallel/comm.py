"""Collective wrappers with the reference's communication-tuning knobs.

The reference's engine-level bucketed allreduce
(/root/reference/deepspeed/pt/deepspeed_light.py:819-882) packs grads into
≤500 MB flat buckets, optionally upcasts to fp32 (``fp32_allreduce``), and
either pre-scales grads by 1/world before the reduce (``prescale_gradients``,
with ``gradient_predivide_factor``) or post-scales after.  On TPU the bucketing
is unnecessary — XLA fuses and schedules collectives — but the *semantics*
(reduce dtype, pre/post scaling order) are preserved here as explicit
wrappers used inside the shard_mapped train step, so results are
bitwise-controlled the same way the reference controls NCCL: ``lax.psum``
per leaf at stage 0, and at ZeRO 1/2 an exchange of the UNREDUCED pieces
(``lax.ppermute``) summed in fp32, in a written-down order, on the rank that
owns the partition (``reduce_scatter_grads``).

All functions take pytrees and an axis name; they must be called inside
``jax.shard_map`` (or ``pjit`` with manual axes) over the engine mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _tree_map(f, tree):
    return jax.tree_util.tree_map(f, tree, is_leaf=lambda x: x is None)


def _prescale(g, prescale_gradients: bool, gradient_predivide_factor: float):
    """What the envelope does to a gradient BEFORE the sum."""
    if prescale_gradients and gradient_predivide_factor != 1.0:
        return g / gradient_predivide_factor
    return g


def _postscale(g, world_size: int, prescale_gradients: bool,
               gradient_predivide_factor: float):
    """What the envelope does to the sum: together with ``_prescale``, a
    division by the world size."""
    if not prescale_gradients:
        return g / world_size
    if gradient_predivide_factor != world_size:
        return g / (world_size / gradient_predivide_factor)
    return g


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

    Single source of truth for the knob semantics (``_prescale`` /
    ``_postscale``) — the dense allreduce and the sparse embedding reduction
    wrap their collective with this; the ZeRO exchange
    (``reduce_scatter_grads``) applies the same two halves around its own
    fp32 sum."""
    orig_dtype = g.dtype
    if fp32_allreduce:
        g = g.astype(jnp.float32)
    g = reduce_fn(_prescale(g, prescale_gradients, gradient_predivide_factor))
    g = _postscale(g, world_size, prescale_gradients,
                   gradient_predivide_factor)
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
    """Mean of a flat gradient over the DP axis, scattered: returns this
    rank's fp32 partition (``flat_grad``'s length must be divisible by the
    partition group).  The ONE reduction of the ZeRO-1/2 boundary, on the
    contiguous 1-D buffer, and no reducing collective at all: every piece
    crosses the wire once, UNREDUCED and in ``flat_grad``'s own dtype, and is
    summed in fp32 where it lands.

    The buffer is ``group`` partitions, partition *j* owned by the *j*-th
    rank of the group.  Step *r* = 1 … group−1 is one ``lax.ppermute`` in
    which every rank sends its piece of its partner's partition and
    receives the partner's piece of its own.  The partner of rank ``me`` in
    step *r* is ``me XOR r`` where the group is a power of two (each step
    then pairs the ranks along one dimension of the chips' torus: on a v5e
    2×2 two of the three steps run at once, 17.6 ms for the cell's three
    0.41 GB pieces, where three rotations run one after the other, 26.4 ms —
    PERF.md, PR 32) and otherwise the rank it sends to is ``(me + r) % group``
    and the rank it hears from ``(me − r) % group``.  **The order of the
    sum** (fp32 addition does not associate, so it is part of the result):
    each piece is widened to fp32, divided by ``gradient_predivide_factor``
    under ``prescale_gradients``, and added left to right as

        own piece + piece heard in step 1 + in step 2 + … + in step group−1

    that is own, ``me^1``, ``me^2``, ``me^3``, … for a power of two, and own,
    ``me−1``, ``me−2``, … (mod group) otherwise; then the sum is post-scaled
    (÷ world, or ÷ world/predivide): the envelope of ``scaled_reduce``,
    applied to fp32 values only.  The up-cast of a bf16/fp16 value is exact,
    so this is the fp32 sum of the same numbers an fp32 all-reduce would add,
    over a wire as narrow as the gradients; ``fp32_allreduce`` has nothing
    left to widen here and is accepted for the callers' one set of knobs.

    Why not ``lax.psum_scatter``: libtpu 0.0.34 lowers it to an all-reduce
    of the whole buffer and a slice (2·(g−1)/g of the buffer through every
    chip, in the sum's dtype); the reference's ZeRO-1 likewise reduces the
    *full* grad then frees non-owned slices (zero_optimizer.py:370-384) and
    names the reduce-scatter as its own roadmap item
    (docs/_posts/2020-03-17-reduce-scatter.md).  The exchange moves
    (g−1)/g of the buffer out of every chip (PERF.md, PR 32).

    With ``partition_group_size`` g < world (ZeRO parameter_parallel_size,
    reference deepspeed_light.py:63-77) the exchange runs within each
    consecutive g-rank sub-group and the fp32 partial sums then psum across
    sub-groups, so every rank ends with the FULL-DP-reduced gradient of its
    sub-partition (replicated across the world/g sub-groups).
    ``across_subgroups=False`` skips that cross-group psum — callers that
    accumulate several scatters (ZeRO-2's per-micro path) defer the single
    linear psum to the boundary via ``finish_subgroup_reduce``.
    """
    del fp32_allreduce          # the sum below is fp32 whatever the wire
    group = partition_group_size or world_size
    within, across = subgroup_index_groups(world_size, group)
    part = flat_grad.shape[0] // group
    me = lax.axis_index(axis_name) % group

    def piece(owner):
        return lax.dynamic_slice_in_dim(flat_grad, owner * part, part)

    if group & (group - 1) == 0:
        def sends_to(i, r):
            return i ^ r
    else:
        def sends_to(i, r):
            return (i + r) % group

    # every step is issued before any received piece is used
    pieces = [piece(me)]
    for r in range(1, group):
        perm = [(ranks[i], ranks[sends_to(i, r)])
                for ranks in within for i in range(group)]
        pieces.append(lax.ppermute(piece(sends_to(me, r)), axis_name, perm))
    # pieces[r] is what step r delivered; a left fold is the stated order
    total = functools.reduce(jnp.add, (
        _prescale(p.astype(jnp.float32), prescale_gradients,
                  gradient_predivide_factor) for p in pieces))
    if across_subgroups and group != world_size:
        total = lax.psum(total, axis_name, axis_index_groups=across)
    return _postscale(total, world_size, prescale_gradients,
                      gradient_predivide_factor)


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
