"""ZeRO stage 1: optimizer-state partitioning over the data-parallel axis.

TPU-native analog of /root/reference/deepspeed/pt/deepspeed_zero_optimizer.py
(class FP16_DeepSpeedZeroOptimizer).  The reference manually flattens each
param group aligned to the DP world size (:20-41), splits the flat buffer into
per-rank partitions (:196-212), keeps an fp32 master clone of only this rank's
partition (:158-165), and after the local update all-gathers the fp16
partitions (:397-432).

Here the same layout is expressed through GSPMD sharding instead of offset
bookkeeping: the fp32 master (and Adam moments) live in ONE flat padded global
array with ``NamedSharding(mesh, P('data'))`` — XLA materialises exactly the
reference's "each DP rank owns 1/N of the flat buffer".  Gradients are
flattened in the dtype the backward wrote them and reduce-scattered onto the
owned partition — the upgrade the reference itself teased
(docs/_posts/2020-03-17-reduce-scatter.md) — as an exchange of the unreduced
pieces summed in fp32 on the owner (``comm.reduce_scatter_grads``); the
update runs shard-locally, and the updated weights return to every rank via a
tiled ``all_gather`` over ICI.

The "empty partition" edge case the reference tests (DP=3 over 2 params,
tests/unit/test_fp16.py:320-347) is handled by the padding: ranks beyond the
real parameter count own pure padding and the gather discards it.

``parameter_parallel_size`` sub-groups (reference deepspeed_light.py:63-77)
partition over a SUBSET of DP: the flat buffer is tiled ``dp/pps`` times into
``[repl * padded]`` P('data') so each consecutive block of pps devices holds
the full partitioned state, with ``axis_index_groups`` collectives
(engine._make_step_local / parallel.comm).  The ``allgather_size`` chunking
knob (:399-425) is accepted in config; under XLA the gather schedule is the
compiler's, so chunking is a no-op — kept as a documented escape hatch.
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class FlatMeta(NamedTuple):
    """Static metadata to flatten/unflatten a pytree through one padded flat
    buffer (the reference's partition bookkeeping, zero_optimizer.py:214-262,
    reduced to shapes)."""
    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    sizes: Tuple[int, ...]
    total: int            # unpadded element count
    padded: int           # total padded to a multiple of (dp * align)
    partition: int        # padded // dp


#: Elements per tile of a 1-D array on the TPU, in every dtype the boundary
#: moves (f32 ``T(1024)``, bf16/fp16 ``T(1024)(128)(2,1)``).  A partition of
#: whole tiles is what lets each rank's piece of a collective land in place
#: and leave in place (the gradient exchange permutes ``[partition]`` slices
#: of the 1-D buffer in its own tiling, PERF.md, PR 32): with 128 (a lane,
#: not a tile) libtpu 0.0.34 compiled the weight all-gather into
#: ``[group, 1, partition]`` and reached the flat buffer from there through
#: re-tiling copies and unaligned ``dynamic-update-slice`` loops (PERF.md,
#: PR 25).
FLAT_ALIGN = 1024


def make_flat_meta(params, dp_size: int, align: int = FLAT_ALIGN) -> FlatMeta:
    """Compute the flatten layout.  Every partition is a whole number of
    the TPU's 1-D tiles (``FLAT_ALIGN``; the reference aligns to the DP
    world size only, zero_optimizer.py:20-41)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    shapes = tuple(tuple(l.shape) for l in leaves)
    return _meta_from_shapes(treedef, shapes, dp_size, align)


def _meta_from_shapes(treedef, shapes, dp_size: int, align: int) -> FlatMeta:
    sizes = tuple(int(np.prod(s)) if len(s) else 1 for s in shapes)
    total = int(sum(sizes))
    chunk = dp_size * align
    padded = ((total + chunk - 1) // chunk) * chunk
    return FlatMeta(treedef=treedef, shapes=shapes, sizes=sizes, total=total,
                    padded=padded, partition=padded // dp_size)


def _spec_axes(entry):
    if entry is None:
        return ()
    return entry if isinstance(entry, tuple) else (entry,)


class LazyParts:
    """Deferred host leaf for the streaming checkpoint restore.

    ``parts`` are the raw array sources (np.memmap chunk views into the
    checkpoint container) and ``assemble(arrays)`` — arrays in ``parts``
    order — builds the materialized leaf.  Threading these through the
    host-side tree reassembly instead of eager ``np.concatenate`` lets the
    restore path hand every chunk read to a reader pool and assemble each
    leaf as its chunks land (checkpoint._stream_leaves); ``materialize()``
    is the inline (serial) equivalent and produces bitwise the same value.
    """

    __slots__ = ("parts", "assemble")

    def __init__(self, parts, assemble):
        self.parts = list(parts)
        self.assemble = assemble

    def materialize(self):
        return self.assemble([np.asarray(p) for p in self.parts])

    @property
    def nbytes(self) -> int:
        return sum(int(getattr(p, "nbytes", 0)) for p in self.parts)

    @classmethod
    def wrap(cls, value) -> "LazyParts":
        """Lift a plain array source into a single-part LazyParts."""
        if isinstance(value, cls):
            return value
        return cls([value], lambda arrs: arrs[0])

    @classmethod
    def concat(cls, values, axis: int) -> "LazyParts":
        """Compose: concatenate ``values`` (LazyParts or raw sources) along
        ``axis``, keeping every underlying chunk an independent part."""
        lazies = [cls.wrap(v) for v in values]
        counts = [len(lz.parts) for lz in lazies]
        subs = [lz.assemble for lz in lazies]

        def assemble(arrs):
            out, i = [], 0
            for n, sub in zip(counts, subs):
                out.append(sub(arrs[i:i + n]))
                i += n
            return np.concatenate(out, axis=axis)

        return cls([p for lz in lazies for p in lz.parts], assemble)


def _local_shape(shape, spec, axis_sizes) -> Tuple[int, ...]:
    """Per-device-group shape of a leaf under a PartitionSpec: each dim is
    divided by the product of the mesh-axis sizes sharding it."""
    out = list(shape)
    for i, entry in enumerate(spec):
        if i >= len(out):
            break
        for ax in _spec_axes(entry):
            size = axis_sizes.get(ax, 1)
            if out[i] % size != 0:
                raise ValueError(
                    f"dim {i} of shape {shape} not divisible by mesh axis "
                    f"{ax!r} (size {size})")
            out[i] //= size
    return tuple(out)


def make_local_flat_meta(params, specs, axis_sizes, dp_size: int,
                         align: int = FLAT_ALIGN) -> FlatMeta:
    """Flatten layout of the LOCAL (per-model-shard) parameter slices.

    Under ZeRO x tensor parallelism the reference partitions optimizer state
    within each MP rank's data-parallel group (deepspeed_light.py:63-77,
    _configure_zero_optimizer :520-531): every model shard keeps a flat fp32
    master of only ITS slice of the parameters, split over DP.  The local
    meta describes exactly those slices — model-sharded leaves shrink by the
    model-axis degree, model-replicated leaves keep their global shape."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    spec_leaves = treedef.flatten_up_to(specs)
    shapes = tuple(_local_shape(tuple(l.shape), s, axis_sizes)
                   for l, s in zip(leaves, spec_leaves))
    return _meta_from_shapes(treedef, shapes, dp_size, align)


def norm_dedup_weights(meta: FlatMeta, specs, state_axes) -> np.ndarray:
    """Per-element weights so a state-axes psum of weighted squared norms
    counts every parameter exactly once (the reference's replicated-parameter
    dedup, deepspeed_utils.py:100-158).  ``state_axes`` is a sequence of
    ``(axis_name, size)`` — the model/pipe axes parameters may shard over:
    leaves sharded over an axis contribute distinct slices on every shard
    (weight factor 1), leaves replicated over it are identical on every
    shard (factor 1/size); factors multiply across axes."""
    spec_leaves = meta.treedef.flatten_up_to(specs)
    pieces = []
    for spec, size in zip(spec_leaves, meta.sizes):
        axes = set()
        for entry in spec:
            axes.update(_spec_axes(entry))
        w = 1.0
        for name, n in state_axes:
            if name not in axes:
                w /= n
        pieces.append(np.full((size,), w, np.float32))
    pad = meta.padded - meta.total
    if pad:
        pieces.append(np.zeros((pad,), np.float32))
    return np.concatenate(pieces)


def combine_composite_trees(local_trees, specs, axes, lazy=False):
    """Reassemble a global pytree from per-composite-rank local trees (host
    side).  ``axes`` is ``[(axis_name, size), ...]`` row-major (first axis
    slowest-varying — pipe before model); the innermost axis combines
    first.  Single owner of the composite-rank ordering invariant shared by
    checkpoint reassembly and engine._params_from_master_flat.

    ``lazy=True`` defers every model-sharded concatenation to
    :class:`LazyParts` (streaming-restore callers only — the leaves reach
    ``checkpoint._place_trees``, which schedules the underlying chunks on
    the reader pool and assembles as they land)."""
    if len(local_trees) == 1:
        return local_trees[0]
    if len(axes) == 1:
        return combine_local_trees(local_trees, specs, axes[0][0],
                                   lazy=lazy)
    inner = 1
    for _, n in axes[1:]:
        inner *= n
    outer = [combine_composite_trees(local_trees[o * inner:(o + 1) * inner],
                                     specs, axes[1:], lazy=lazy)
             for o in range(axes[0][1])]
    return combine_local_trees(outer, specs, axes[0][0], lazy=lazy)


def combine_local_trees(local_trees, specs, model_axis: str, lazy=False):
    """Reassemble a global pytree from per-model-shard local trees (host
    side): model-sharded leaves concatenate along their sharded dim,
    replicated leaves are taken from shard 0.  ``lazy=True`` (and any
    already-deferred input leaf) keeps the concatenation deferred — see
    :func:`combine_composite_trees`."""
    treedef = jax.tree_util.tree_structure(local_trees[0])
    spec_leaves = treedef.flatten_up_to(specs)
    all_leaves = [jax.tree_util.tree_leaves(t) for t in local_trees]
    out = []
    for i, spec in enumerate(spec_leaves):
        dim = None
        for d, entry in enumerate(spec):
            if model_axis in _spec_axes(entry):
                dim = d
                break
        if dim is None:
            out.append(all_leaves[0][i])
        elif lazy or any(isinstance(lv[i], LazyParts) for lv in all_leaves):
            # streaming restore: keep the per-shard chunks independent so
            # the reader pool schedules them (raw memmap sources would
            # otherwise page-fault serially, GIL held, on the consumer);
            # assembly is the SAME np.concatenate, just deferred
            # (bitwise-identical)
            out.append(LazyParts.concat([lv[i] for lv in all_leaves], dim))
        else:
            out.append(np.concatenate(
                [np.asarray(lv[i]) for lv in all_leaves], axis=dim))
    return treedef.unflatten(out)


def flatten_tree(tree, meta: FlatMeta) -> jnp.ndarray:
    """Concat + pad all leaves into one flat [padded] vector (jit-safe), in
    the leaves' common dtype: a bf16 gradient tree gives a bf16 buffer (half
    the bytes to write here and to send), an fp32 tree (masters, an
    accumulator) an fp32 one, and a tree that mixes dtypes promotes as
    ``jnp.result_type`` says.  Equivalent of
    ``flatten_dense_tensors_aligned`` (zero_optimizer.py:20-41)."""
    leaves = meta.treedef.flatten_up_to(tree)
    dtype = jnp.result_type(*leaves)
    flat = jnp.concatenate(
        [jnp.reshape(l, (-1,)).astype(dtype) for l in leaves])
    pad = meta.padded - meta.total
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), dtype)])
    return flat


def unflatten_tree(flat: jnp.ndarray, meta: FlatMeta):
    """Split a flat [padded] vector back into the original pytree (jit-safe).
    Equivalent of re-viewing model params into the flat buffer
    (zero_optimizer.py:146-149).  No cast here: the ZeRO boundary gathers
    in the compute dtype, so each leaf is one slice and one reshape (a
    down-cast fused between the two cost the TPU compiler, libtpu 0.0.34,
    ~0.5 ms PER ROW of compile time at aligned offsets: 25 s for a 2-layer
    BERT-large tree)."""
    out = []
    offset = 0
    for shape, size in zip(meta.shapes, meta.sizes):
        piece = jax.lax.dynamic_slice_in_dim(flat, offset, size)
        out.append(jnp.reshape(piece, shape))
        offset += size
    return meta.treedef.unflatten(out)
