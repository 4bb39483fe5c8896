"""Checkpoint save/load with the reference's layout and role split.

TPU-native analog of /root/reference/deepspeed/pt/deepspeed_light.py:949-1127:

* layout   ``<dir>/<tag>/mp_rank_{MP:02d}_model_states.pt`` — ONE file per
           model shard (reference writes per-MP-rank files, :961-967) +
           ``<dir>/<tag>/zero_pp_rank_{DP}_mp_rank_{MP:02d}optim_states.pt``
           (path builders reference :949-967)
* roles    each model shard's states are written by the process holding its
           replica-0 device shards; every ZeRO partition owner saves its
           optimizer shard (reference _configure_checkpointing :329-343).
           All writes go through ``addressable_shards`` — a model-axis-sharded
           global array is NEVER gathered across hosts.
* content  model (compute-dtype) weights + fp32 masters, optimizer state,
           loss-scale state, lr-scheduler state, engine counters
           (global_steps/skipped_steps/micro_steps) and arbitrary
           ``client_state`` returned to the caller on load (reference
           :1019-1032)
* resume   fp32 master partitions round-trip bit-exactly (the reference saves
           them for the same reason, zero_optimizer.py:510-513); ZeRO
           checkpoints are saved UNPADDED, so a restore onto a different DP
           world size re-pads and re-partitions cleanly; non-ZeRO model
           states reassemble from per-MP-rank files and re-shard, so a
           restore onto a different MP degree also works (both beyond the
           reference, SURVEY.md §7.3)

Serialization is a pickled dict of numpy arrays per file, loaded through a
restricted unpickler that only resolves numpy array/dtype reconstructors and
builtin containers — unlike ``torch.load``, a checkpoint cannot smuggle
arbitrary code.
"""

from __future__ import annotations

import os
import pickle
import re
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu import zero as zero_mod
from deepspeed_tpu.parallel.topology import (DATA_AXIS, MODEL_AXIS,
                                             PIPE_AXIS)
from deepspeed_tpu.resilience import chaos as _chaos

MODEL_FILE = "mp_rank_{mp:02d}_model_states.pt"
# pipeline stages get their own model-state files (generalizing the
# reference's per-MP-rank layout rule, deepspeed_light.py:949-967)
MODEL_FILE_PP = "pp_stage_{pp:02d}_mp_rank_{mp:02d}_model_states.pt"
ZERO_FILE = "zero_pp_rank_{dp}_mp_rank_{mp:02d}optim_states.pt"
LATEST_FILE = "latest"


def _to_np(tree):
    return jax.tree_util.tree_map(lambda x: np.asarray(x), tree)


# ------------------------------------------------- chunked container format
#
# Layout: MAGIC (8 bytes) | header offset (8 bytes LE) | raw array payloads
# | pickled header.  In the header every ndarray above _INLINE_MAX bytes is
# replaced by a plain tuple ("__dstpu_chunk__", offset, dtype_name, shape)
# pointing into the payload region.  Writers stream one leaf at a time
# (peak host RAM = one leaf, not the whole state dict — VERDICT r4 weak #3:
# the old single-pickle format serialized ~14 bytes/param in RAM with
# training stalled); readers hand back np.memmap views, so restores stream
# from disk too.  Legacy files (plain pickle, no magic) still load.

_MAGIC = b"DSTPUCK1"
_CHUNK_TAG = "__dstpu_chunk__"
#: wrapper for USER tuples that collide with the ref namespace (a tuple in
#: ``client_state`` whose first element is the chunk/escape tag string):
#: the writer wraps them ``(_ESCAPE_TAG, t)`` at seal time, the reader
#: unwraps — so a chunk ref is ALWAYS the writer's own, never user data
_ESCAPE_TAG = "__dstpu_escape__"
_INLINE_MAX = 512          # small arrays stay pickled in the header
_HEADER_PREFIX = len(_MAGIC) + 8   # magic + header-offset word
_ML_DTYPES = {"bfloat16", "float8_e3m4", "float8_e4m3",
              "float8_e4m3b11fnuz", "float8_e4m3fn", "float8_e4m3fnuz",
              "float8_e5m2", "float8_e5m2fnuz", "float8_e8m0fnu",
              "float4_e2m1fn", "float6_e2m3fn", "float6_e3m2fn",
              "int2", "int4", "uint2", "uint4"}


def _np_dtype(name: str):
    if name in _ML_DTYPES:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))
    return np.dtype(name)


class _ChunkedWriter:
    """Streams arrays into the payload region; ``finish(header)`` seals the
    file.  ``put(obj)`` walks dict/list/tuple containers, converting each
    ndarray (or jax.Array) leaf to a chunk ref AS IT IS WRITTEN, so only one
    leaf's host copy is live at a time."""

    def __init__(self, path: str):
        self._path = path
        self._tmp = path + ".tmp"
        self._f = open(self._tmp, "wb")
        self._f.write(_MAGIC)
        self._f.write((0).to_bytes(8, "little"))
        self._refs = set()     # id()s of the ref tuples THIS writer issued

    def put_array(self, arr) -> tuple:
        a = np.ascontiguousarray(np.asarray(arr))
        off = self._f.tell()
        a.tofile(self._f)
        ref = (_CHUNK_TAG, off, a.dtype.name, tuple(a.shape))
        self._refs.add(id(ref))
        return ref

    def put(self, obj):
        if isinstance(obj, dict):
            return {k: self.put(v) for k, v in obj.items()}
        if isinstance(obj, tuple) and hasattr(obj, "_fields"):
            # the restricted unpickler cannot reconstruct user namedtuple
            # classes on load, and silently flattening them to plain
            # tuples (what this writer once did) corrupts round trips —
            # refuse loudly (docs/features.md "client_state restrictions")
            raise TypeError(
                f"checkpoint state contains a namedtuple "
                f"({type(obj).__name__}): convert it to a dict or plain "
                f"tuple before save_checkpoint — namedtuple classes "
                f"cannot be reconstructed by the restricted checkpoint "
                f"loader")
        if isinstance(obj, (list, tuple)):
            t = [self.put(v) for v in obj]
            return t if isinstance(obj, list) else tuple(t)
        if isinstance(obj, jax.Array) or (
                isinstance(obj, np.ndarray) and obj.nbytes > _INLINE_MAX):
            return self.put_array(obj)
        return obj

    def _escape(self, obj):
        """Namespace the ref tags: any tuple in the header that LOOKS like
        a chunk ref / escape wrapper but was not issued by this writer is
        user data — wrap it ``(_ESCAPE_TAG, t)`` so the reader never
        misinterprets it (``_resolve_chunks`` unwraps)."""
        if id(obj) in self._refs:
            return obj
        if isinstance(obj, dict):
            return {k: self._escape(v) for k, v in obj.items()}
        if isinstance(obj, list):
            return [self._escape(v) for v in obj]
        if isinstance(obj, tuple):
            t = tuple(self._escape(v) for v in obj)
            if t and t[0] in (_CHUNK_TAG, _ESCAPE_TAG):
                return (_ESCAPE_TAG, t)
            return t
        return obj

    def finish(self, header: Any) -> None:
        _chaos.io_point("ckpt_write")   # chaos tier: Nth-write IO failure
        header = self._escape(header)
        off = self._f.tell()
        pickle.dump(header, self._f, protocol=pickle.HIGHEST_PROTOCOL)
        self._f.seek(len(_MAGIC))
        self._f.write(off.to_bytes(8, "little"))
        self._f.close()
        os.replace(self._tmp, self._path)   # readers never see a torn file

    def abort(self) -> None:
        self._f.close()
        if os.path.exists(self._tmp):
            os.remove(self._tmp)


def _resolve_chunks(obj, path: str, payload_end: Optional[int] = None):
    """Replace chunk refs with read-only np.memmap views into ``path``.

    ``payload_end`` is the header offset — the payload region is
    ``[_HEADER_PREFIX, payload_end)`` and every ref is validated against
    it (offset/dtype/shape) BEFORE the memmap is constructed: a corrupt or
    truncated ref raises a ValueError naming the problem instead of
    handing back a garbage view.  User tuples that collide with the tag
    namespace arrive wrapped ``(_ESCAPE_TAG, t)`` and unwrap here."""
    if isinstance(obj, tuple) and len(obj) == 2 and obj[0] == _ESCAPE_TAG:
        return tuple(_resolve_chunks(v, path, payload_end) for v in obj[1])
    if isinstance(obj, tuple) and len(obj) == 4 and obj[0] == _CHUNK_TAG:
        _, off, dtype_name, shape = obj
        if not (isinstance(off, int) and isinstance(dtype_name, str)
                and isinstance(shape, (tuple, list))
                and all(isinstance(s, int) and s >= 0 for s in shape)):
            raise ValueError(
                f"corrupt checkpoint {path!r}: malformed chunk ref "
                f"{obj!r}")
        try:
            dtype = _np_dtype(dtype_name)
        except Exception:
            raise ValueError(
                f"corrupt checkpoint {path!r}: chunk ref names unknown "
                f"dtype {dtype_name!r}")
        nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
        if off < _HEADER_PREFIX or (
                payload_end is not None and off + nbytes > payload_end):
            raise ValueError(
                f"corrupt checkpoint {path!r}: chunk ref offset={off} "
                f"size={nbytes} falls outside the payload region "
                f"[{_HEADER_PREFIX}, {payload_end})")
        return np.memmap(path, dtype=dtype, mode="r",
                         offset=off, shape=tuple(shape))
    if isinstance(obj, dict):
        return {k: _resolve_chunks(v, path, payload_end)
                for k, v in obj.items()}
    if isinstance(obj, list):
        return [_resolve_chunks(v, path, payload_end) for v in obj]
    if isinstance(obj, tuple):
        return tuple(_resolve_chunks(v, path, payload_end) for v in obj)
    return obj


def _save_obj(path: str, obj: Any) -> None:
    """One-shot save through the chunked container (the streaming writers
    below are preferred for large states; this keeps small single-dict
    call sites simple)."""
    w = _ChunkedWriter(path)
    try:
        w.finish(w.put(obj))
    except BaseException:
        w.abort()
        raise


class _RestrictedUnpickler(pickle.Unpickler):
    """Only numpy array machinery and builtin containers resolve; anything
    else (os.system, subprocess, __reduce__ payloads) raises.  The format
    stays torch.save-like on disk without torch.load's arbitrary-code risk
    (ADVICE.md round 1)."""

    _SAFE = {
        "builtins": {"dict", "list", "tuple", "set", "frozenset", "complex",
                     "slice", "bytearray", "range"},
        "numpy": {"ndarray", "dtype", "bool_", "number", "generic"},
        "numpy.core.multiarray": {"_reconstruct", "scalar"},
        "numpy._core.multiarray": {"_reconstruct", "scalar"},
        "numpy.core.numeric": {"_frombuffer"},
        "numpy._core.numeric": {"_frombuffer"},
        "collections": {"OrderedDict"},
    }

    # the ml_dtypes scalar types a checkpoint can legitimately reference
    # (dtype classes only — finfo/iinfo and any future public callables
    # stay forbidden)
    _SAFE_ML_DTYPES = {
        "bfloat16", "float8_e3m4", "float8_e4m3", "float8_e4m3b11fnuz",
        "float8_e4m3fn", "float8_e4m3fnuz", "float8_e5m2",
        "float8_e5m2fnuz", "float8_e8m0fnu", "float4_e2m1fn",
        "float6_e2m3fn", "float6_e3m2fn", "int2", "int4", "uint2", "uint4",
    }

    def find_class(self, module, name):
        if module == "numpy.dtypes" or module == "numpy.core.numerictypes" \
                or module == "numpy._core.numerictypes":
            return super().find_class(module, name)   # dtype classes only
        if module == "ml_dtypes" and name in self._SAFE_ML_DTYPES:
            # bf16/fp8/intN numpy scalar types: a bf16 params array pickles
            # a reference to ml_dtypes.bfloat16.  Explicit allowlist (like
            # _SAFE) so new ml_dtypes public callables never widen this
            return super().find_class(module, name)
        if name in self._SAFE.get(module, ()):
            return super().find_class(module, name)
        if module == "numpy" and not name.startswith("_"):
            attr = getattr(np, name, None)
            if isinstance(attr, type) and issubclass(attr, np.generic):
                return attr                            # numpy scalar types
        raise pickle.UnpicklingError(
            f"checkpoint contains forbidden global {module}.{name}")


def _load_obj(path: str) -> Any:
    with open(path, "rb") as f:
        head = f.read(len(_MAGIC))
        if head == _MAGIC:
            off = int.from_bytes(f.read(8), "little")
            f.seek(off)
            header = _RestrictedUnpickler(f).load()
            return _resolve_chunks(header, path, payload_end=off)
        f.seek(0)            # legacy single-pickle file (round <= 4)
        return _RestrictedUnpickler(f).load()


def model_file(ckpt_dir: str, tag: str, mp_rank: int = 0,
               pp_stage: int = 0, pp_size: int = 1) -> str:
    if pp_size > 1:
        return os.path.join(ckpt_dir, tag,
                            MODEL_FILE_PP.format(pp=pp_stage, mp=mp_rank))
    return os.path.join(ckpt_dir, tag, MODEL_FILE.format(mp=mp_rank))


def zero_file(ckpt_dir: str, tag: str, dp_rank: int, mp_rank: int = 0) -> str:
    return os.path.join(ckpt_dir, tag,
                        ZERO_FILE.format(dp=dp_rank, mp=mp_rank))


# ------------------------------------------- per-(pp stage, mp rank) split

def _axis_dim(spec, axis: str) -> Optional[int]:
    for d, entry in enumerate(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        if axis in axes:
            return d
    return None


def _rank_owners(mesh, axes):
    """Writer process for each composite rank: the process holding the mesh
    device at (rank's axis coordinates, every other axis 0).  Deterministic
    and communication-free — unlike replica-id probing, it cannot leave a
    rank ownerless when its sharded leaves' replica-0 copies straddle hosts
    (pipe-sharded blocks on one host, pipe-replicated embeddings on
    another)."""
    names = list(mesh.axis_names)
    sizes = [n for _, n in axes]
    S = 1
    for n in sizes:
        S *= n
    owners = []
    for r in range(S):
        rem, comps = r, []
        for n in reversed(sizes):
            rem, c = divmod(rem, n)
            comps.insert(0, c)
        idx = [0] * len(names)
        for (name, _), c in zip(axes, comps):
            if name in names:
                idx[names.index(name)] = c
        owners.append(int(mesh.devices[tuple(idx)].process_index))
    return owners


def _collect_shard_states(tree, specs, axes, mesh=None, replace=None,
                          materialize=True):
    """Split a sharded pytree into per-composite-rank local trees using ONLY
    this process's addressable shards (multi-host safe: nothing is gathered).

    ``axes`` is ``[(axis_name, size), ...]`` (row-major: first axis is the
    slowest-varying component of the composite rank — pipe before model).
    Returns ``(local_trees, owned)``: ``local_trees[r]`` is composite rank
    r's local slice tree (leaves this process cannot see are None) and
    ``owned[r]`` says whether this process is rank r's writer — the
    write-role rule (the reference's "dp rank 0 of each MP group saves",
    deepspeed_light.py:329-343).  With ``mesh`` the role comes from
    ``_rank_owners`` (multi-host safe for composite ranks); without it,
    from holding the replica-0 copy of every sharded leaf.

    ``replace`` (flat list aligned with the tree's leaves) substitutes
    non-None entries verbatim for every rank WITHOUT touching the leaf —
    the stage-3 save uses it to stamp partitioned-leaf markers into model
    files while the actual data goes to per-dp shard files.
    ``materialize=False`` returns the live ``Shard`` objects instead of
    host np copies (callers then stream ``np.asarray(shard.data)`` one
    leaf at a time — the chunked-writer path)."""
    sizes = [n for _, n in axes]
    axis_size = {name: n for name, n in axes}
    if mesh is not None:
        axis_size.update({str(k): int(v) for k, v in mesh.shape.items()})
    S = 1
    for n in sizes:
        S *= n
    strides = []
    acc = 1
    for n in reversed(sizes):
        strides.insert(0, acc)
        acc *= n
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    spec_leaves = treedef.flatten_up_to(specs)
    per_rank = [[None] * len(leaves) for _ in range(S)]
    owned = [True] * S
    any_sharded = False

    def ranks_for(comps):
        """Composite ranks a shard with per-axis components ``comps``
        (None = replicated over that axis → all positions) belongs to."""
        ranks = [0]
        for k, c in enumerate(comps):
            if c is None:
                ranks = [r + j * strides[k] for r in ranks
                         for j in range(sizes[k])]
            else:
                ranks = [r + c * strides[k] for r in ranks]
        return ranks

    def dim_comps(leaf, spec, s):
        """Per-state-axis component of shard ``s``, decoding dims that
        carry SEVERAL mesh axes (e.g. the stage-3 ``('model','data')``
        weight dim) by mixed radix in the spec entry's (major → minor)
        order."""
        comps = [None] * len(axes)
        for d, entry in enumerate(spec):
            if entry is None:
                continue
            names = list(entry) if isinstance(entry, tuple) else [entry]
            if not any(nm == name for nm in names for name, _ in axes):
                continue
            if any(nm not in axis_size for nm in names):
                raise ValueError(
                    f"cannot decode dim {d} sharded over {names}: axis "
                    f"size unknown (pass mesh)")
            total = 1
            for nm in names:
                total *= axis_size[nm]
            block = leaf.shape[d] // total
            linear = (s.index[d].start or 0) // block
            minor = 1
            for nm in reversed(names):
                comp = (linear // minor) % axis_size[nm]
                minor *= axis_size[nm]
                for k, (name, _) in enumerate(axes):
                    if name == nm:
                        comps[k] = comp
        return comps

    for i, (leaf, spec) in enumerate(zip(leaves, spec_leaves)):
        if replace is not None and replace[i] is not None:
            for r in range(S):
                per_rank[r][i] = replace[i]
            continue
        dims = [_axis_dim(spec, name) for name, _ in axes]
        if all(d is None for d in dims) or S == 1:
            # replicated over every state axis: addressable everywhere
            val = (leaf.addressable_shards[0] if not materialize
                   else np.asarray(leaf.addressable_shards[0].data))
            for r in range(S):
                per_rank[r][i] = val
            continue
        any_sharded = True
        seen = {}
        for s in leaf.addressable_shards:
            for r in ranks_for(dim_comps(leaf, spec, s)):
                if r not in seen or s.replica_id == 0:
                    seen[r] = (s, s.replica_id == 0)
        for r in range(S):
            if r in seen:
                per_rank[r][i] = (seen[r][0] if not materialize
                                  else np.asarray(seen[r][0].data))
                owned[r] = owned[r] and seen[r][1]
            else:
                owned[r] = False
    if mesh is not None:
        me = jax.process_index()
        owners = _rank_owners(mesh, axes)
        owned = [owners[r] == me for r in range(S)]
        for r in range(S):
            if owned[r] and any(v is None for v in per_rank[r]):
                raise RuntimeError(
                    f"checkpoint write role for composite rank {r} assigned "
                    f"to process {me} but some leaves are not addressable "
                    f"here — mesh/process layout mismatch")
    elif not any_sharded:
        owned = [jax.process_index() == 0] * S
    trees = [treedef.unflatten(per_rank[r]) for r in range(S)]
    return trees, owned


def _combine_shard_states(local_trees, specs, axes, lazy=False):
    """Inverse of ``_collect_shard_states`` on the host: one global np tree
    (``lazy=True``: deferred :class:`LazyParts` leaves for the streaming
    restore — only callers that feed ``_place_trees`` may ask for it).
    Combines the innermost axis first (rank = outer * inner_size + inner)."""
    return zero_mod.combine_composite_trees(local_trees, specs, axes,
                                            lazy=lazy)


def _state_axes(pp_size: int, mp_size: int):
    """The composite split used for model-state files: pipe major, model
    minor; at least one axis so the rank-0 path is uniform."""
    axes = []
    if pp_size > 1:
        axes.append((PIPE_AXIS, pp_size))
    axes.append((MODEL_AXIS, mp_size))
    return axes


def _collect_mp_states(tree, specs, mp_size: int):
    """Model-axis-only split (multi-process write-role tests exercise this
    directly; the engine paths use the composite _collect_shard_states)."""
    return _collect_shard_states(tree, specs, [(MODEL_AXIS, mp_size)])


# ------------------------------------------------- stage-3 native sharding
#
# ADVICE r4 (medium): the old stage-3 save materialised EVERY leaf's full
# global value on EVERY host (~14 bytes/param held simultaneously) — the
# exact anti-pattern ZeRO-3 exists to avoid.  The native format instead has
# each process write only its addressable data-axis shards: partitioned
# leaves live in per-(row, dp-rank) shard files, the per-row model-state
# files carry replicated leaves plus ("__dstpu_zero3_part__", dim, dp)
# markers, and loads reassemble by concatenating shard chunks along the
# recorded dim — so cross-topology and cross-stage restores still work.

_Z3_TAG = "__dstpu_zero3_part__"
_Z3_SKIP = ("__dstpu_zero3_skip__",)
ZERO3_FILE = "zero3_dp_rank_{dp}_row_{row:02d}_states.pt"


def zero3_file(ckpt_dir: str, tag: str, dp_rank: int, row: int) -> str:
    return os.path.join(ckpt_dir, tag,
                        ZERO3_FILE.format(dp=dp_rank, row=row))


def _z3_marker(obj):
    return (isinstance(obj, tuple) and len(obj) == 3 and obj[0] == _Z3_TAG)


def _flat_with_paths(tree):
    """(keystr, leaf) pairs in tree_flatten order."""
    return [(jax.tree_util.keystr(p), l)
            for p, l in jax.tree_util.tree_leaves_with_path(tree)]


def _shard_np(x):
    """Host value of a collected entry (a live Shard when collection ran
    with materialize=False, else an ndarray/marker already)."""
    return np.asarray(x.data) if hasattr(x, "data") and hasattr(
        x, "replica_id") else x


def _snapshot_put(x):
    """Async-save leaf transform: host np copy now, chunk-write later.

    The copy must be EXPLICIT (``np.array(..., copy=True)``):
    ``np.asarray`` on a jax array may return a zero-copy view of the
    device/host buffer on backends that allow it (CPU, and donated-buffer
    aliasing), and the async writer's "copy before donate" contract says
    the snapshot must survive the next train step overwriting that buffer
    — relying on backend-specific copy behavior is a silent-corruption
    bug waiting for a backend change (ADVICE round 5)."""
    if _z3_marker(x) or x is None:
        return x
    return np.array(_shard_np(x), copy=True)


def _stream_put(writer):
    """Sync-save leaf transform: host copy AND chunk write per leaf, so
    only one leaf's host copy is ever live."""
    def put(x):
        if _z3_marker(x) or x is None:
            return x
        a = np.asarray(_shard_np(x))
        if a.nbytes <= _INLINE_MAX:
            return a
        return writer.put_array(a)
    return put


# ------------------------------------------------------------------- saving

class _AsyncSaver:
    """One background writer thread; saves queue in submission order.  The
    synchronous caller hands over HOST data only (np copies made before the
    next step can donate the device buffers), so the training stall is the
    device→host snapshot, not the disk write (VERDICT r4 weak #3)."""

    def __init__(self):
        self._queue = None
        self._thread = None
        self._errors = []

    def _ensure(self):
        import atexit
        import queue
        import threading
        if self._thread is None or not self._thread.is_alive():
            self._queue = queue.Queue()
            self._thread = threading.Thread(target=self._run, daemon=True,
                                            name="dstpu-ckpt-writer")
            self._thread.start()
            atexit.register(self.wait)

    def _run(self):
        while True:
            fn = self._queue.get()
            try:
                fn()
            except BaseException as e:        # surfaced at wait()
                self._errors.append(e)
            finally:
                self._queue.task_done()

    def submit(self, fn):
        self._ensure()
        self._queue.put(fn)

    def wait(self):
        """Block until every queued save is on disk; re-raise the first
        background failure (a silent half-written checkpoint is worse
        than a late exception)."""
        if self._queue is not None:
            self._queue.join()
        if self._errors:
            e, self._errors = self._errors[0], []
            raise e


ASYNC_SAVER = _AsyncSaver()


def _reject_namedtuples(obj, where: str) -> None:
    """Raise on namedtuples anywhere in a user state tree (see
    _ChunkedWriter.put; checked eagerly so async saves fail at submit
    time on the calling thread, not inside the background writer)."""
    if isinstance(obj, tuple) and hasattr(obj, "_fields"):
        raise TypeError(
            f"save_checkpoint: {where} contains a namedtuple "
            f"({type(obj).__name__}): convert it to a dict or plain tuple "
            f"— namedtuple classes cannot be reconstructed by the "
            f"restricted checkpoint loader (docs/features.md)")
    if isinstance(obj, dict):
        for k, v in obj.items():
            _reject_namedtuples(v, f"{where}[{k!r}]")
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            _reject_namedtuples(v, f"{where}[{i}]")


def save_checkpoint(engine, save_dir: str, tag: Optional[str] = None,
                    client_state: Optional[dict] = None,
                    async_save: Optional[bool] = None) -> str:
    """Engine-level save (reference save_checkpoint :1048-1114).

    ``async_save=True`` snapshots device state to host synchronously (the
    only part that must stall training — after it returns, the next step
    may donate every device buffer) and performs the container writes on a
    background thread; ``engine.checkpoint_wait()`` blocks until durable.
    Defaults to the ``checkpoint.async_save`` config key.  Multi-process
    runs fall back to synchronous saves: the publish barriers are device
    collectives and must run on the main thread."""
    if async_save is None:
        async_save = bool(getattr(engine.config, "checkpoint_async_save",
                                  False))
    if async_save and jax.process_count() > 1:
        import logging
        logging.getLogger("deepspeed_tpu").warning(
            "async_save requested in a multi-process run: falling back to "
            "synchronous saves (the publish barrier is a device collective "
            "and cannot run on the writer thread)")
        async_save = False
    ASYNC_SAVER.wait()     # serialize with any still-pending earlier save
    # client_state restriction (docs/features.md): namedtuples cannot be
    # reconstructed by the restricted loader, and the async writer once
    # silently flattened them to plain tuples — reject at CALL time so the
    # failure is synchronous in both save modes
    _reject_namedtuples(client_state, "client_state")

    tag = tag or f"global_step{engine.global_steps}"
    path = os.path.join(save_dir, tag)
    os.makedirs(path, exist_ok=True)

    mp = engine.mp_world_size
    pp = getattr(engine, "pp_world_size", 1)
    axes = _state_axes(pp, mp)
    zero_flat = getattr(engine, "zero_flat", engine.zero_enabled)
    zero3 = getattr(engine, "zero3", False)
    scalar_state = {
        "loss_scale_state": _to_np(engine.loss_scale_state._asdict()),
        "loss_scale_variant": engine._ls_variant,
        "lr_scheduler": (engine.lr_scheduler.state_dict()
                         if engine.lr_scheduler is not None
                         and hasattr(engine.lr_scheduler, "state_dict")
                         else None),
        # the live hyperparameters the scheduler wrote into the facade
        # (torch persists these inside optimizer.state_dict param_groups)
        "param_groups": [dict(g) for g in engine.optimizer.param_groups],
        "global_steps": engine.global_steps,
        "skipped_steps": engine.skipped_steps,
        "micro_steps": engine.micro_steps,
        "zero_enabled": engine.zero_enabled,
        "zero_stage": getattr(engine, "zero_stage",
                              1 if engine.zero_enabled else 0),
        "mp_world_size": mp,
        "pp_world_size": pp,
        "client_state": dict(client_state or {}),
    }
    # the scheduler's state_dict is user-shaped too: catch namedtuples
    # there at CALL time as well, or an async save would only fail later
    # on the writer thread (surfacing at the NEXT wait/save, far from the
    # offending call)
    _reject_namedtuples(scalar_state["lr_scheduler"],
                        "lr_scheduler.state_dict()")

    S = pp * mp
    specs = engine._param_specs
    markers = None
    if zero3:
        # partitioned leaves go to per-(row, dp) shard files; model files
        # get markers (the stage-3-native format — ADVICE r4 medium)
        leaves, treedef = jax.tree_util.tree_flatten(engine.params)
        dflat = treedef.flatten_up_to(engine._zero3_dims)
        markers = [(_Z3_TAG, int(d), engine.dp_world_size) if d >= 0
                   else None for d in dflat]
        scalar_state["zero3_native"] = True
    collect = lambda t: _collect_shard_states(
        t, specs, axes, mesh=engine.mesh, replace=markers,
        materialize=False)
    params_s, owned = collect(engine.params)
    if zero_flat:
        # three SEPARATE lists: masters live in ZeRO files, and sharing one
        # list object would make any future in-place write corrupt all three
        master_s, m_s, v_s = ([None] * S for _ in range(3))
        step_np = None
    else:
        # replicated masters — or, at stage 3, markers pointing at the
        # per-dp shard files (no zero_pp_rank_* flat partitions)
        master_s, _ = collect(engine.master)
        m_s = ([None] * S if engine.opt_state.m is None else
               collect(engine.opt_state.m)[0])
        v_s = ([None] * S if engine.opt_state.v is None else
               collect(engine.opt_state.v)[0])
        step_np = np.asarray(engine.opt_state.step)

    writes = []      # (path, header_builder(writer)) thunks

    def model_state_write(rank):
        stage, mp_rank = divmod(rank, mp)

        def build(put):
            state = dict(scalar_state)
            state["mp_rank"] = mp_rank
            state["pp_stage"] = stage
            state["module"] = jax.tree_util.tree_map(
                put, params_s[rank], is_leaf=_z3_marker)
            if zero_flat:
                state["optimizer"] = None
            else:
                state["optimizer"] = {
                    "master": jax.tree_util.tree_map(
                        put, master_s[rank], is_leaf=_z3_marker),
                    "opt_state": {
                        "step": step_np,
                        "m": (None if m_s[rank] is None else
                              jax.tree_util.tree_map(
                                  put, m_s[rank], is_leaf=_z3_marker)),
                        "v": (None if v_s[rank] is None else
                              jax.tree_util.tree_map(
                                  put, v_s[rank], is_leaf=_z3_marker))},
                }
            return state
        return model_file(save_dir, tag, mp_rank, stage, pp), build

    for rank in range(S):
        if owned[rank]:
            writes.append(model_state_write(rank))

    if zero3:
        writes.extend(_zero3_shard_writes(engine, save_dir, tag, axes))
    if engine.save_zero_checkpoint:
        writes.extend(_zero_checkpoint_writes(engine, save_dir, tag))

    if async_save:
        # snapshot NOW (device→host copies — the training stall); write in
        # the background thread.
        snapped = [(p, build(_snapshot_put)) for p, build in writes]

        def flush():
            for p, header in snapped:
                w = _ChunkedWriter(p)
                try:
                    w.finish(w.put(header))
                except BaseException:
                    w.abort()
                    raise
            _publish(engine, save_dir, tag, path, S, mp, pp)
        ASYNC_SAVER.submit(flush)
        return path

    for p, build in writes:
        w = _ChunkedWriter(p)
        try:
            # leaves stream through the writer one at a time: ``put``
            # materialises one Shard's host copy and writes it immediately
            w.finish(build(_stream_put(w)))
        except BaseException:
            w.abort()
            raise

    _publish(engine, save_dir, tag, path, S, mp, pp)
    return path


def _publish(engine, save_dir, tag, path, S, mp, pp):
    """Barrier + stale-file cleanup + `latest` pointer.  In async mode this
    runs on the writer thread — safe because async saves are single-process
    (the barriers are device collectives and are skipped at
    process_count == 1)."""
    # all hosts finish their shard writes BEFORE the dp-leader publishes the
    # pointer (reference uses dist.barrier around checkpoint dirs,
    # deepspeed_light.py:1089); otherwise a reader following `latest` could
    # see a tag whose zero_pp_rank_* shards are still being written
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(f"dstpu_ckpt_{tag}_written")
    if jax.process_index() == 0:
        # drop model-state / zero3 shard files left by an earlier save of
        # the SAME tag under a different topology or stage (pp=1's
        # mp_rank_* vs pp>1's pp_stage_* names; stage-3's zero3_dp_rank_*
        # vs none) — a reader following `latest` must never pick up a
        # stale file (the flat zero shards handle the same hazard via
        # partition_count)
        expected = {os.path.basename(model_file(save_dir, tag,
                                                r % mp, r // mp, pp))
                    for r in range(S)}
        if getattr(engine, "zero3", False):
            dp = engine.dp_world_size
            expected |= {ZERO3_FILE.format(dp=d, row=row)
                         for d in range(dp) for row in range(S)}
        for f in os.listdir(path):
            stale = ((f.endswith("_model_states.pt")
                      or f.startswith("zero3_dp_rank_"))
                     and f not in expected)
            if stale:
                os.remove(os.path.join(path, f))
        # atomic pointer publish: a crash mid-write must never leave a
        # truncated/empty `latest` that breaks every future resume (the
        # same temp + os.replace contract as the state files themselves)
        latest = os.path.join(save_dir, LATEST_FILE)
        tmp = latest + ".tmp"
        with open(tmp, "w") as f:
            f.write(tag)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, latest)
    # second barrier: by the time ANY process returns, the pointer is
    # visible — tests/distributed/workers.py pins this contract
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(f"dstpu_ckpt_{tag}_published")


def _zero3_shard_writes(engine, save_dir, tag, axes):
    """Write thunks for the stage-3 per-(row, dp-rank) shard files: each
    process emits ONLY its addressable replica-0 data-axis slices of the
    partitioned leaves (param + fp32 master + moments) — nothing is
    gathered, so per-process host RAM during save is 1/dp of the
    partitioned state (the ADVICE r4 fix)."""
    dp = engine.dp_world_size
    mp = engine.mp_world_size
    pp = getattr(engine, "pp_world_size", 1)
    axes3 = axes + [(DATA_AXIS, dp)]
    specs = engine._param_specs
    leaves, treedef = jax.tree_util.tree_flatten(engine.params)
    dflat = treedef.flatten_up_to(engine._zero3_dims)
    skip = [None if d >= 0 else _Z3_SKIP for d in dflat]
    keys = [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_leaves_with_path(engine.params)]
    collect3 = lambda t: _collect_shard_states(
        t, specs, axes3, mesh=engine.mesh, replace=skip, materialize=False)
    p3, owned3 = collect3(engine.params)
    mast3, _ = collect3(engine.master)
    m3 = (None if engine.opt_state.m is None
          else collect3(engine.opt_state.m)[0])
    v3 = (None if engine.opt_state.v is None
          else collect3(engine.opt_state.v)[0])
    step_np = np.asarray(engine.opt_state.step)

    writes = []
    for r in range(pp * mp * dp):
        if not owned3[r]:
            continue
        row, dpi = divmod(r, dp)

        def build(put, r=r, row=row, dpi=dpi):
            pl = treedef.flatten_up_to(p3[r])
            ml = treedef.flatten_up_to(mast3[r])
            mm = None if m3 is None else treedef.flatten_up_to(m3[r])
            vv = None if v3 is None else treedef.flatten_up_to(v3[r])
            # records key by FLATTEN-ORDER LEAF INDEX: the index is the
            # one identifier save and load share exactly (both walk the
            # same treedef), whereas a formatted keystr depends on the key
            # type's repr — an int-keyed dict in the state tree broke the
            # old string reconstruction.  keystr stays as a debug label.
            recs = {}
            for i, key in enumerate(keys):
                if skip[i] is not None:
                    continue
                recs[i] = {
                    "keystr": key,
                    "dim": int(dflat[i]),
                    "param": put(pl[i]),
                    "master": put(ml[i]),
                    "m": None if mm is None else put(mm[i]),
                    "v": None if vv is None else put(vv[i]),
                }
            return {"row": row, "dp_rank": dpi, "dp_world_size": dp,
                    "mp_world_size": mp, "pp_world_size": pp,
                    "step": step_np, "leaves": recs}
        writes.append((zero3_file(save_dir, tag, dpi, row), build))
    return writes


def _flat_partitions(arr, part: int) -> dict:
    """(mp_rank, dp_rank) → np partition for the flat-buffer shards THIS
    process holds (replica 0 only).  Handles both the 1-D P('data') layout
    and the ZeRO x MP [mp, local_padded] P('model','data') layout.
    Multi-host safe: never materialises the non-addressable global array."""
    out = {}
    for s in arr.addressable_shards:
        if s.replica_id != 0:
            continue
        if arr.ndim == 2:
            m = s.index[0].start or 0
            start = s.index[1].start or 0
            data = np.asarray(s.data)[0]
        else:
            m = 0
            start = (s.index[0].start or 0) if s.index else 0
            data = np.asarray(s.data)
        # a device shard may span several logical partitions (e.g. after a
        # mesh with fewer data shards than dp ranks); split it
        for off in range(0, data.shape[0], part):
            out[(m, (start + off) // part)] = data[off:off + part]
    return out


def _zero_checkpoint_writes(engine, save_dir: str, tag: str):
    """Write thunks for the per-partition flat optimizer shards (reference
    _save_zero_checkpoint :1116-1127).  Each process writes ONLY the
    partitions it owns (the reference's every-partition-owner-saves role,
    :338-343); the trailing padding is dropped so restores re-pad for
    their own topology."""
    meta = engine.flat_meta
    dp = engine.dp_world_size
    # parameter-parallel sub-groups (parameter_parallel_size < dp) tile the
    # flat buffer: only the first sub-group's partitions are distinct
    parts = engine.zero_pps
    part = meta.partition
    masters = _flat_partitions(engine.master_flat, part)
    ms = _flat_partitions(engine.opt_state.m["flat"], part)
    vs = _flat_partitions(engine.opt_state.v["flat"], part)
    step = np.asarray(engine.opt_state.step)
    writes = []
    for (m, r), master in masters.items():
        if r >= parts:
            continue  # replica of partition r % parts
        lo = r * part
        count = int(np.clip(meta.total - lo, 0, part))

        def build(put, m=m, r=r, master=master, count=count):
            return {
                "partition_id": r,
                "mp_rank": m,  # composite row id: pp_stage * mp + mp_rank
                "dp_world_size": dp,
                "partition_count": parts,
                "mp_world_size": engine.mp_world_size,
                "pp_world_size": getattr(engine, "pp_world_size", 1),
                "unpadded_total": meta.total,
                "step": step,
                "master": put(master[:count]),
                "m": put(ms[(m, r)][:count]),
                "v": put(vs[(m, r)][:count]),
            }
        writes.append((zero_file(save_dir, tag, r, m), build))
    return writes


# ------------------------------------------------------- tag discovery

def _model_probe(load_dir: str, tag: str) -> Optional[str]:
    """Path of the tag's canonical model-state file (mp rank 0 / stage 0),
    or None when neither layout's file exists."""
    mfile = model_file(load_dir, tag, 0)
    if os.path.exists(mfile):
        return mfile
    mfile = os.path.join(load_dir, tag, MODEL_FILE_PP.format(pp=0, mp=0))
    return mfile if os.path.exists(mfile) else None


def validate_tag(load_dir: str, tag: str) -> bool:
    """True when ``tag`` looks restorable: its canonical model-state file
    exists and its container header parses.  Cheap (header-only; chunk
    payloads resolve to lazy memmaps) — the auto-resume discovery runs it
    over every candidate, so a half-written or corrupt tag is skipped
    instead of crashing the restart (docs/resilience.md)."""
    probe = _model_probe(load_dir, tag)
    if probe is None:
        return False
    try:
        _load_obj(probe)
    except Exception:
        return False
    return True


def list_tags(load_dir: str) -> list:
    """Candidate tag names under ``load_dir``: every direct tag directory
    plus ``emergency/<tag>`` preemption-drain tags."""
    out = []
    try:
        entries = sorted(os.listdir(load_dir))
    except OSError:
        return out
    for e in entries:
        p = os.path.join(load_dir, e)
        if not os.path.isdir(p):
            continue
        if e == "emergency":
            try:
                subs = sorted(os.listdir(p))
            except OSError:
                continue
            out.extend(f"emergency/{s}" for s in subs
                       if os.path.isdir(os.path.join(p, s)))
        else:
            out.append(e)
    return out


def _tag_step(tag: str) -> int:
    """Trailing step number of a tag (``global_step12`` → 12; -1 when the
    tag carries none) — NUMERIC, so the mtime tie-break cannot misorder
    ``global_step9`` above ``global_step10`` lexicographically."""
    m = re.search(r"(\d+)$", tag)
    return int(m.group(1)) if m else -1


def find_latest_valid_tag(load_dir: str, exclude=()) -> Optional[str]:
    """Newest VALID checkpoint tag under ``load_dir`` — the auto-resume
    discovery (resilience.run_resumable).  "Newest" is by model-state-file
    mtime (trailing step number, then tag name, as deterministic
    tie-breaks for coarse-mtime or copy-flattened filesystems), over
    regular AND ``emergency/`` tags; tags whose model-state header does
    not parse are skipped, as are ``exclude``d tags (the resume driver
    passes tags that already failed a full load — e.g. a mid-save SIGKILL
    left the model header durable but the ZeRO shard files missing, which
    a header-only probe cannot see — so discovery falls back to the
    next-newest candidate instead of bricking every restart on the same
    half-written tag).  The ``latest`` pointer is NOT trusted blindly: a
    stale or corrupt pointer must not hide a newer (or the only) valid
    checkpoint."""
    best = None
    excluded = set(exclude)
    for tag in list_tags(load_dir):
        if tag in excluded:
            continue
        probe = _model_probe(load_dir, tag)
        if probe is None:
            continue
        try:
            _load_obj(probe)        # validate_tag's check, probe reused
        except Exception:
            continue
        key = (os.path.getmtime(probe), _tag_step(tag), tag)
        if best is None or key > best[0]:
            best = (key, tag)
    return None if best is None else best[1]


# ------------------------------------------- parallel streaming restore
#
# PR 4 made auto-resume the normal operating mode, which put RESTORE on the
# critical path of every restart — and the serial read path (leaf-at-a-time
# np.concatenate over memmap views, then per-leaf device placement) was the
# slow side.  The pipeline below mirrors the async writer in the other
# direction: a reader pool streams chunk records from the container (ZeRO-3
# shard records read concurrently per shard file), each leaf is assembled
# as its chunks land, and device placement (`_put_global`) of leaf i
# overlaps the reads of every later leaf.  Readers use positioned file
# reads (`readinto`, which releases the GIL during the syscall) instead of
# memmap page faults (which hold it), each read is composed with
# ``io_retry``, and in-flight read results are bounded by
# ``restore_readahead_mb`` — peak host RAM is one readahead window plus the
# leaf being placed, NOT the whole state tree.  ``restore_threads <= 1``
# executes the same plan inline (the serial fallback); both paths run the
# identical per-leaf assembly, so they are bitwise-interchangeable
# (pinned by tests/test_checkpoint_restore.py).

LazyParts = zero_mod.LazyParts


class CheckpointReadError(RuntimeError):
    """A restore reader failed (corrupt/truncated chunk, or storage errors
    that exhausted the per-reader ``io_retries`` budget).  Named — a dead
    reader must surface as a prompt exception on the restoring thread, not
    as a hang of the consumer."""


class _RestorePlan:
    """Resolved restore-path knobs for one load: reader-pool width,
    readahead window, per-reader retry budget."""

    def __init__(self, threads: int = 1, readahead_mb: float = 256.0,
                 io_retries: int = 3):
        self.threads = int(threads)
        self.readahead_bytes = max(1, int(float(readahead_mb) * 2 ** 20))
        self.io_retries = int(io_retries)

    @classmethod
    def auto_threads(cls) -> int:
        # reads are memcpy-bound once the page cache is warm and IO-bound
        # when cold; a couple of readers per core covers both without
        # oversubscribing small hosts
        return max(2, min(8, 2 * (os.cpu_count() or 1)))

    @classmethod
    def from_engine(cls, engine) -> "_RestorePlan":
        cfg = getattr(engine, "config", None)
        threads = int(getattr(cfg, "checkpoint_restore_threads", 0))
        if threads == 0:
            threads = cls.auto_threads()
        return cls(
            threads=threads,
            readahead_mb=float(getattr(cfg, "checkpoint_restore_readahead_mb",
                                       256.0)),
            io_retries=int(getattr(cfg, "resilience_io_retries", 3)))


def _read_part(part):
    """Materialize one chunk source as a host array.

    np.memmap chunks are fetched with a positioned ``readinto`` — unlike
    ``np.asarray(memmap)``, whose page faults hold the GIL for the whole
    IO wait, ``readinto`` releases it, so pool readers actually overlap.
    A short read names the truncation instead of handing back garbage."""
    _chaos.read_point("ckpt_read")
    if isinstance(part, np.memmap) and getattr(part, "filename", None):
        out = np.empty(part.shape, part.dtype)
        if out.nbytes:
            with open(part.filename, "rb") as f:
                f.seek(int(part.offset))
                got = f.readinto(memoryview(
                    out.reshape(-1).view(np.uint8)))
            if got != out.nbytes:
                raise CheckpointReadError(
                    f"truncated checkpoint chunk in {part.filename!r}: "
                    f"wanted {out.nbytes} bytes at offset {part.offset}, "
                    f"file ended after {got}")
        return out
    if isinstance(part, np.ndarray):
        return np.asarray(part)
    return part


def _leaf_plan(leaf):
    """(parts, assemble) of one restore leaf — LazyParts pass through,
    anything else is a single already-resolved source."""
    if isinstance(leaf, LazyParts):
        return leaf.parts, leaf.assemble
    return [leaf], (lambda arrs: arrs[0])


def _part_desc(part) -> str:
    fn = getattr(part, "filename", None)
    if fn:
        return f"{fn}@{getattr(part, 'offset', '?')}"
    return type(part).__name__


def _stream_leaves(leaves, plan: _RestorePlan):
    """Yield host arrays for ``leaves`` in order, reads pipelined.

    Every leaf expands into its chunk parts; with ``plan.threads > 1`` a
    reader pool fetches parts concurrently (submission runs ahead of
    consumption until ``readahead_bytes`` of results are in flight, so
    the window — not the pool — bounds host RAM), and the consumer
    assembles each leaf as its chunks land.  The serial fallback
    (``threads <= 1``) executes the same plan inline: identical reads,
    identical assembly, bitwise-identical leaves."""
    from deepspeed_tpu.resilience.retry import io_retry

    def read(part):
        # exhausted-retry storage errors surface as the SAME named error on
        # both the serial and pooled paths (tests pin the contract)
        try:
            return io_retry(lambda: _read_part(part),
                            retries=plan.io_retries,
                            what=f"checkpoint chunk read ({_part_desc(part)})")
        except CheckpointReadError:
            raise
        except Exception as e:
            raise CheckpointReadError(
                f"checkpoint restore reader failed on "
                f"{_part_desc(part)}: {e}") from e

    plans = [_leaf_plan(x) for x in leaves]
    if plan.threads <= 1:
        for parts, assemble in plans:
            yield assemble([read(p) for p in parts])
        return

    import collections
    from concurrent.futures import ThreadPoolExecutor
    flat = [(p, int(getattr(p, "nbytes", 0) or 0))
            for parts, _ in plans for p in parts]
    ex = ThreadPoolExecutor(max_workers=plan.threads,
                            thread_name_prefix="dstpu-ckpt-reader")
    pending = collections.deque()   # (future, nbytes, part) in flat order
    state = {"si": 0, "inflight": 0}

    def pump():
        # keep at least one read in flight and the window full; consuming
        # a result frees window bytes, so the pool always drains forward
        # (no reader ever waits on the consumer — deadlock-free)
        while state["si"] < len(flat) and (
                not pending or state["inflight"] < plan.readahead_bytes):
            part, nb = flat[state["si"]]
            pending.append((ex.submit(read, part), nb, part))
            state["si"] += 1
            state["inflight"] += nb

    try:
        for parts, assemble in plans:
            arrs = []
            for _ in parts:
                pump()
                fut, nb, part = pending.popleft()
                try:
                    arrs.append(fut.result())
                except CheckpointReadError:
                    raise
                except Exception as e:
                    raise CheckpointReadError(
                        f"checkpoint restore reader failed on "
                        f"{_part_desc(part)}: {e}") from e
                state["inflight"] -= nb
                pump()
            yield assemble(arrs)
    finally:
        ex.shutdown(wait=False, cancel_futures=True)


def _place_trees(pairs, plan: _RestorePlan):
    """Restore ``pairs`` of (engine tree, loaded host/lazy tree): streams
    every leaf through ONE pipelined read plan (so placing the module
    overlaps reading the masters) and places each with ``_put_global``.
    Returns the placed trees in ``pairs`` order; ``None`` new-trees map to
    ``None`` (absent moment trees)."""
    olds, news, treedefs, counts = [], [], [], []
    for old, new in pairs:
        if old is None or new is None:
            treedefs.append(None)
            counts.append(0)
            continue
        o, td = jax.tree_util.tree_flatten(old)
        olds.extend(o)
        news.extend(td.flatten_up_to(new))
        treedefs.append(td)
        counts.append(len(o))
    stream = _stream_leaves(news, plan)
    try:
        placed = [_put_global(o, h) for o, h in zip(olds, stream)]
    finally:
        stream.close()      # releases the reader pool on error paths too
    out, i = [], 0
    for td, n in zip(treedefs, counts):
        if td is None:
            out.append(None)
        else:
            out.append(td.unflatten(placed[i:i + n]))
            i += n
    return out


# ------------------------------------------------------------------ loading

def load_module_tree(load_dir: str, tag: Optional[str] = None, specs=None):
    """Host-side module pytree reassembled from a checkpoint's model-state
    files, WITHOUT an engine — the raw-weights read behind
    pretrain→fine-tune transfer (reference BingBertSquad initializes from
    a pretrained BERT checkpoint this way).

    ``specs`` (a PartitionSpec tree matching the SAVED module structure)
    is required only when the checkpoint was written at mp>1 or pp>1 —
    reassembly must know which dims concatenate.  Returns None when no
    checkpoint exists under ``load_dir``.
    """
    ASYNC_SAVER.wait()
    read = _read_model_states(load_dir, tag)
    if read is None:
        return None
    _, states, saved_mp, saved_pp = read
    if saved_mp * saved_pp == 1:
        return states[0]["module"]
    if specs is None:
        raise ValueError(
            f"checkpoint was saved at mp={saved_mp}, pp={saved_pp}: pass "
            "specs (the saving model's partition_specs) so sharded leaves "
            "can be reassembled")
    return _combine_shard_states([s["module"] for s in states], specs,
                                 _state_axes(saved_pp, saved_mp))


def load_params_only(load_dir: str, tag: Optional[str] = None, specs=None,
                     dtype=None, threads: int = 0,
                     readahead_mb: float = 256.0, io_retries: int = 3):
    """Weights-only restore fast path: just the module tree, streamed
    through the PR 5 parallel reader — the serving cold-start read
    (deepspeed_tpu/inference/, docs/inference.md).  Re-entrant by
    design: a speculative-decoding engine calls it TWICE per cold start
    (target weights, then the draft model's checkpoint as a second
    stream with the draft's own ``specs`` — docs/inference.md
    "Speculative decoding").

    Skips every optimizer/ZeRO partition: the stage-1/2 flat-state
    ``zero_pp_rank_*`` shard records are NEVER opened (regression-pinned
    in tests/test_inference.py), and a stage-3 shard-native checkpoint
    reads only the ``param`` chunks of its per-dp shard files (masters
    and moments stay untouched on disk — the container format memmaps
    per chunk, so unread fields cost nothing).

    ``specs`` (the saving model's ``partition_specs()``) is required when
    the checkpoint was written at mp>1 or pp>1, like
    :func:`load_module_tree`.  ``dtype`` casts every floating leaf on
    the host as it lands (the serving engine loads fp32 masters' module
    copies straight into bf16).  ``threads=0`` auto-sizes the reader
    pool; 1 is the serial fallback running the identical plan.

    Returns ``(tag, host_tree)``; ``None`` when no valid checkpoint
    exists under ``load_dir``.
    """
    ASYNC_SAVER.wait()
    plan = _RestorePlan(
        threads=(threads if threads > 0 else _RestorePlan.auto_threads()),
        readahead_mb=readahead_mb, io_retries=io_retries)
    read = _read_model_states(load_dir, tag, lazy=True)
    if read is None:
        return None
    tag, states, saved_mp, saved_pp = read
    if saved_mp * saved_pp == 1:
        module = states[0]["module"]
    else:
        if specs is None:
            raise ValueError(
                f"checkpoint was saved at mp={saved_mp}, pp={saved_pp}: "
                "pass specs (the saving model's partition_specs) so "
                "sharded leaves can be reassembled")
        module = _combine_shard_states([s["module"] for s in states],
                                       specs, _state_axes(saved_pp, saved_mp),
                                       lazy=True)
    np_dtype = None if dtype is None else np.dtype(dtype)

    def _cast(arr):
        arr = np.asarray(arr)
        if np_dtype is None or not (
                np.issubdtype(arr.dtype, np.floating)
                or arr.dtype == jnp.bfloat16):
            return arr
        return arr.astype(np_dtype)

    leaves, treedef = jax.tree_util.tree_flatten(module)
    stream = _stream_leaves(leaves, plan)
    try:
        out = [_cast(h) for h in stream]
    finally:
        stream.close()
    return tag, treedef.unflatten(out)


# --------------------------------------------------------- KV handoff
# Prefill/decode disaggregation ships a slot's written KV page rows from
# a prefill replica to a decode replica as ONE chunk-container file —
# the same on-disk machinery as checkpoints (atomic tmp+rename seal,
# positioned memmap reads, validated chunk refs), so the handoff
# inherits every corruption/torn-file guarantee for free
# (deepspeed_tpu/inference/router.py, docs/inference.md "Fleet serving").

KV_HANDOFF_SCHEMA = "dstpu.kv_handoff"
KV_HANDOFF_VERSION = 1


def write_kv_handoff(path: str, *, k, v, meta: dict,
                     io_retries: int = 3) -> str:
    """Seal one slot's KV handoff artifact at ``path``: the written
    ``k``/``v`` rows (``[layers, tokens, kv_heads, head_dim]``, the
    GLOBAL heads dim) as payload chunks plus a ``meta`` bookkeeping dict
    (prompt tokens, first token, dims — the importer validates these
    against its own cache spec).  Atomic (tmp + rename) and retried
    through ``io_retry`` like every checkpoint write; the target
    directory is created if missing."""
    from deepspeed_tpu.resilience.retry import io_retry
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    header = {"schema": KV_HANDOFF_SCHEMA, "version": KV_HANDOFF_VERSION,
              "meta": dict(meta)}

    def _write():
        w = _ChunkedWriter(path)
        try:
            payload = dict(header)
            payload["k"] = w.put_array(k)
            payload["v"] = w.put_array(v)
            w.finish(payload)
        except BaseException:
            w.abort()
            raise
    io_retry(_write, retries=io_retries,
             what=f"kv handoff write {path!r}")
    return path


def read_kv_handoff(path: str, io_retries: int = 3):
    """Load a KV handoff artifact: ``(meta, k, v)`` with the arrays
    materialized from positioned memmap reads (the PR 5 reader's chunk
    resolution — offsets/dtypes/shapes validated against the payload
    region BEFORE any view is built).  Transient storage errors retry
    through ``io_retry``; a corrupt, truncated or wrong-schema file
    raises :class:`CheckpointReadError` naming the problem — a decode
    replica must fail the one handoff loudly, never import garbage
    pages."""
    from deepspeed_tpu.resilience.retry import io_retry

    def _read():
        _chaos.read_point("ckpt_read")   # chaos tier: Nth-read IO failure
        return _load_obj(path)

    try:
        obj = io_retry(_read, retries=io_retries,
                       what=f"kv handoff read {path!r}")
    except (ValueError, pickle.UnpicklingError, EOFError) as e:
        raise CheckpointReadError(
            f"corrupt KV handoff {path!r}: {e}") from e
    if not isinstance(obj, dict) \
            or obj.get("schema") != KV_HANDOFF_SCHEMA:
        raise CheckpointReadError(
            f"{path!r} is not a KV handoff artifact (schema "
            f"{obj.get('schema') if isinstance(obj, dict) else None!r})")
    if obj.get("version") != KV_HANDOFF_VERSION:
        raise CheckpointReadError(
            f"KV handoff {path!r} has version {obj.get('version')!r}, "
            f"this reader understands {KV_HANDOFF_VERSION}")
    try:
        # np.asarray faults the memmap pages in NOW, so a payload
        # truncated past the validated header surfaces here, named
        k = np.asarray(obj["k"])
        v = np.asarray(obj["v"])
    except (KeyError, ValueError, OSError) as e:
        raise CheckpointReadError(
            f"corrupt KV handoff {path!r}: {e}") from e
    return obj.get("meta", {}), k, v


def _zero3_rehydrate(load_dir: str, tag: str, states, lazy: bool = False):
    """Replace stage-3 partition markers in freshly read model states with
    full-along-data leaves reassembled from the per-(row, dp) shard files
    (concat along the recorded dim).  After this the states look exactly
    like stage-<=2 files, so every downstream path (cross-row combine,
    cross-topology/-stage restore, raw-weights reads) works unchanged.
    With ``lazy=False`` reassembly materialises one full leaf at a time on
    the host (the shard chunks themselves are memmap views); ``lazy=True``
    returns :class:`LazyParts` leaves instead — same chunks, same concat,
    deferred so the restore reader pool can fetch the per-dp shard records
    of one leaf concurrently (``_stream_leaves``)."""
    if not states or not states[0].get("zero3_native"):
        return states
    for row, state in enumerate(states):
        cache = {}

        def shard_leaves(dpi):
            if dpi not in cache:
                f = zero3_file(load_dir, tag, dpi, row)
                if not os.path.exists(f):
                    raise FileNotFoundError(
                        f"stage-3 checkpoint is missing shard file {f} "
                        f"(saved at dp={states[0].get('dp_world_size')})")
                cache[dpi] = _load_obj(f)["leaves"]
            return cache[dpi]

        def fix(tree, field):
            """Replace markers by walking the state tree in FLATTEN ORDER:
            leaf i here is leaf i of the saving engine's params tree, so
            the shard record is ``leaves[i]`` — no path-string formatting
            (the old hand-built keystrs broke on int-keyed dicts; ADVICE
            r5).  ``keystr``-keyed records from legacy shard files still
            resolve as a fallback."""
            idx = [-1]

            def one(path, leaf):
                idx[0] += 1
                if not _z3_marker(leaf):
                    return leaf
                _, dim, dp = leaf

                def rec(d):
                    leaves = shard_leaves(d)
                    r = leaves.get(idx[0])
                    if r is None:   # legacy keystr-keyed shard files
                        r = leaves[jax.tree_util.keystr(path)]
                    return r

                chunks = [rec(d)[field] for d in range(dp)]
                if lazy:
                    return LazyParts.concat(chunks, dim)
                return np.concatenate(
                    [np.asarray(c) for c in chunks], axis=dim)

            return jax.tree_util.tree_map_with_path(
                one, tree, is_leaf=_z3_marker)

        state["module"] = fix(state["module"], "param")
        opt = state.get("optimizer")
        if opt is not None:
            opt["master"] = fix(opt["master"], "master")
            if opt["opt_state"]["m"] is not None:
                opt["opt_state"]["m"] = fix(opt["opt_state"]["m"], "m")
            if opt["opt_state"]["v"] is not None:
                opt["opt_state"]["v"] = fix(opt["opt_state"]["v"], "v")
    return states


def _read_model_states(load_dir: str, tag: Optional[str], lazy: bool = False):
    """Shared tag-resolution + model-state file reads (load_checkpoint and
    load_module_tree).  Returns ``(tag, states, saved_mp, saved_pp)`` or
    None when no checkpoint exists.  ``lazy`` defers the stage-3 shard
    reassembly to :class:`LazyParts` leaves (the streaming restore)."""
    if tag is None:
        latest = os.path.join(load_dir, LATEST_FILE)
        tag = None
        if os.path.exists(latest):
            with open(latest) as f:
                tag = f.read().strip() or None
        if tag is None or not validate_tag(load_dir, tag):
            # a corrupt/empty/stale `latest` (crash mid-publish, deleted
            # tag dir) must not break resume: fall back to the newest
            # valid tag directory on disk (regression-pinned in
            # tests/test_resilience.py)
            fallback = find_latest_valid_tag(load_dir)
            if tag is not None and fallback is not None:
                import logging
                logging.getLogger(__name__).warning(
                    "checkpoint `latest` pointer (%r) is corrupt or names "
                    "an invalid tag; falling back to newest valid tag %r",
                    tag, fallback)
            tag = fallback
            if tag is None:
                return None
    mfile = _model_probe(load_dir, tag)
    if mfile is None:
        # (explicit-tag path; the canonical probe covers both the mp_rank
        # and the pp>1 per-stage file layouts)
        return None
    state = _load_obj(mfile)
    saved_mp = int(state.get("mp_world_size", 1))
    saved_pp = int(state.get("pp_world_size", 1))
    states = [state] + [
        _load_obj(model_file(load_dir, tag, r % saved_mp, r // saved_mp,
                             saved_pp))
        for r in range(1, saved_pp * saved_mp)]
    states = _zero3_rehydrate(load_dir, tag, states, lazy=lazy)
    return tag, states, saved_mp, saved_pp


def _put_global(old, new):
    """Place the host-global value ``new`` on devices with ``old``'s
    sharding and dtype, WITHOUT collectives.

    ``jax.device_put`` of a host value whose target sharding spans
    processes first runs ``multihost_utils.assert_equal`` — a full-array
    cross-host broadcast per LEAF.  Across a restore that is O(model
    bytes) of gloo/ICI traffic for values every host just read from the
    same files, and the per-leaf broadcast stream was the desync surface
    the chaos tier kept tripping (a lagging rank pairs broadcast k with
    k+1 and the transport aborts).  ``make_array_from_callback`` builds
    the array from locally-addressable shards with no cross-process
    traffic at all."""
    arr = np.asarray(new, old.dtype)
    if arr.shape != tuple(old.shape):
        raise ValueError(
            f"checkpoint restore: loaded value has shape {arr.shape}, "
            f"engine expects {tuple(old.shape)}")
    sharding = old.sharding
    if sharding.is_fully_addressable:
        # device_put straight from the host buffer: routing through
        # jnp.asarray first would stage an extra full-leaf copy on the
        # restore critical path
        return jax.device_put(arr, sharding)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


def init_from_module_tree(engine, module) -> tuple:
    """Transfer same-named, same-shaped leaves of ``module`` into
    ``engine.params`` — the pretrain→fine-tune initialization (a fresh
    task head keeps its random init).  fp32 masters re-derive from the
    merged params so the first ``step()`` cannot revert the transfer.
    Returns ``(loaded, skipped)`` key-path lists.
    """
    src = {jax.tree_util.keystr(k): v
           for k, v in jax.tree_util.tree_leaves_with_path(module)}
    loaded, skipped = [], []

    def merge(path, old):
        key = jax.tree_util.keystr(path)
        new = src.get(key)
        if new is not None and tuple(np.shape(new)) == tuple(old.shape):
            loaded.append(key)
            return _put_global(old, new)
        skipped.append(key)
        return old

    engine.params = jax.tree_util.tree_map_with_path(merge, engine.params)
    _rederive_masters(engine)
    return loaded, skipped


def load_checkpoint(engine, load_dir: str, tag: Optional[str] = None,
                    load_optimizer_states: bool = True,
                    load_lr_scheduler_states: bool = True):
    """Engine-level load (reference load_checkpoint :974-1046).  Returns
    ``(path, client_state)``; (None, None) when nothing is found.

    The heavy reads run through the streaming restore pipeline (see the
    "parallel streaming restore" section above): every state tree's leaves
    enter ONE read plan, so the reader pool fetches the masters' chunks
    while the module weights are already being placed on devices."""
    ASYNC_SAVER.wait()   # never read a tag whose writes are still queued
    plan = _RestorePlan.from_engine(engine)
    read = _read_model_states(load_dir, tag, lazy=True)
    if read is None:
        return None, None
    tag, states, saved_mp, saved_pp = read
    state = states[0]

    # module weights (compute dtype), reassembled from the per-stage/MP-rank
    # local slices and re-sharded for the CURRENT mesh — reference :995-1004
    # (which requires the same MP degree; the reassembly lifts that)
    saved_axes = _state_axes(saved_pp, saved_mp)
    # lazy: cross-MP/PP-shard concatenations stay deferred so the reader
    # pool fetches each shard's chunks concurrently (_place_trees streams
    # every leaf below)
    module = _combine_shard_states([s["module"] for s in states],
                                   engine._param_specs, saved_axes,
                                   lazy=True)

    # counters — reference :1014-1017
    engine.global_steps = int(state["global_steps"])
    engine.skipped_steps = int(state["skipped_steps"])
    engine.micro_steps = int(state["micro_steps"])

    # loss scale — through _put_global, NOT a bare jnp.asarray: the
    # engine pins these leaves committed+replicated at build, and an
    # unpinned restore would hash a DIFFERENT executable key than the
    # cached step program, so every resume would pay a recompile the
    # persistent cache can never serve (the same stability.unpinned-
    # sharding class as the opt_state.step incident; pinned by
    # test_compile_cache_hits_after_restore)
    old_ls = engine.loss_scale_state._asdict()
    engine.loss_scale_state = type(engine.loss_scale_state)(
        **{k: _put_global(old_ls[k], np.asarray(v))
           for k, v in state["loss_scale_state"].items()})

    for live, saved in zip(engine.optimizer.param_groups,
                           state.get("param_groups", [])):
        live.update(saved)

    if (load_lr_scheduler_states and engine.lr_scheduler is not None
            and state.get("lr_scheduler") is not None
            and hasattr(engine.lr_scheduler, "load_state_dict")):
        engine.lr_scheduler.load_state_dict(state["lr_scheduler"])

    restored_masters = False
    saved_stage = state.get("zero_stage",
                            1 if state.get("zero_enabled") else 0)
    zero_flat = getattr(engine, "zero_flat", engine.zero_enabled)
    opt_pairs = []
    if load_optimizer_states:
        if zero_flat:
            if saved_stage == 3:
                raise ValueError(
                    "checkpoint was saved at ZeRO stage 3 (optimizer state "
                    "inline, per-leaf) but this engine runs the stage-1/2 "
                    "flat layout — set zero_optimization.stage=3 (or 0) to "
                    "restore it, or pass load_optimizer_states=False")
        elif saved_stage in (1, 2):
            raise ValueError(
                "checkpoint was saved with zero_optimization stage 1/2 "
                "(its optimizer state lives in zero_pp_rank_* shards) but "
                "this engine runs no flat ZeRO layout — match the stage, "
                "or pass load_optimizer_states=False for a weights-only "
                "load")
        elif state.get("optimizer") is not None:
            master = _combine_shard_states(
                [s["optimizer"]["master"] for s in states],
                engine._param_specs, saved_axes, lazy=True)
            m_trees = [s["optimizer"]["opt_state"]["m"] for s in states]
            m_tree = (None if m_trees[0] is None
                      else _combine_shard_states(m_trees,
                                                 engine._param_specs,
                                                 saved_axes, lazy=True))
            v_trees = [s["optimizer"]["opt_state"]["v"] for s in states]
            v_tree = (None if v_trees[0] is None
                      else _combine_shard_states(v_trees,
                                                 engine._param_specs,
                                                 saved_axes, lazy=True))
            opt_pairs = [(engine.master, master),
                         (engine.opt_state.m, m_tree),
                         (engine.opt_state.v, v_tree)]

    placed = _place_trees([(engine.params, module)] + opt_pairs, plan)
    engine.params = placed[0]
    if opt_pairs:
        engine.master = placed[1]
        engine.opt_state = type(engine.opt_state)(
            # through _put_global, NOT a bare jnp.asarray: the step counter
            # must come back with the engine's replicated sharding or the
            # boundary program re-lowers with an unpinned scalar input —
            # a different executable, so the persistent compile cache
            # misses on every resume (the exact recompile fast resume
            # exists to avoid)
            step=_put_global(engine.opt_state.step,
                             state["optimizer"]["opt_state"]["step"]),
            m=placed[2], v=placed[3])
        restored_masters = True
    if load_optimizer_states and zero_flat:
        _load_zero_checkpoint(engine, load_dir, tag, plan)
        restored_masters = True
    if not restored_masters:
        # weights-only fine-tune (load_optimizer_states=False), or a
        # checkpoint whose optimizer states live elsewhere: the fp32 masters
        # MUST be re-derived from the loaded weights or the first step()
        # would silently revert params to the pre-load masters
        _rederive_masters(engine)

    return os.path.join(load_dir, tag), state.get("client_state", {})


def _rederive_masters(engine) -> None:
    """Rebuild fp32 masters (flat or per-leaf) from engine.params."""
    masters = jax.tree_util.tree_map(
        lambda p: jnp.asarray(p, jnp.float32), engine.params)
    zero_flat = getattr(engine, "zero_flat", engine.zero_enabled)
    if zero_flat and engine._zero_state_axes:
        engine.master_flat = engine._flatten_masters_2d(masters)
    elif zero_flat:
        flat = engine._tile_flat(
            zero_mod.flatten_tree(masters, engine.flat_meta))
        engine.master_flat = jax.device_put(flat,
                                            engine.master_flat.sharding)
    else:
        engine.master = jax.tree_util.tree_map(
            lambda old, m: jax.device_put(m, old.sharding),
            engine.master, masters)


def _load_zero_checkpoint(engine, load_dir: str, tag: str,
                          plan: Optional[_RestorePlan] = None) -> None:
    """Reassemble the flat fp32 master + moments from per-partition shards
    saved under ANY dp world size, re-pad for the current topology
    (reference _load_zero_checkpoint :1034-1046 requires matching topology;
    we lift the DP restriction — MP and PP must match, like the
    reference).  The shard-chunk reads stream through the restore plan:
    master / m / v enter one pipelined plan, so the moments' partitions
    read while the master is being placed and the params re-derived."""
    mp = engine.mp_world_size
    pp = getattr(engine, "pp_world_size", 1)
    meta = engine.flat_meta
    first = zero_file(load_dir, tag, 0, 0)
    if not os.path.exists(first):
        raise FileNotFoundError(
            f"no zero checkpoint shards under {load_dir}/{tag}")
    shard0 = _load_obj(first)
    saved_mp = int(shard0.get("mp_world_size", 1))
    saved_pp = int(shard0.get("pp_world_size", 1))
    if saved_mp != mp or saved_pp != pp:
        raise ValueError(
            f"zero checkpoint was saved with model_parallel_size="
            f"{saved_mp}, pipeline_parallel_size={saved_pp}; engine has "
            f"mp={mp}, pp={pp}: ZeRO flat partitions are per-stage/shard "
            f"and cannot be re-split (load with "
            f"load_optimizer_states=False for a weights-only restore)")
    # trust the recorded partition count, not directory probing — stale
    # shards from an earlier save of the same tag under a larger dp must be
    # ignored (partition_count < dp_world_size when the save side used
    # parameter_parallel_size sub-groups)
    saved_dp = int(shard0.get("partition_count", shard0["dp_world_size"]))
    total = int(shard0["unpadded_total"])
    if total != meta.total:
        raise ValueError(
            f"zero checkpoint has {total} elements, engine expects "
            f"{meta.total} (different model?)")

    rows = pp * mp  # composite stage/rank rows of the [S, local] layout
    table = [[_load_obj(zero_file(load_dir, tag, r, m))
              for r in range(saved_dp)] for m in range(rows)]

    def lazy_stack(key):
        """Deferred [rows?, padded·repl] buffer for ``key``: the per-(row,
        partition) shard chunks are the parts a reader pool fetches;
        assembly concatenates each row, re-pads, and re-tiles for the
        engine's sub-group layout (no-op at pps == dp)."""
        parts = [table[m][r][key]
                 for m in range(rows) for r in range(saved_dp)]

        def assemble(arrs):
            mats = []
            for m in range(rows):
                flat = np.concatenate(
                    [np.asarray(a)
                     for a in arrs[m * saved_dp:(m + 1) * saved_dp]])
                assert flat.shape[0] == total, (key, flat.shape, total)
                pad = meta.padded - total
                if pad:
                    flat = np.concatenate(
                        [flat, np.zeros((pad,), flat.dtype)])
                mats.append(engine._tile_flat(flat))
            return mats[0] if rows == 1 else np.stack(mats)

        return LazyParts(parts, assemble)

    stream = _stream_leaves(
        [lazy_stack("master"), lazy_stack("m"), lazy_stack("v")],
        plan or _RestorePlan())
    try:
        host_master = next(stream)
        engine.master_flat = _put_global(engine.master_flat, host_master)
        host_m = next(stream)
        host_v = next(stream)
    finally:
        stream.close()
    engine.opt_state = type(engine.opt_state)(
        # _put_global keeps the step counter's replicated sharding so the
        # restored boundary step re-lowers to the SAME executable and the
        # persistent compile cache can serve it (see the stage-3 site)
        step=_put_global(engine.opt_state.step, table[0][0]["step"]),
        m={"flat": _put_global(engine.opt_state.m["flat"], host_m)},
        v={"flat": _put_global(engine.opt_state.v["flat"], host_v)})
    # params re-derived from the HOST copy of the restored master (bit-exact
    # resume; never device_gets the sharded global array — multi-host safe)
    engine.params = engine._params_from_master_flat(host_master)
