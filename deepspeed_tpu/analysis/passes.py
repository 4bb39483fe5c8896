"""The four graph-lint passes.

Each pass takes a traced jaxpr (open or closed) and appends
:class:`~deepspeed_tpu.analysis.report.Finding`s to a
:class:`~deepspeed_tpu.analysis.report.Report`.  See docs/analysis.md for
the rule catalogue; rule codes are stable and suppressible by prefix.

1. ``collectives``  — every rank must issue the same ordered collective
   sequence.  Under SPMD the one divergence mechanism is control flow on a
   rank-dependent value, so the pass taints dataflow from ``axis_index`` and
   compares the ordered collective signatures of every ``cond``/``switch``
   branch whose predicate carries that taint (the 1F1B/GPipe stage
   schedules in parallel/pipeline.py are exactly this shape).  Signatures
   include the operand shape/dtype — the wire format — so branches
   chunking the same payload differently (K same-primitive collectives
   told apart only by their chunk shapes) are a real deadlock and are
   flagged.  Also checks
   axis names against the engine mesh and ``ppermute`` permutation validity
   — all of ``comm.py``'s wrappers (psum, psum_scatter with
   ``axis_index_groups`` sub-groups, all_gather) produce these primitives.
2. ``precision``    — fp32 compute reachable from low-precision values via
   an explicit upcast.  The error class is a convert-to-fp32 feeding a
   ``dot_general``/conv (doubles MXU and HBM cost versus a bf16 dot with
   ``preferred_element_type=fp32``, which is free and is NOT flagged);
   large elementwise upcast islands are reported at info, low-precision
   big reductions at warning.
3. ``transfers``    — in-graph host round trips (``pure_callback`` /
   ``io_callback``), weak-typed program inputs (Python scalars in carried
   state force a retrace when their dtype promotes), and donation
   opportunities (a large input whose shape/dtype matches an output and is
   not in ``donated_invars``).
4. ``shard specs``  — shard_map/NamedSharding PartitionSpecs validated
   against the mesh and the actual values BEFORE compile: unknown axes,
   specs longer than the value rank, and non-divisible dims.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.analysis import graph as G
from deepspeed_tpu.analysis import report as R

# primitive-name sets ------------------------------------------------------

#: blocking cross-rank primitives (mismatched order across ranks = deadlock)
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "pmean", "ppermute", "pshuffle", "all_gather",
    "all_to_all", "psum_scatter", "reduce_scatter", "pgather",
    "psum_invariant",
})

#: in-graph host round trips; pure/io callbacks stall the device every step
HARD_CALLBACK_PRIMS = frozenset({"pure_callback", "io_callback",
                                 "outside_call", "host_callback_call"})
SOFT_CALLBACK_PRIMS = frozenset({"debug_callback", "debug_print"})

DOT_PRIMS = frozenset({"dot_general", "conv_general_dilated"})
REDUCE_PRIMS = frozenset({"reduce_sum", "cumsum", "cumlogsumexp"})

LOW_PRECISION = (jnp.bfloat16, jnp.float16)

#: element-count thresholds: below these an upcast / low-precision reduce is
#: noise (scalars, layer-norm stats), above it it is load-bearing
UPCAST_INFO_MIN_SIZE = 1 << 16
LOWP_REDUCE_MIN_SIZE = 1 << 16
DONATION_MIN_BYTES = 1 << 20


def _is_lowp(dtype) -> bool:
    return dtype is not None and any(dtype == jnp.dtype(d)
                                     for d in LOW_PRECISION)


def _is_f32(dtype) -> bool:
    return dtype is not None and dtype == jnp.dtype(jnp.float32)


# ======================================================================
# Pass 1: collective consistency
# ======================================================================

#: operand-independent layout params that change the wire format of a
#: collective (all_to_all split/concat dims, scatter tiling): two ranks
#: issuing the "same" collective with different layouts still mismatch
_SIG_LAYOUT_KEYS = ("split_axis", "concat_axis", "split_count",
                    "scatter_dimension", "all_gather_dimension", "tiled",
                    "axis")


def _collective_sig(eqn) -> Tuple:
    p = eqn.params
    axes = p.get("axes", p.get("axis_name", ()))
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    groups = p.get("axis_index_groups")
    perm = p.get("perm")
    layout = tuple((k, p[k]) for k in _SIG_LAYOUT_KEYS if k in p)
    # operand shapes/dtypes are part of the wire format: K same-primitive
    # chunked psums differ only in the buffer shape, so two branches chunking
    # the same payload DIFFERENTLY (or one chunked, one monolithic) must
    # compare unequal — ranks in either branch would block exchanging
    # mismatched buffers.  ALL operands are hashed: psum-family eqns carry
    # several arrays at once, and a divergence in operand 2..N (or in the
    # operand count) mismatches on the wire just as hard as the first
    op = tuple(
        (tuple(getattr(v.aval, "shape", ())),
         str(getattr(v.aval, "dtype", "")))
        for v in eqn.invars)
    return (
        eqn.primitive.name,
        tuple(str(a) for a in axes),
        None if groups is None else tuple(tuple(g) for g in groups),
        None if perm is None else tuple(tuple(pr) for pr in perm),
        layout,
        op,
    )


def _fmt_sig(sig: Tuple) -> str:
    if sig[0] == "scan":           # composite: ("scan", length, inner_sigs)
        _, length, inner = sig
        body = ", ".join(_fmt_sig(s) for s in inner)
        return f"scan[length={length}]({body})"
    name, axes, groups, perm, layout, op = sig
    s = f"{name}(axis={','.join(axes)}"
    if groups is not None:
        s += f", groups={list(map(list, groups))}"
    if perm is not None:
        s += f", perm={list(map(list, perm))}"
    for k, v in layout:
        s += f", {k}={v}"
    for shape, dt in op:
        s += f", operand={dt}{list(shape)}"
    return s + ")"


def _first_divergence(a: List[Tuple], b: List[Tuple]) -> str:
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            return (f"position {i}: {_fmt_sig(x)} vs {_fmt_sig(y)}")
    if len(a) != len(b):
        i = min(len(a), len(b))
        longer = a if len(a) > len(b) else b
        return (f"position {i}: {_fmt_sig(longer[i])} vs <no collective> "
                f"(sequence lengths {len(a)} vs {len(b)})")
    return "<identical>"


#: full-axis sum/max-style reductions whose result is REPLICATED over the
#: reduced axes (without axis_index_groups) — they launder rank identity
RANK_INVARIANT_PRIMS = frozenset({"psum", "pmax", "pmin", "pmean",
                                  "all_gather", "psum_invariant"})


def check_collectives(jaxpr, report: R.Report,
                      mesh_axes: Optional[Sequence[str]] = None) -> None:
    """Pass 1.  ``mesh_axes``: the engine mesh axis names; None skips the
    axis-name check (standalone jaxprs traced with axis_env)."""
    known_axes = set(map(str, mesh_axes)) if mesh_axes is not None else None

    def visit(j, taint: G.AxisTaint, path: str) -> List[Tuple]:
        seq: List[Tuple] = []
        jj = G._as_open_jaxpr(j)
        if jj is None:
            return seq
        for eqn in jj.eqns:
            name = eqn.primitive.name
            if name == "axis_index":
                ax = eqn.params.get("axis_name")
                axs = ax if isinstance(ax, (tuple, list)) else (ax,)
                for v in eqn.outvars:
                    taint.mark(v, tuple(str(a) for a in axs))
            elif (name in RANK_INVARIANT_PRIMS
                    and eqn.params.get("axis_index_groups") is None):
                # a full-axis reduce/gather replicates its result over the
                # reduced axes: rank-dependence over THOSE axes ends here
                sig_axes = _collective_sig(eqn)[1]
                taint.step(eqn, removed=sig_axes)
            else:
                taint.step(eqn)

            if name in COLLECTIVE_PRIMS:
                sig = _collective_sig(eqn)
                seq.append(sig)
                if known_axes is not None:
                    unknown = [a for a in sig[1] if a not in known_axes]
                    if unknown:
                        report.add(
                            "collective.axis-unknown", R.ERROR,
                            f"{_fmt_sig(sig)} reduces over axis "
                            f"{unknown} which is not an engine mesh axis "
                            f"{sorted(known_axes)}; this program cannot run "
                            f"on the engine mesh",
                            path=path, source=G.source_of(eqn),
                            pass_name="collectives")
                if sig[3] is not None:      # ppermute perm validity
                    srcs = [p[0] for p in sig[3]]
                    dsts = [p[1] for p in sig[3]]
                    if len(set(srcs)) != len(srcs) or \
                            len(set(dsts)) != len(dsts):
                        report.add(
                            "collective.ppermute-malformed", R.ERROR,
                            f"{_fmt_sig(sig)} has duplicate sources or "
                            f"destinations: it is not a permutation, so "
                            f"some rank will wait on a message that never "
                            f"arrives (deadlock)",
                            path=path, source=G.source_of(eqn),
                            pass_name="collectives")

            subs = G.subjaxprs(eqn)
            if not subs:
                continue

            if name in ("cond", "switch") and len(subs) > 1:
                pred = eqn.invars[0]
                pred_rankdep = bool(taint.axes_of(pred))
                branch_seqs = []
                for i, (label, sub) in enumerate(subs):
                    sub_path = f"{path}/{label}" if path else label
                    sub_t = taint.seed_sub(eqn, sub)
                    branch_seqs.append(visit(sub, sub_t, sub_path))
                    taint.propagate_out(eqn, sub, sub_t)
                base = branch_seqs[0]
                mismatch = next((i for i, b in enumerate(branch_seqs[1:], 1)
                                 if b != base), None)
                if mismatch is not None:
                    detail = _first_divergence(base,
                                               branch_seqs[mismatch])
                    if pred_rankdep:
                        report.add(
                            "collective.divergent-order", R.ERROR,
                            f"cond/switch branches issue DIFFERENT ordered "
                            f"collective sequences and the predicate "
                            f"depends on axis_index (rank identity): ranks "
                            f"taking different branches will block in "
                            f"mismatched collectives — a whole-slice "
                            f"deadlock at run time.  First divergence: "
                            f"{detail}",
                            path=path, source=G.source_of(eqn),
                            pass_name="collectives")
                    else:
                        report.add(
                            "collective.branch-mismatch", R.INFO,
                            f"cond/switch branches issue different "
                            f"collective sequences ({detail}); safe only "
                            f"if the predicate is identical on every rank "
                            f"— verify it derives from replicated state",
                            path=path, source=G.source_of(eqn),
                            pass_name="collectives")
                # representative branch for the enclosing sequence
                seq.extend(base)
            else:
                for label, sub in subs:
                    sub_path = f"{path}/{label}" if path else label
                    sub_t = taint.seed_sub(eqn, sub)
                    sub_seq = visit(sub, sub_t, sub_path)
                    taint.propagate_out(eqn, sub, sub_t)
                    if name == "scan" and sub_seq:
                        # fold the trip count into the signature: a scan
                        # issues its body's collectives `length` times, so
                        # branches scanning the same body DIFFERENT numbers
                        # of times must compare unequal (a real deadlock),
                        # and the length is visible in the report
                        seq.append(("scan", eqn.params.get("length"),
                                    tuple(sub_seq)))
                    else:
                        seq.extend(sub_seq)
        return seq

    visit(jaxpr, G.AxisTaint(), "")


# ======================================================================
# Pass 2: precision flow
# ======================================================================

def check_precision(jaxpr, report: R.Report) -> None:
    """Pass 2: upcast-then-dot errors, large upcast islands, low-precision
    reductions.  The taint is "was explicitly converted up from bf16/fp16":
    converting back down to a low-precision dtype launders it (layer-norm /
    gelu fp32 islands end in a down-cast and stay quiet unless a dot ran
    inside)."""

    def visit(j, upcast: G.Taint, path: str, emit: bool = True) -> None:
        jj = G._as_open_jaxpr(j)
        if jj is None:
            return
        for eqn in jj.eqns:
            name = eqn.primitive.name
            if name == "convert_element_type":
                new_dtype = jnp.dtype(eqn.params.get("new_dtype"))
                src = eqn.invars[0]
                if _is_f32(new_dtype) and _is_lowp(G.dtype_of(src)):
                    for v in eqn.outvars:
                        upcast.mark(v)
                    if emit and G.size_of(src) >= UPCAST_INFO_MIN_SIZE:
                        report.add(
                            "precision.upcast", R.INFO,
                            f"large fp32 upcast of a "
                            f"{G.dtype_of(src)} value "
                            f"({G.size_of(src)} elements): fp32 copies "
                            f"double HBM traffic; intended for loss / "
                            f"norm islands, a mistake on the compute path",
                            path=path, source=G.source_of(eqn),
                            pass_name="precision")
                elif _is_lowp(new_dtype):
                    # down-cast launders the upcast taint
                    pass
                else:
                    upcast.step(eqn)
                continue

            if emit and name in DOT_PRIMS:
                out_dt = G.dtype_of(eqn.outvars[0])
                if _is_f32(out_dt) and upcast.any_marked(eqn.invars):
                    report.add(
                        "precision.upcast-dot", R.ERROR,
                        "fp32 matmul/conv on operands explicitly upcast "
                        "from bf16/fp16: this runs the MXU at fp32 rates "
                        "and doubles operand HBM bytes.  Keep the operands "
                        "low-precision and request fp32 accumulation via "
                        "preferred_element_type=jnp.float32 instead",
                        path=path, source=G.source_of(eqn),
                        pass_name="precision")

            if emit and name in REDUCE_PRIMS:
                in_dt = G.dtype_of(eqn.invars[0])
                if _is_lowp(in_dt) and \
                        G.size_of(eqn.invars[0]) >= LOWP_REDUCE_MIN_SIZE:
                    # info, not warning: the biggest legitimate source is
                    # the transpose of broadcast-adds (bias grads), which
                    # every fp16 framework sums in compute dtype under the
                    # loss-scale FSM's protection.  Forward-path bf16 sums
                    # are worth a look, hence the report.
                    report.add(
                        "precision.lowp-accum", R.INFO,
                        f"{name} accumulates {G.size_of(eqn.invars[0])} "
                        f"elements in {in_dt}: large sums lose mantissa "
                        f"bits in bf16/fp16 — if this is forward-path "
                        f"compute (not a bias-grad transpose), accumulate "
                        f"in fp32 and down-cast the result",
                        path=path, source=G.source_of(eqn),
                        pass_name="precision")

            subs = G.subjaxprs(eqn)
            if subs:
                # sub-jaxpr-carrying equations propagate through the
                # bodies ONLY (seed -> visit -> propagate_out): a coarse
                # outer step would re-taint outputs whose branches all
                # laundered the upcast with a down-cast
                for label, sub in subs:
                    sub_path = f"{path}/{label}" if path else label
                    sub_t = upcast.seed_sub(eqn, sub)
                    if name == "scan":
                        # loop-carried taint: an upcast created in
                        # iteration N can reach a dot in iteration N+1
                        # through the carry, so iterate taint-only passes
                        # (emit=False) mapping carry-out -> carry-in to a
                        # fixed point before the reporting pass
                        _scan_carry_fixpoint(eqn, sub, sub_t, sub_path)
                    visit(sub, sub_t, sub_path, emit=emit)
                    upcast.propagate_out(eqn, sub, sub_t)
            else:
                # taint flows through everything else (stopped only by
                # the explicit down-cast branch above)
                upcast.step(eqn)

    def _scan_carry_fixpoint(eqn, sub, sub_t, sub_path):
        body = G._as_open_jaxpr(sub)
        num_consts = int(eqn.params.get("num_consts", 0))
        num_carry = int(eqn.params.get("num_carry", 0))
        if num_carry <= 0 or body is None:
            return
        carry_in = body.invars[num_consts:num_consts + num_carry]
        carry_out = body.outvars[:num_carry]
        for _ in range(num_carry + 1):      # monotone; small bound suffices
            visit(sub, sub_t, sub_path, emit=False)
            changed = False
            for co, ci in zip(carry_out, carry_in):
                if sub_t.is_marked(co) and not sub_t.is_marked(ci):
                    sub_t.mark(ci)
                    changed = True
            if not changed:
                return

    visit(jaxpr, G.Taint(), "")


# ======================================================================
# Pass 3: transfers / recompilation
# ======================================================================

def _is_spool_drain(eqn) -> bool:
    """Allowlist check: the telemetry drain callback carries a
    ``_dstpu_spool_drain`` marker on the wrapped host function
    (observability/spool.py sets it on the one function it passes to
    ``io_callback``).  Matching on the marker — not the primitive — means
    any OTHER io_callback in a step program still errors."""
    cb = eqn.params.get("callback")
    if cb is None:
        return False
    fn = getattr(cb, "callback_func", None) or getattr(cb, "f", None) or cb
    return bool(getattr(fn, "_dstpu_spool_drain", False))


def check_transfers(jaxpr, report: R.Report) -> None:
    """Pass 3: host callbacks, weak-typed inputs, donation opportunities.
    The telemetry spool's once-per-window drain callback is allowlisted
    (``transfer.spool-drain``, info) — see :func:`_is_spool_drain`."""
    jj = G._as_open_jaxpr(jaxpr)
    if jj is None:
        return

    # weak-typed program inputs: a Python scalar in carried state retraces
    # the program when its value becomes a strong-typed array
    for i, v in enumerate(jj.invars):
        aval = G.aval_of(v)
        if getattr(aval, "weak_type", False):
            report.add(
                "transfer.weak-type", R.WARNING,
                f"program input {i} is weak-typed ({aval}): it was traced "
                f"from a Python scalar — passing a jnp/np array (or a "
                f"different Python type) later forces a silent retrace "
                f"and recompile.  Stage carried state as jnp.asarray with "
                f"an explicit dtype",
                path="", source="", pass_name="transfers")

    for eqn, path in G.walk(jj):
        name = eqn.primitive.name
        if name in HARD_CALLBACK_PRIMS:
            if _is_spool_drain(eqn):
                # the ONE sanctioned ordered host transfer: the telemetry
                # MetricSpool's batched drain callback — dispatched once
                # per report window (never per step), reading a tiny ring
                # buffer the compiled step filled on device
                # (observability/spool.py).  An UNSPOOLED per-step
                # io_callback still takes the error branch below.
                report.add(
                    "transfer.spool-drain", R.INFO,
                    f"{name} is the telemetry MetricSpool drain — an "
                    f"allowlisted ordered host transfer batched once per "
                    f"report window (docs/observability.md)",
                    path=path, source=G.source_of(eqn),
                    pass_name="transfers")
                continue
            report.add(
                "transfer.host-callback", R.ERROR,
                f"{name} embeds a host round trip in the step program: "
                f"the device blocks on Python every execution — on a pod "
                f"slice every chip stalls for the slowest host.  Move the "
                f"computation into the graph or do it outside the step",
                path=path, source=G.source_of(eqn), pass_name="transfers")
        elif name in SOFT_CALLBACK_PRIMS:
            report.add(
                "transfer.debug-callback", R.WARNING,
                f"{name} (jax.debug.*) runs a host callback inside the "
                f"step program; fine for debugging, remove before "
                f"production runs",
                path=path, source=G.source_of(eqn), pass_name="transfers")

        # donation: a jit level records donated_invars; large inputs whose
        # aval matches an output and are not donated double-buffer in HBM
        if name == "jit" and "donated_invars" in eqn.params:
            donated = eqn.params["donated_invars"]
            sub = G._as_open_jaxpr(eqn.params.get("jaxpr"))
            if sub is None:
                continue
            out_avals = {}
            for ov in sub.outvars:
                aval = G.aval_of(ov)
                key = (getattr(aval, "shape", None),
                       str(getattr(aval, "dtype", "")))
                out_avals[key] = out_avals.get(key, 0) + 1
            for i, (iv, don) in enumerate(zip(sub.invars, donated)):
                if don:
                    continue
                aval = G.aval_of(iv)
                key = (getattr(aval, "shape", None),
                       str(getattr(aval, "dtype", "")))
                nbytes = G.size_of(iv) * getattr(
                    getattr(aval, "dtype", np.dtype(np.int8)), "itemsize", 1)
                if out_avals.get(key, 0) > 0 and \
                        nbytes >= DONATION_MIN_BYTES:
                    out_avals[key] -= 1
                    report.add(
                        "transfer.donation", R.INFO,
                        f"input {i} ({key[0]}, {key[1]}, "
                        f"{nbytes / 2**20:.1f} MiB) matches an output "
                        f"shape/dtype but is not donated: XLA keeps both "
                        f"buffers live across the step.  If the caller "
                        f"does not reuse it, donate it "
                        f"(jax.jit(..., donate_argnums=...))",
                        path=path, source=G.source_of(eqn),
                        pass_name="transfers")


# ======================================================================
# Pass 4: shard-spec validation
# ======================================================================

def _spec_entries(spec):
    """PartitionSpec -> list of per-dim entries (each None | str | tuple)."""
    return list(spec)


def _axes_of_entry(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, (tuple, list)):
        return tuple(str(a) for a in entry)
    return (str(entry),)


def check_shard_specs(mesh_shape, specs, tree, report: R.Report,
                      where: str = "") -> None:
    """Pass 4: validate a pytree of PartitionSpecs against the mesh and the
    matching pytree of values/ShapeDtypeStructs.  ``mesh_shape`` is a
    ``{axis_name: size}`` mapping (``dict(mesh.shape)``).  Findings carry
    the pytree path so the error names the offending leaf."""
    mesh_shape = dict(mesh_shape)
    flat_s, _ = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    flat_v, _ = jax.tree_util.tree_flatten_with_path(tree)
    vals = [(jax.tree_util.keystr(p), v) for p, v in flat_v]
    for pth, spec in flat_s:
        if not isinstance(spec, jax.sharding.PartitionSpec):
            continue
        key = jax.tree_util.keystr(pth)
        entries = _spec_entries(spec)
        spec_label = f"{where}{key}" if where else (key or "<root>")
        for axis in {a for e in entries for a in _axes_of_entry(e)}:
            if axis not in mesh_shape:
                report.add(
                    "shardspec.axis-unknown", R.ERROR,
                    f"{spec_label}: spec {spec} names mesh axis {axis!r} "
                    f"but the engine mesh has axes "
                    f"{sorted(mesh_shape)}",
                    path=spec_label, pass_name="shard-specs")
        # a spec pytree may be a PREFIX of the value pytree (one spec for
        # a whole subtree — valid shard_map in_specs): the spec applies
        # to EVERY value leaf under its path, so validate against all of
        # them, not just an exact path match
        leaves = [(kv, v) for kv, v in vals
                  if kv == key or kv.startswith(key)]
        for leaf_key, leaf in leaves:
            shape = getattr(leaf, "shape", None)
            if shape is None:
                continue
            label = f"{where}{leaf_key}" if where else (leaf_key or "<leaf>")
            if len(entries) > len(shape):
                report.add(
                    "shardspec.rank", R.ERROR,
                    f"{label}: spec {spec} has {len(entries)} entries but "
                    f"the value has rank {len(shape)} "
                    f"(shape {tuple(shape)})",
                    path=label, pass_name="shard-specs")
                continue
            for dim, entry in enumerate(entries):
                axes = [a for a in _axes_of_entry(entry) if a in mesh_shape]
                if not axes:
                    continue
                total = 1
                for a in axes:
                    total *= int(mesh_shape[a])
                if total > 0 and shape[dim] % total != 0:
                    report.add(
                        "shardspec.indivisible", R.ERROR,
                        f"{label}: dim {dim} of shape {tuple(shape)} is "
                        f"sharded over axis "
                        f"{entry!r} (size {total}) by spec {spec}, but "
                        f"{shape[dim]} % {total} != 0 — shard_map would "
                        f"fail or silently pad.  Fix the batch/param "
                        f"shape or the spec",
                        path=label, pass_name="shard-specs")
