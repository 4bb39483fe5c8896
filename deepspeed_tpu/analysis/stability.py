"""Compile-stability pass — statically pin WHICH executables a run builds.

jax keys its compiled-executable cache on the abstract signature of every
call: argument pytree structure, per-leaf aval (shape/dtype/weak_type),
the sharding of committed arguments, the donation mask, and static
arguments.  Anything that silently forks that key pays a full XLA
recompile mid-run — minutes on a pod slice, and on the preemption path a
recompile the persistent cache can never serve.  The repo's two most
expensive recent bugs were exactly this class:

* **PR 5**: restore rebuilt ``opt_state.step`` with a bare
  ``jnp.asarray`` — an unpinned scalar where the engine's own path
  carries a committed replicated NamedSharding — so the boundary program
  re-lowered to a DIFFERENT executable on EVERY resume.
* **PR 10**: executables deserialized from the persistent compile cache
  with DONATED buffers compute garbage on quirk-listed backends
  (jax 0.4.x XLA-CPU) — bitwise-restored state stepped to NaN.

This pass makes both classes (and the shape-varying-call-site class that
would break the inference engine's "exactly N executables" promise)
build-time findings instead of incidents:

``stability.unpinned-sharding``   (error)  an engine state leaf whose
    placement is uncommitted or not equivalent to the engine's declared
    sharding — the next call forks the executable key (the PR 5 class).
``stability.shape-varying``       (error)  call-site signatures for one
    program kind diverge (shape/dtype/structure), so one logical program
    compiles several executables — defeats the single-executable
    contract (and the serving engine's exactly-N promise).
``stability.donation-cache-quirk`` (error) donated buffers + persistent
    compile cache on a backend whose profile declares
    ``persistent_cache_donation_unsafe`` (the PR 10 class).
``stability.weak-input``          (warning) a weak-typed call argument —
    the key forks when its dtype promotes (Python scalars in carried
    state).

Verification contract (tests/test_dispatch_stability.py): over an N-step
run, :func:`predict_executables`'s total equals the measured
``compile_cache_misses`` delta, for the training engine (fused AND split
API) and the inference engine (prefill + decode across prompt lengths).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax

from deepspeed_tpu.analysis import profiles as prof_mod
from deepspeed_tpu.analysis import report as R

#: env escape hatch: keep donation even when the persistent cache is
#: enabled on a quirk-listed backend (reproducing the PR 10 failure, or
#: overriding a wrongly-listed profile).  The stability pass then flags
#: the combination as ``stability.donation-cache-quirk``.
FORCE_DONATE_ENV = "DSTPU_FORCE_DONATE"


# ---------------------------------------------------------------- signatures

def _sharding_desc(leaf) -> str:
    s = getattr(leaf, "sharding", None)
    if s is None:
        return "<host>"
    spec = getattr(s, "spec", None)
    if spec is not None:
        return f"NamedSharding({spec})"
    return type(s).__name__


@dataclasses.dataclass(frozen=True)
class LeafSig:
    """Cache-key-relevant facts of one call-argument leaf."""

    path: str
    shape: Tuple[int, ...]
    dtype: str
    weak_type: bool
    sharding: str
    committed: bool

    def key(self) -> Tuple:
        return (self.shape, self.dtype, self.weak_type, self.sharding,
                self.committed)


@dataclasses.dataclass
class ProgramSignature:
    """The abstract signature jax keys one program's executable cache on:
    argument structure + per-leaf avals/shardings + the donation mask.
    Two calls with unequal signatures compile two executables."""

    kind: str
    treedef: str
    leaves: Tuple[LeafSig, ...]
    donation: Tuple[int, ...] = ()

    def key(self) -> Tuple:
        return (self.treedef, tuple(l.key() for l in self.leaves),
                self.donation)

    def diff(self, other: "ProgramSignature") -> List[str]:
        """Leaf-path-bearing description of every divergence between two
        signatures (empty = same executable)."""
        out: List[str] = []
        if self.treedef != other.treedef:
            out.append("argument pytree structure differs")
        if self.donation != other.donation:
            out.append(f"donation mask {self.donation} vs {other.donation}")
        a = {l.path: l for l in self.leaves}
        b = {l.path: l for l in other.leaves}
        for path in list(a) + [p for p in b if p not in a]:
            la, lb = a.get(path), b.get(path)
            if la is None or lb is None:
                out.append(f"{path}: present in one signature only")
            elif la.key() != lb.key():
                bits = []
                if (la.shape, la.dtype) != (lb.shape, lb.dtype):
                    bits.append(f"{la.dtype}{list(la.shape)} vs "
                                f"{lb.dtype}{list(lb.shape)}")
                if la.sharding != lb.sharding or \
                        la.committed != lb.committed:
                    bits.append(f"sharding {la.sharding}"
                                f"{'' if la.committed else ' (uncommitted)'}"
                                f" vs {lb.sharding}"
                                f"{'' if lb.committed else ' (uncommitted)'}")
                if la.weak_type != lb.weak_type:
                    bits.append(f"weak_type {la.weak_type} vs "
                                f"{lb.weak_type}")
                out.append(f"{path}: " + "; ".join(bits))
        return out


def signature_of(args, kind: str = "", donate_argnums: Sequence[int] = (),
                 arg_labels: Optional[Sequence[str]] = None
                 ) -> ProgramSignature:
    """Abstract signature of calling a program with ``args`` (a tuple of
    pytrees — concrete arrays, numpy arrays or ShapeDtypeStructs)."""
    leaves: List[LeafSig] = []
    treedefs = []
    for pos, a in enumerate(args):
        head = (arg_labels[pos] if arg_labels and pos < len(arg_labels)
                else f"arg{pos}")
        treedefs.append(str(jax.tree_util.tree_structure(a)))
        for p, leaf in jax.tree_util.tree_flatten_with_path(a)[0]:
            aval = getattr(leaf, "aval", leaf)
            leaves.append(LeafSig(
                path=f"{head}{jax.tree_util.keystr(p)}",
                shape=tuple(getattr(leaf, "shape", ())),
                dtype=str(getattr(leaf, "dtype",
                                  type(leaf).__name__)),
                weak_type=bool(getattr(aval, "weak_type", False)),
                sharding=_sharding_desc(leaf),
                committed=bool(getattr(leaf, "_committed", True)),
            ))
    return ProgramSignature(kind=kind, treedef="|".join(treedefs),
                            leaves=tuple(leaves),
                            donation=tuple(sorted(donate_argnums)))


def check_single_executable(kind: str, signatures: Sequence[ProgramSignature],
                            report: R.Report) -> None:
    """Every signature in ``signatures`` must hash to the SAME executable;
    a divergence is a ``stability.shape-varying`` error naming the leaf
    paths that fork the key (the serving engine's "exactly N
    executables" promise becomes this check across prompt lengths)."""
    if not signatures:
        return
    base = signatures[0]
    for sig in signatures[1:]:
        diff = base.diff(sig)
        if diff:
            report.add(
                "stability.shape-varying", R.ERROR,
                f"call sites of program '{kind}' produce DIFFERENT "
                f"executable-cache signatures — each distinct signature "
                f"compiles another executable, so the single-executable "
                f"contract (one compile per program kind) is broken and "
                f"steady-state steps pay recompiles.  Divergence: "
                + "; ".join(diff[:4])
                + ("; ..." if len(diff) > 4 else ""),
                path=kind, pass_name="stability")
            return


# ------------------------------------------------------- engine state checks

def _flatten_with_specs(tree, specs):
    """(path, leaf, spec) triples; ``specs`` may be a prefix tree (one
    spec for a whole subtree) — each value leaf takes the spec at the
    LONGEST matching path prefix.  PartitionSpec is a tuple subclass, so
    plain tree flattening would recurse INTO the specs; flatten with an
    explicit is_leaf instead (same wrinkle passes.check_shard_specs
    handles)."""
    is_p = lambda x: isinstance(x, jax.sharding.PartitionSpec)
    spec_flat = [(jax.tree_util.keystr(p), s) for p, s in
                 jax.tree_util.tree_flatten_with_path(
                     specs, is_leaf=is_p)[0]
                 if is_p(s)]
    out = []
    for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = jax.tree_util.keystr(p)
        best = None
        for sk, s in spec_flat:
            if (key == sk or sk == "" or key.startswith(sk)) and (
                    best is None or len(sk) > len(best[0])):
                best = (sk, s)
        if best is not None:
            out.append((key, leaf, best[1]))
    return out


def check_tree_shardings(mesh, tree, specs, label: str,
                         report: R.Report) -> None:
    """Flag every leaf of ``tree`` whose placement would fork the
    executable key against the engine's declared sharding ``specs``:
    committed to a non-equivalent sharding, or uncommitted on a
    multi-device mesh (empirically both re-lower — the PR 5 class)."""
    from jax.sharding import NamedSharding
    n_dev = len(mesh.devices.flat) if hasattr(mesh, "devices") else 1
    for path, leaf, spec in _flatten_with_specs(tree, specs):
        actual = getattr(leaf, "sharding", None)
        if actual is None:
            continue        # host value — staged fresh each call
        expected = NamedSharding(mesh, spec)
        ndim = getattr(leaf, "ndim", 0)
        try:
            equiv = actual.is_equivalent_to(expected, ndim)
        except Exception:   # pragma: no cover - jax version drift
            equiv = (actual == expected)
        committed = bool(getattr(leaf, "_committed", True))
        if equiv and (committed or n_dev <= 1):
            continue
        how = ("is UNCOMMITTED (placed by a bare jnp.asarray/np "
               "round-trip)" if not committed else
               f"is committed to {_sharding_desc(leaf)}")
        report.add(
            "stability.unpinned-sharding", R.ERROR,
            f"{label}{path} {how} but the engine's step programs were "
            f"lowered for NamedSharding({spec}) — the next call hashes a "
            f"DIFFERENT executable key and re-lowers the whole program "
            f"(the PR 5 resume-recompile class; a resume then pays a "
            f"recompile the persistent cache can never serve).  Pin the "
            f"leaf with checkpoint._put_global / jax.device_put to the "
            f"engine sharding",
            path=f"{label}{path}", pass_name="stability")


def check_donation_cache(donate_argnums: Sequence[int], report: R.Report,
                         subject: str = "",
                         arg_labels: Optional[Sequence[str]] = None,
                         profile: Optional[prof_mod.BackendProfile] = None
                         ) -> None:
    """The PR 10 class: donated buffers + a persistent compile cache on a
    backend whose profile declares deserialized donation unsafe — a
    cache-HIT step silently computes garbage.  The engine auto-skips
    donation for this combination; finding it here means the skip was
    overridden (``DSTPU_FORCE_DONATE=1``) or a caller hand-built the
    donation."""
    from deepspeed_tpu.utils import compile_cache

    if not donate_argnums or compile_cache.enabled_dir() is None:
        return
    if profile is None:
        profile = prof_mod.default_profile()
    if profile is None or not profile.persistent_cache_donation_unsafe:
        return
    names = [(arg_labels[i] if arg_labels and i < len(arg_labels)
              else f"arg{i}") for i in donate_argnums]
    report.add(
        "stability.donation-cache-quirk", R.ERROR,
        f"{subject or 'program'} donates {names} while the persistent "
        f"compile cache is enabled on backend profile '{profile.name}', "
        f"which declares persistent_cache_donation_unsafe: executables "
        f"DESERIALIZED from the cache lose donated-buffer aliasing and "
        f"compute garbage (the PR 10 resume incident — bitwise-restored "
        f"state stepped to NaN).  Disable donation (DSTPU_NO_DONATE=1, or "
        f"drop {FORCE_DONATE_ENV}) or the compile cache on this backend",
        path=subject, pass_name="stability")


def check_weak_inputs(args, report: R.Report, subject: str = "",
                      arg_labels: Optional[Sequence[str]] = None) -> None:
    """Weak-typed CALL arguments (Python scalars carried in state): the
    executable key forks when the leaf later arrives strong-typed."""
    sig = signature_of(args, kind=subject, arg_labels=arg_labels)
    for leaf in sig.leaves:
        if leaf.weak_type:
            report.add(
                "stability.weak-input", R.WARNING,
                f"{subject or 'program'} argument {leaf.path} is "
                f"weak-typed ({leaf.dtype}): passing a strong-typed "
                f"array (or a different Python type) later forks the "
                f"executable key and silently recompiles.  Stage it as "
                f"jnp.asarray with an explicit dtype",
                path=leaf.path, pass_name="stability")


# --------------------------------------------------- executable-count model

@dataclasses.dataclass
class ExecutablePrediction:
    """How many executables a run's program set compiles — the number the
    measured ``compile_cache_misses`` counter must match over a cold-cache
    run (and whose steady-state delta must be ZERO)."""

    subject: str
    #: (program kind, format label, executables) — the invariant is one
    #: executable per (kind, batch format)
    programs: List[Tuple[str, str, int]]

    @property
    def total(self) -> int:
        return sum(n for _, _, n in self.programs)

    def format_table(self) -> str:
        lines = [f"{'program':<14} {'format':<22} executables"]
        for kind, fmt, n in self.programs:
            lines.append(f"{kind:<14} {fmt:<22} {n}")
        lines.append(f"{'total':<14} {'':<22} {self.total}")
        return "\n".join(lines)

    def to_json(self) -> dict:
        return {"subject": self.subject, "total": self.total,
                "programs": [{"kind": k, "format": f, "executables": n}
                             for k, f, n in self.programs]}


def _format_label(i: int) -> str:
    return f"format{i}"


def predict_executables(engine, batches: Sequence, train: bool = True,
                        fused: bool = True,
                        steps_per_dispatch: Optional[int] = None
                        ) -> ExecutablePrediction:
    """Executable count the engine builds for ``batches`` (a sequence of
    example batches; distinct FORMATS — pytree structure + leaf
    shapes/dtypes — are deduped exactly like the engine's own program
    caches, the PR 1 fix made checkable).  Exactly ONE executable per
    (program kind, format); the split API adds the format-independent
    ``step`` program, and an active metric spool adds its drain (and, on
    the split API, append) program.  ``steps_per_dispatch`` > 1 models
    the K-fused driver: ``train_many`` replaces ``train_batch`` (still
    one executable per format — K is part of the program, not the
    format)."""
    if steps_per_dispatch is None:
        steps_per_dispatch = int(getattr(engine, "steps_per_dispatch", 1))
    keys = []
    for b in batches:
        b = tuple(b) if isinstance(b, (tuple, list)) else (b,)
        k = engine._batch_cache_key(b)
        if k not in keys:
            keys.append(k)
    programs: List[Tuple[str, str, int]] = []
    if train and fused:
        kind = ("train_many" if steps_per_dispatch > 1 else "train_batch")
        for i, _ in enumerate(keys):
            programs.append((kind, _format_label(i), 1))
    elif train:
        for i, _ in enumerate(keys):
            programs.append(("fwdbwd", _format_label(i), 1))
        programs.append(("step", "-", 1))
    else:
        for i, _ in enumerate(keys):
            programs.append(("eval", _format_label(i), 1))
    if train and getattr(engine, "_spool", None) is not None:
        if not fused:
            # split-API append: one tiny jitted program per boundary,
            # compiled once (the fused path folds it into train_batch)
            programs.append(("spool_append", "-", 1))
        programs.append(("spool_drain", "-", 1))
    return ExecutablePrediction(
        subject="train" if train else "eval", programs=programs)


def predict_executables_serve(engine) -> ExecutablePrediction:
    """The inference engine's promise, as a number: a STATICALLY
    ENUMERATED executable set over the continuous-greedy serving path,
    regardless of prompt lengths, request counts or scheduler decisions:

    * ``prefill`` — one per admission bucket: the full bucket, plus the
      narrow ``prefill_tail`` bucket when prefix reuse is on (a hit's
      tail re-forward, docs/inference.md "Prefix reuse");
    * the decode program — ``decode``, or the D-fused ``decode_many``
      (``inference.decode_iters_per_dispatch`` > 1), or — with a draft
      model — ``draft_prefill`` + the fused ``spec_step`` (the J-draft +
      verify dispatch; the per-iteration ``decode`` then only compiles
      for the static baseline / custom-sampler fallback);
    * with ``inference.fleet.disaggregate``, the KV handoff pair —
      ``export_kv`` + ``import_kv`` (one shape-stable executable each,
      regardless of prompt length or reuse offset).
    The ring-layout ``copy_page`` program is deliberately NOT counted:
    it compiles only if a wrap-around ever collides with a shared page —
    an exceptional path, priced by the dispatch plan's note instead of
    the steady-state executable promise."""
    programs = [("prefill", "bucket", 1)]
    if int(getattr(engine, "tail_bucket", 0) or 0) > 0:
        programs.append(("prefill_tail", "tail bucket", 1))
    j = int(getattr(engine, "spec_draft_tokens", 0) or 0)
    if j > 0:
        programs.append(("draft_prefill", "bucket", 1))
        programs.append(("spec_step", f"J={j}", 1))
    elif int(getattr(engine, "decode_iters_per_dispatch", 1)) > 1:
        programs.append(("decode_many", "slots", 1))
    else:
        programs.append(("decode", "slots", 1))
    if bool(getattr(engine, "fleet_disaggregate", False)):
        programs.append(("export_kv", "capacity", 1))
        programs.append(("import_kv", "capacity", 1))
    return ExecutablePrediction(subject="serve", programs=programs)


# ----------------------------------------------------------- engine surface

_STEP_LABELS = ("master", "opt_state", "grads", "loss_scale", "hypers",
                "zero_norm_w", "zero_gid")


def check_engine(engine, batch, fused: bool = True,
                 train: bool = True) -> R.Report:
    """The build-time stability report for one training-engine program
    family: state-sharding pins, weak-typed call args, and the
    donation × persistent-cache quirk.  ``train=False`` checks the eval
    surface (params pin + batch weak types) only."""
    rep = R.Report(subject="stability")
    batch = tuple(batch) if isinstance(batch, (tuple, list)) else (batch,)

    check_tree_shardings(engine.mesh, engine.params, engine._param_specs,
                         "params", rep)
    if not train:
        check_weak_inputs((engine.params, batch), rep, subject="eval",
                          arg_labels=("params", "batch"))
        return rep

    master_spec, opt_spec, ls_spec = engine._step_specs()
    if engine.zero_flat:
        check_tree_shardings(engine.mesh, engine.master_flat, master_spec,
                             "master_flat", rep)
    else:
        check_tree_shardings(engine.mesh, engine.master, master_spec,
                             "master", rep)
    check_tree_shardings(engine.mesh, engine.opt_state, opt_spec,
                         "opt_state", rep)
    check_tree_shardings(engine.mesh, engine.loss_scale_state, ls_spec,
                         "loss_scale_state", rep)
    spool = getattr(engine, "_spool", None)
    if spool is not None:
        # the ring state is a fused-program argument: unpinned at build
        # it forks the first call's key against every later call's
        from jax.sharding import PartitionSpec
        specs = jax.tree_util.tree_map(lambda _: PartitionSpec(),
                                       spool.state)
        check_tree_shardings(engine.mesh, spool.state, specs, "spool",
                             rep)
    channel = getattr(engine, "_scalars", None)
    if channel is not None:
        # so are the step scalars' totals (zeros after every read)
        from jax.sharding import PartitionSpec
        specs = jax.tree_util.tree_map(lambda _: PartitionSpec(),
                                       channel.device)
        check_tree_shardings(engine.mesh, channel.device, specs,
                             "step_scalars", rep)

    from deepspeed_tpu import analysis
    from deepspeed_tpu.analysis import memplan
    if fused:
        args = analysis.train_batch_args(engine, batch)
        # the fused call protocol's labels have one owner
        labels = memplan._TRAIN_BATCH_LABELS + memplan._tail_labels(engine)
        subject = "train_batch"
    else:
        _, grad_shapes = jax.eval_shape(
            engine._ensure_fwdbwd(batch), engine.params,
            engine.loss_scale_state.cur_scale, batch)
        args = analysis.step_args(engine, grad_shapes)
        labels = _STEP_LABELS
        subject = "step"
    check_weak_inputs(args, rep, subject=subject, arg_labels=labels)
    check_donation_cache(engine._donate_argnums(fused=fused), rep,
                         subject=subject, arg_labels=labels)
    return rep


def check_inference_engine(engine,
                           prompt_lengths: Sequence[int] = ()) -> R.Report:
    """The serving stability report: the exactly-N-executables promise
    checked as an invariant — each admission bucket's CALL-path
    signature must be identical for every admissible prompt length AND
    every reuse start offset (the host-side bucket padding, not the
    compiler, absorbs the variation: full prefill is ``start=0``, a
    prefix-hit tail is ``start=reused`` — same executable) — plus
    sharding pins on weights/caches (draft included) and the donation
    quirk."""
    import numpy as np

    rep = R.Report(subject="serve-stability")
    check_tree_shardings(engine.mesh, engine.params, engine._param_specs,
                         "params", rep)
    check_tree_shardings(engine.mesh, engine._cache, engine._cache_specs,
                         "kv_cache", rep)
    if getattr(engine, "draft_params", None) is not None:
        check_tree_shardings(engine.mesh, engine.draft_params,
                             engine._draft_specs, "draft_params", rep)
        check_tree_shardings(engine.mesh, engine._draft_cache,
                             engine._cache_specs, "draft_kv_cache", rep)

    donate = engine._donate_argnums("prefill")
    buckets = [("prefill", engine.prefill_bucket)]
    if getattr(engine, "tail_bucket", 0):
        buckets.append(("prefill_tail", engine.tail_bucket))
    labels = ("params", "k", "v", "pos", "tokens", "rows", "slot",
              "start", "n_new")
    cap = engine.cache_spec.capacity
    for kind, bucket in buckets:
        lengths = list(prompt_lengths) or sorted(
            {1, max(1, bucket // 2), bucket})
        sigs = []
        for i, n in enumerate(lengths):
            padded, length = engine._pad_prompt(
                list(range(max(1, min(n, bucket)))), bucket)
            # the reuse start offset varies call to call, exactly like
            # the length — both must be invisible to the compiler
            start = np.int32((i * 7) % max(1, cap - bucket + 1))
            args = (engine.params, engine._cache["k"],
                    engine._cache["v"], engine._cache["pos"], padded,
                    np.zeros((1, cap), np.int32), np.int32(0), start,
                    length)
            sigs.append(signature_of(
                args, kind=kind, donate_argnums=donate,
                arg_labels=labels))
        check_single_executable(kind, sigs, rep)
    check_donation_cache(donate, rep, subject="prefill/decode",
                         arg_labels=("params", "k", "v", "pos"))
    return rep
