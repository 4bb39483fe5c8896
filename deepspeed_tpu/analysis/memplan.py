"""Capacity planner, memory half: static per-device peak-HBM prediction.

ZeRO's whole pitch is memory *arithmetic* — optimizer states 12/dp bytes
per parameter, grads 4/dp under stage 2, params 2/dp under stage 3 — yet
until this pass the repo only learned whether a config fits by compiling
and OOMing.  This module turns the question into a static query: walk the
traced step program (the same jaxprs graph-lint already covers, with the
per-device *local* shapes the ``shard_map`` body carries) and simulate
XLA's buffer assignment well enough to predict the per-device peak.

The walk (:func:`peak_of`) is a liveness simulation over one jaxpr level:

* every equation's outputs allocate; buffers free after their last use;
* ``reshape``/``transpose``-style ops alias (XLA bitcasts them);
* elementwise ops reuse a dying same-size input buffer (XLA fuses the
  chain and writes in place);
* ``scan`` carries update in place (XLA aliases while-loop state) and the
  stacked ``ys`` — the *scan residuals*, including everything remat
  decides to save — allocate up front for the whole trip count, so remat
  on/off changes the prediction exactly the way it changes the program;
* call-like primitives (``jit``/``remat2``/``cond``/custom-vjp) peak at
  ``max(outer live + inner peak, outer live + own outputs)`` — inner
  scratch and the call's results never coexist;
* jaxpr outputs matching a *donated* input's shape/dtype are free (XLA
  input/output aliasing — the engine donates master/opt-state/loss-scale
  into every step);
* on CPU only (``profile.lowp_dot_f32_copies``): each fp16/bf16 dot
  operand/result charges a transient fp32 copy — the host has no native
  half GEMM.  TPU predictions must not carry this.

Accuracy contract: tests/test_memplan.py pins the prediction against
``compiled.memory_analysis()`` across ZeRO stages 0-3 x remat on/off x
MP/PP at +-10% (with a small absolute floor for toy-scale
buffer-assignment noise).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.analysis import graph as G
from deepspeed_tpu.analysis import profiles as prof_mod
from deepspeed_tpu.analysis import report as R

# --------------------------------------------------------------- primitives

#: pure layout changes XLA lowers to bitcasts / fuses into the consumer
ALIAS_PRIMS = frozenset({
    "reshape", "squeeze", "expand_dims", "bitcast_convert_type", "copy",
    "stop_gradient", "transpose", "rev",
})

#: elementwise ops XLA fuses and computes in place over a dying operand
ELEMENTWISE_PRIMS = frozenset({
    "add", "sub", "mul", "div", "max", "min", "rem", "pow", "atan2", "and",
    "or", "xor", "not", "neg", "sign", "floor", "ceil", "round", "exp",
    "log", "log1p", "expm1", "tanh", "logistic", "erf", "erf_inv", "erfc",
    "sqrt", "rsqrt", "cbrt", "integer_pow", "abs", "cos", "sin", "tan",
    "convert_element_type", "select_n", "clamp", "nextafter", "is_finite",
    "eq", "ne", "ge", "gt", "le", "lt", "add_any", "square",
})

#: sub-jaxpr carriers whose scratch and outputs never coexist
CALL_PRIMS = frozenset({
    "jit", "remat2", "remat", "custom_vjp_call_jaxpr", "custom_jvp_call",
    "custom_vjp_call", "closed_call", "core_call", "xla_call", "cond",
    "switch", "while",
})

DOT_PRIMS = frozenset({"dot_general", "conv_general_dilated"})

#: contributors kept per peak snapshot (the error message's top-N)
_TOP_K = 12


def nbytes(aval) -> int:
    """Buffer bytes of one abstract value (bools are byte-wide in XLA)."""
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    n = 1
    for d in shape:
        try:
            n *= int(d)
        except Exception:       # symbolic dims: refuse to guess small
            return 1 << 62
    dt = getattr(aval, "dtype", None)
    if dt is None:
        return 4 * n
    return n * max(1, np.dtype(dt).itemsize)


def _is_lowp(aval) -> bool:
    dt = getattr(aval, "dtype", None)
    return dt is not None and str(dt) in ("float16", "bfloat16")


@dataclasses.dataclass
class Contributor:
    """One buffer alive at the predicted peak."""

    bytes: int
    label: str                  # producing primitive, or the argument leaf path
    shape: Tuple[int, ...]
    dtype: str
    path: str = ""              # jaxpr path ("scan/remat2")
    source: str = ""            # "file:line (function)" when jax recorded one

    def format(self) -> str:
        loc = self.source or self.path or ""
        where = f"  @ {loc}" if loc else ""
        return (f"{self.bytes / 2**20:8.2f} MiB  {self.label:24s} "
                f"{self.dtype}{list(self.shape)}{where}")


@dataclasses.dataclass
class ProgramPlan:
    """Predicted per-device memory envelope of one step program."""

    subject: str
    argument_bytes: int         # persistent inputs (params/master/opt/batch)
    peak_bytes: int             # predicted per-device peak HBM
    contributors: List[Contributor]

    @property
    def transient_bytes(self) -> int:
        return max(0, self.peak_bytes - self.argument_bytes)

    def top_contributors(self, k: int = 5) -> List[Contributor]:
        return sorted(self.contributors, key=lambda c: -c.bytes)[:k]


def _peak_of(jaxpr, donated=None, lowp_dot_copies: bool = False,
             path: str = "") -> Tuple[int, List[Contributor]]:
    """Liveness walk over one (open or closed) jaxpr level.

    Returns ``(peak_extra_bytes, contributors)``: the peak of allocations
    this level makes beyond its own invars (the caller owns those), and
    the owned buffers alive at that peak (flattened through the inner
    level the peak passed through)."""
    j = G._as_open_jaxpr(jaxpr)
    if j is None:
        return 0, []

    last = {}
    for i, eqn in enumerate(j.eqns):
        for v in eqn.invars:
            if G.is_var(v):
                last[v] = i
    n_eqns = len(j.eqns)
    for v in j.outvars:
        if G.is_var(v):
            last[v] = n_eqns

    # donation pool: outvars may land in a dying donated-argument buffer,
    # matched by (shape, dtype) multiset exactly like XLA's aliasing
    donate_pool: dict = {}
    for v in donated or ():
        key = (tuple(getattr(v.aval, "shape", ())),
               str(getattr(v.aval, "dtype", "")))
        donate_pool[key] = donate_pool.get(key, 0) + 1

    alive: dict = {}            # var -> owned bytes (0 = alias/reused view)
    meta: dict = {}             # var -> (label, source)
    cur = 0
    peak = 0
    peak_snapshot: List[Contributor] = []

    def snapshot(inner_contribs: List[Contributor]) -> List[Contributor]:
        own = [Contributor(bytes=b, label=meta.get(v, ("?", ""))[0],
                           shape=tuple(getattr(v.aval, "shape", ())),
                           dtype=str(getattr(v.aval, "dtype", "")),
                           path=path, source=meta.get(v, ("?", ""))[1])
               for v, b in alive.items() if b > 0]
        own.sort(key=lambda c: -c.bytes)
        return (own + inner_contribs)[:_TOP_K]

    for i, eqn in enumerate(j.eqns):
        name = eqn.primitive.name
        subs = G.subjaxprs(eqn)
        inner = 0
        inner_contribs: List[Contributor] = []
        for label, sub in subs:
            sub_path = f"{path}/{label}" if path else label
            p, c = _peak_of(sub, lowp_dot_copies=lowp_dot_copies,
                            path=sub_path)
            if p > inner:
                inner, inner_contribs = p, c

        dying = [iv for iv in eqn.invars if G.is_var(iv)
                 and last.get(iv) == i and alive.get(iv, 0) > 0]
        out_assign: dict = {}
        new_alloc = 0

        def place(v, allow_reuse: bool) -> None:
            """Assign an output buffer: donated-alias > in-place reuse >
            fresh allocation."""
            nonlocal new_alloc
            need = nbytes(v.aval)
            if G.is_var(v) and last.get(v) == n_eqns:
                key = (tuple(getattr(v.aval, "shape", ())),
                       str(getattr(v.aval, "dtype", "")))
                if donate_pool.get(key, 0) > 0:
                    donate_pool[key] -= 1
                    out_assign[v] = 0
                    return
            if allow_reuse:
                for iv in dying:
                    if alive.get(iv, 0) >= need:
                        dying.remove(iv)
                        out_assign[v] = alive[iv]
                        alive[iv] = 0       # ownership transferred
                        return
            out_assign[v] = need
            new_alloc += need

        # CPU fp32-GEMM quirk: half-precision dot operands/results charge
        # a transient fp32 copy at the dot (2x their half-width bytes)
        extra_during = 0
        if lowp_dot_copies and name in DOT_PRIMS:
            seen = set()
            for iv in eqn.invars:
                if _is_lowp(getattr(iv, "aval", None)) and id(iv) not in seen:
                    seen.add(id(iv))
                    extra_during += 2 * nbytes(iv.aval)
            for ov in eqn.outvars:
                if _is_lowp(ov.aval):
                    extra_during += 2 * nbytes(ov.aval)

        if name in ALIAS_PRIMS:
            # the view shares the source's storage: if the source var
            # dies HERE, ownership moves to the view (its bytes stay
            # live until the view's own last use), otherwise the view
            # owns nothing — freeing the source while the reshape lives
            # would underpredict the peak
            alias_src = next(
                (iv for iv in eqn.invars if G.is_var(iv)), None)
            for v in eqn.outvars:
                if (alias_src is not None
                        and last.get(alias_src) == i
                        and alive.get(alias_src, 0) > 0):
                    out_assign[v] = alive[alias_src]
                    alive[alias_src] = 0    # ownership transferred
                    alias_src = None
                else:
                    out_assign[v] = 0
            during = cur
        elif name == "scan":
            num_carry = int(eqn.params.get("num_carry", 0))
            for k, v in enumerate(eqn.outvars):
                place(v, allow_reuse=(k < num_carry))
            during = cur + new_alloc + inner
        elif name in CALL_PRIMS:
            for v in eqn.outvars:
                place(v, allow_reuse=False)
            during = max(cur + inner, cur + new_alloc)
        elif name in ELEMENTWISE_PRIMS:
            for v in eqn.outvars:
                place(v, allow_reuse=True)
            during = cur + new_alloc
        else:
            for v in eqn.outvars:
                place(v, allow_reuse=False)
            during = cur + new_alloc + inner + extra_during

        cur += new_alloc
        src = G.source_of(eqn)
        for v in out_assign:
            meta[v] = (name, src)
        high = max(during, cur)
        if high > peak:
            peak = high
            alive.update(out_assign)
            peak_snapshot = snapshot(inner_contribs if during >= cur else [])
        else:
            alive.update(out_assign)
        for v in list(alive):
            if last.get(v, -1) <= i:
                cur -= alive.pop(v)

    return peak, peak_snapshot


def _find_shard_map_body(closed_jaxpr):
    """The shard_map body jaxpr of an engine program — the level whose
    shapes are already per-device.  None for plain (unsharded) programs."""
    for eqn, _ in G.walk(closed_jaxpr):
        if eqn.primitive.name == "shard_map":
            subs = G.subjaxprs(eqn)
            if subs:
                return subs[0][1]
    return None


def analyze_program(fn, args, donate_argnums: Sequence[int] = (),
                    arg_labels=None, subject: str = "",
                    profile: Optional[prof_mod.BackendProfile] = None,
                    closed=None) -> ProgramPlan:
    """Predict the per-device peak HBM of ``fn(*args)``.

    ``args`` are example values/ShapeDtypeStructs (never executed — the
    program is traced abstractly).  ``donate_argnums`` must match the
    jit-level donation so output aliasing is modeled.  ``arg_labels``
    (optional, same length as ``args``) names argument groups so peak
    contributors carry engine leaf paths instead of "arg 3".  ``closed``
    accepts a pre-traced ``jax.make_jaxpr(fn)(*args)`` so one trace can
    feed both planner halves."""
    if profile is None:
        profile = prof_mod.default_profile()
    quirk = bool(profile is not None and profile.lowp_dot_f32_copies)

    if closed is None:
        closed = jax.make_jaxpr(fn)(*args)
    body = _find_shard_map_body(closed) or G._as_open_jaxpr(closed)

    # map flat argument positions to body invars (tree-flatten order is
    # the shard_map calling convention)
    leaf_counts = [len(jax.tree_util.tree_leaves(a)) for a in args]
    labels: List[str] = []
    for pos, a in enumerate(args):
        head = (arg_labels[pos] if arg_labels and pos < len(arg_labels)
                else f"arg{pos}")
        flat = jax.tree_util.tree_flatten_with_path(a)[0]
        if len(flat) == 1:
            labels.append(str(head))
        else:
            labels.extend(f"{head}{jax.tree_util.keystr(p)}"
                          for p, _ in flat)
    invars = list(body.invars)
    donated = []
    off = 0
    for pos, n in enumerate(leaf_counts):
        if pos in set(donate_argnums):
            donated.extend(invars[off:off + n])
        off += n

    arg_bytes = sum(nbytes(v.aval) for v in invars)
    extra, contribs = _peak_of(body, donated=donated,
                               lowp_dot_copies=quirk)

    # argument leaves are live for the whole program: they are peak
    # contributors too, named by their engine leaf path
    arg_contribs = [
        Contributor(bytes=nbytes(v.aval),
                    label=(labels[k] if k < len(labels) else f"arg{k}"),
                    shape=tuple(getattr(v.aval, "shape", ())),
                    dtype=str(getattr(v.aval, "dtype", "")),
                    path="<argument>")
        for k, v in enumerate(invars)]
    merged = sorted(arg_contribs + contribs, key=lambda c: -c.bytes)[:_TOP_K]
    return ProgramPlan(subject=subject, argument_bytes=arg_bytes,
                       peak_bytes=arg_bytes + extra, contributors=merged)


# ----------------------------------------------------------- engine surface

@dataclasses.dataclass
class CapacityPlan:
    """Fit verdict of one engine + batch format against a profile."""

    programs: List[ProgramPlan]
    persistent: dict                        # engine.memory_estimate()
    profile: Optional[prof_mod.BackendProfile]
    budget_bytes: Optional[int]
    comm: Optional[object] = None           # whole-step commplan.CommPlan
    boundary_comm: Optional[object] = None  # step-program-only CommPlan

    @property
    def peak_bytes(self) -> int:
        return max((p.peak_bytes for p in self.programs), default=0)

    @property
    def peak_program(self) -> Optional[ProgramPlan]:
        return max(self.programs, key=lambda p: p.peak_bytes, default=None)

    def fits(self) -> Optional[bool]:
        if self.budget_bytes is None:
            return None
        return self.peak_bytes <= self.budget_bytes

    def headroom_bytes(self) -> Optional[int]:
        if self.budget_bytes is None:
            return None
        return self.budget_bytes - self.peak_bytes

    # -------------------------------------------------------------- report

    def to_report(self, subject: str = "capacity plan") -> R.Report:
        """Findings under the ``memory.*`` rule family (same severity /
        suppression machinery as graph lint — docs/analysis.md)."""
        rep = R.Report(subject=subject)
        peak = self.peak_bytes
        prog = self.peak_program
        where = prog.subject if prog else "<no program>"
        if self.comm is not None:
            # the comm.* family's one (info) rule so far: the wire
            # roll-up, suppressible like any other code
            rep.add("comm.wire", R.INFO, self.comm.format_summary(),
                    path=self.comm.subject, pass_name="commplan")
        if self.budget_bytes is None:
            rep.add(
                "memory.no-budget", R.INFO,
                f"predicted per-device peak HBM is "
                f"{_fmt_bytes(peak)} ({where}); no memory budget "
                f"configured — set analysis.memory_budget_gb or a "
                f"--profile to gate it",
                pass_name="memplan")
            return rep
        budget = self.budget_bytes
        if peak > budget:
            tops = "\n".join(
                "            " + c.format()
                for c in (prog.top_contributors(5) if prog else []))
            rep.add(
                "memory.budget-exceeded", R.ERROR,
                f"predicted per-device peak HBM {_fmt_bytes(peak)} "
                f"exceeds the budget {_fmt_bytes(budget)}"
                + (f" (profile {self.profile.name})" if self.profile
                   else "")
                + f" in program '{where}'.  Top live-set contributors:\n"
                + tops,
                path=where, pass_name="memplan")
        elif peak > 0.9 * budget:
            rep.add(
                "memory.budget", R.WARNING,
                f"predicted per-device peak HBM {_fmt_bytes(peak)} is "
                f"within 10% of the {_fmt_bytes(budget)} budget "
                f"({where}); one batch-size or remat change from OOM",
                path=where, pass_name="memplan")
        else:
            rep.add(
                "memory.fit", R.INFO,
                f"predicted per-device peak HBM {_fmt_bytes(peak)} "
                f"fits the {_fmt_bytes(budget)} budget "
                f"(headroom {_fmt_bytes(self.headroom_bytes())})",
                path=where, pass_name="memplan")
        return rep

    # ---------------------------------------------------------- fit table

    def format_table(self) -> str:
        lines = []
        name = self.profile.name if self.profile else "<none>"
        budget = (f"{self.budget_bytes / 2**30:.3f} GiB"
                  if self.budget_bytes is not None else "unset")
        lines.append(f"profile {name}  budget {budget}")
        lines.append(f"{'program':<14} {'args':>12} {'transient':>12} "
                     f"{'peak':>12}  fit")
        for p in self.programs:
            fit = "-"
            if self.budget_bytes is not None:
                fit = "OK" if p.peak_bytes <= self.budget_bytes else "OVER"
            lines.append(
                f"{p.subject:<14} {p.argument_bytes / 2**20:>10.2f}Mi "
                f"{p.transient_bytes / 2**20:>10.2f}Mi "
                f"{p.peak_bytes / 2**20:>10.2f}Mi  {fit}")
        pers = self.persistent
        if pers:
            lines.append(
                "persistent: params "
                f"{pers['params_bytes'] / 2**20:.2f}Mi + optimizer "
                f"{pers['optimizer_state_bytes'] / 2**20:.2f}Mi + grad-acc "
                f"{pers['grad_accumulator_bytes'] / 2**20:.2f}Mi "
                f"(zero_stage={pers['zero_stage']})")
            if "kv_cache_bytes" in pers:
                # serving plans (inference/engine.py) carry the
                # preallocated KV page pool as a persistent line item
                lines.append(
                    f"kv cache: {pers['kv_cache_bytes'] / 2**20:.2f}Mi "
                    f"preallocated (page pool)")
            if "draft_params_bytes" in pers:
                # speculative decoding: the draft model's weights and
                # its (plain, unshared) KV pool ride the budget too
                lines.append(
                    f"draft: params "
                    f"{pers['draft_params_bytes'] / 2**20:.2f}Mi + "
                    f"kv cache "
                    f"{pers.get('draft_kv_cache_bytes', 0) / 2**20:.2f}Mi")
        if self.comm is not None:
            lines.append(self.comm.format_summary())
        return "\n".join(lines)

    def to_json(self) -> dict:
        out = {
            "profile": self.profile.name if self.profile else None,
            "budget_bytes": self.budget_bytes,
            "peak_bytes": self.peak_bytes,
            "fits": self.fits(),
            "persistent": dict(self.persistent),
            "programs": [{
                "subject": p.subject,
                "argument_bytes": p.argument_bytes,
                "transient_bytes": p.transient_bytes,
                "peak_bytes": p.peak_bytes,
                "top_contributors": [{
                    "bytes": c.bytes, "label": c.label,
                    "shape": list(c.shape), "dtype": c.dtype,
                    "path": c.path, "source": c.source,
                } for c in p.top_contributors(5)],
            } for p in self.programs],
        }
        if self.comm is not None:
            out["comm"] = self.comm.to_json()
        if self.boundary_comm is not None:
            out["boundary_comm"] = self.boundary_comm.to_json()
        return out


def _fmt_bytes(n: int) -> str:
    """GiB at real scale, MiB below 0.01 GiB — '0.000 GiB exceeds the
    budget 0.000 GiB' helps nobody at toy scale."""
    if abs(n) >= int(0.01 * 2**30):
        return f"{n / 2**30:.3f} GiB"
    return f"{n / 2**20:.3f} MiB"


def _engine_train_batch_args(engine, batch):
    # the protocol owner lives in the package __init__ (PR 3: callers
    # must not hand-marshal the tuple); lazy import avoids the cycle
    from deepspeed_tpu import analysis
    return analysis.train_batch_args(engine, batch)


def _engine_step_args(engine, grads):
    from deepspeed_tpu import analysis
    return analysis.step_args(engine, grads)


#: argument labels of the fused call protocol (analysis.train_batch_args).
#: The optional tail (step-scalar totals, metric-spool state) comes LAST —
#: argument offsets 0..7 stay aligned with the shard_map body invars
#: whether or not it is there (the spool append runs OUTSIDE the
#: shard_map, at the jit level).
_TRAIN_BATCH_LABELS = ("params", "master", "opt_state", "loss_scale",
                       "hypers", "zero_norm_w", "zero_gid", "batch")

#: K-fused call protocol (analysis.train_many_args): the hyper slot is
#: the [K, 4, G] block, "live" the cond predicate input, "batch" the
#: tuple of K per-step batch trees
_TRAIN_MANY_LABELS = ("params", "master", "opt_state", "loss_scale",
                      "hypers", "zero_norm_w", "zero_gid", "live",
                      "batch")


def _tail_labels(engine):
    """Labels of the optional trailing arguments, in the protocol's order."""
    from deepspeed_tpu import analysis
    return tuple(label for label, _ in analysis.fused_tail(engine))


def plan_engine(engine, batch, train: bool = True,
                profile: Optional[prof_mod.BackendProfile] = None,
                budget_bytes: Optional[int] = None, fused: bool = True,
                with_comm: bool = True,
                steps_per_dispatch: Optional[int] = None) -> CapacityPlan:
    """Full capacity plan for one engine + batch format.

    ``fused=True`` plans the fused ``train_batch`` program (the
    production step — fwd, bwd, boundary collectives AND the optimizer in
    one trace); ``fused=False`` plans the split-API pair (``fwdbwd`` per
    micro-batch + the ``step`` boundary program), whose step-only
    :class:`~.commplan.CommPlan` is the predicted *boundary* wire time.
    ``steps_per_dispatch`` (default: the engine's configured K) > 1
    plans the ACTUAL K-fused ``train_many`` program — which holds K full
    effective batches as simultaneous inputs, so pricing the single-step
    program would under-count ~(K-1) batch copies of residency and let
    an over-HBM K config through the error gate.  (Its CommPlan prices
    one DISPATCH = K optimizer steps.)
    ``budget_bytes=None`` = report-only (``memory.no-budget``); callers
    gating against a profile pass ``profile.hbm_bytes`` themselves (the
    engine/CLI do, for *explicitly chosen* profiles — the
    memory-model-quirk default below must never become a surprise
    budget).  Each program is traced abstractly exactly ONCE; both
    planner halves share the jaxpr."""
    from deepspeed_tpu.analysis import commplan

    batch = tuple(batch) if isinstance(batch, (tuple, list)) else (batch,)
    if profile is None:
        profile = prof_mod.default_profile()
    if steps_per_dispatch is None:
        steps_per_dispatch = int(getattr(engine, "steps_per_dispatch", 1))
    k = steps_per_dispatch if (train and fused) else 1
    mesh_shape = dict(engine.mesh.shape)
    multi_host = jax.process_count() > 1

    programs = []
    comm = None
    boundary_comm = None
    if train and fused and k > 1:
        from deepspeed_tpu import analysis as _analysis
        key = (k, engine._batch_cache_key(batch))
        fn = engine._cached_batch_fn(
            engine._train_many_fns, key,
            lambda: engine._build_train_many(batch, k))
        args = _analysis.train_many_args(
            engine, tuple(batch for _ in range(k)))
        donate = engine._donate_argnums(fused=True)
        closed = jax.make_jaxpr(fn)(*args)
        programs.append(analyze_program(
            fn, args, donate_argnums=donate,
            arg_labels=_TRAIN_MANY_LABELS + _tail_labels(engine),
            subject="train_many",
            profile=profile, closed=closed))
        if with_comm:
            comm = commplan.analyze_comm(
                closed, mesh_shape, profile=profile,
                subject="train_many", multi_host=multi_host)
    elif train and fused:
        key = engine._batch_cache_key(batch)
        fn = engine._cached_batch_fn(
            engine._train_batch_fns, key,
            lambda: engine._build_train_batch(batch))
        args = _engine_train_batch_args(engine, batch)
        donate = engine._donate_argnums(fused=True)
        closed = jax.make_jaxpr(fn)(*args)
        programs.append(analyze_program(
            fn, args, donate_argnums=donate,
            arg_labels=_TRAIN_BATCH_LABELS + _tail_labels(engine),
            subject="train_batch",
            profile=profile, closed=closed))
        if with_comm:
            comm = commplan.analyze_comm(
                closed, mesh_shape, profile=profile,
                subject="train_batch", multi_host=multi_host)
    elif train:
        # split API: fwdbwd over one micro-batch + the boundary step
        fwdbwd = engine._ensure_fwdbwd(batch)
        fb_args = (engine.params, engine.loss_scale_state.cur_scale, batch)
        fb_closed = jax.make_jaxpr(fwdbwd)(*fb_args)
        programs.append(analyze_program(
            fwdbwd, fb_args, arg_labels=("params", "loss_scale", "batch"),
            subject="fwdbwd", profile=profile, closed=fb_closed))
        _, grad_shapes = jax.eval_shape(fwdbwd, *fb_args)
        if engine._step_fn is None:
            engine._step_fn = engine._build_step()
        st_args = _engine_step_args(engine, grad_shapes)
        donate = engine._donate_argnums(fused=False)
        st_closed = jax.make_jaxpr(engine._step_fn)(*st_args)
        programs.append(analyze_program(
            engine._step_fn, st_args, donate_argnums=donate,
            arg_labels=("master", "opt_state", "grads", "loss_scale",
                        "hypers", "zero_norm_w", "zero_gid"),
            subject="step", profile=profile, closed=st_closed))
        if with_comm:
            fb_comm = commplan.analyze_comm(
                fb_closed, mesh_shape, profile=profile, subject="fwdbwd",
                multi_host=multi_host)
            boundary_comm = commplan.analyze_comm(
                st_closed, mesh_shape, profile=profile, subject="step",
                multi_host=multi_host)
            gas = engine.gradient_accumulation_steps()
            comm = commplan.CommPlan(
                subject="fwdbwd*gas+step",
                costs=[dataclasses.replace(
                    c, executions=c.executions * gas)
                    for c in fb_comm.costs] + list(boundary_comm.costs),
                mesh_shape=mesh_shape, profile=profile,
                multi_host=multi_host)
    else:
        ev = engine._ensure_eval(batch)
        ev_closed = jax.make_jaxpr(ev)(engine.params, batch)
        programs.append(analyze_program(
            ev, (engine.params, batch), arg_labels=("params", "batch"),
            subject="eval", profile=profile, closed=ev_closed))
        if with_comm:
            comm = commplan.analyze_comm(
                ev_closed, mesh_shape, profile=profile, subject="eval",
                multi_host=multi_host)

    return CapacityPlan(
        programs=programs,
        persistent=engine.memory_estimate(),
        profile=profile,
        budget_bytes=budget_bytes,
        comm=comm, boundary_comm=boundary_comm)
