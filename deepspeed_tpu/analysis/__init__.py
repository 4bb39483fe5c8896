"""Graph Lint — jaxpr-level static analysis of the engine's step programs.

JAX exposes the whole train step as a traceable jaxpr before any chip
executes it, so the distributed-training mistakes that cost a multi-hour
hang on a pod slice are decidable at engine-build time.  Four passes
(``analysis/passes.py``):

1. collective consistency (rank-divergent collective order = deadlock)
2. precision flow (fp32 compute reachable from bf16/fp16 via upcasts)
3. transfer/recompile lint (host callbacks, weak types, donation)
4. shard-spec validation (specs vs mesh axes and value shapes, pre-compile)

Three entry points:

* engine config ``graph_lint: {"mode": "off"|"warn"|"error"}`` — the engine
  lints each step program once per batch format at build time.
* CLI ``python -m deepspeed_tpu.analysis <ds_config.json> ...`` — builds a
  representative model for the config, traces, prints a findings report.
* library: :func:`analyze_jaxpr` for any jaxpr, :func:`analyze_engine` for
  a constructed engine + batch.

See docs/analysis.md for the rule catalogue and suppression story.
"""

from __future__ import annotations

import logging
from typing import Optional, Sequence

import jax

from deepspeed_tpu.analysis import graph  # noqa: F401  (re-export for users)
from deepspeed_tpu.analysis import commplan  # noqa: F401
from deepspeed_tpu.analysis import concurrency  # noqa: F401
from deepspeed_tpu.analysis import dispatchplan  # noqa: F401
from deepspeed_tpu.analysis import lockwatch  # noqa: F401
from deepspeed_tpu.analysis import memplan  # noqa: F401
from deepspeed_tpu.analysis import passes
from deepspeed_tpu.analysis import profiles  # noqa: F401
from deepspeed_tpu.analysis import stability  # noqa: F401
from deepspeed_tpu.analysis.concurrency import ConcurrencyLintError
from deepspeed_tpu.analysis.dispatchplan import (DispatchPlan,
                                                 plan_engine_dispatch,
                                                 plan_serve_dispatch)
from deepspeed_tpu.analysis.memplan import (CapacityPlan, ProgramPlan,
                                            analyze_program, plan_engine)
from deepspeed_tpu.analysis.report import (ERROR, INFO, WARNING, Finding,
                                           GraphLintError, MemoryPlanError,
                                           Report, ShardSpecError)
from deepspeed_tpu.analysis.stability import (ExecutablePrediction,
                                              ProgramSignature,
                                              predict_executables,
                                              signature_of)

logger = logging.getLogger(__name__)

MODES = ("off", "warn", "error")

__all__ = [
    "ERROR", "WARNING", "INFO", "Finding", "Report", "GraphLintError",
    "MemoryPlanError", "ShardSpecError", "ConcurrencyLintError", "MODES",
    "concurrency", "lockwatch", "analyze_jaxpr",
    "analyze_step", "analyze_engine", "analyze_engine_train_batch",
    "analyze_engine_train_many", "trace_train_batch", "lower_train_batch",
    "train_batch_args", "fused_tail",
    "train_many_args", "step_args",
    "check_shard_specs",
    "validate_specs_or_raise", "dispatch_report",
    "CapacityPlan", "ProgramPlan", "analyze_program", "plan_engine",
    "DispatchPlan", "plan_engine_dispatch", "plan_serve_dispatch",
    "ExecutablePrediction", "ProgramSignature", "predict_executables",
    "signature_of",
    "commplan", "dispatchplan", "memplan", "profiles", "stability",
]


def analyze_jaxpr(jaxpr, mesh_axes: Optional[Sequence[str]] = None,
                  subject: str = "") -> Report:
    """Run the three jaxpr passes over one (closed or open) jaxpr."""
    rep = Report(subject=subject)
    passes.check_collectives(jaxpr, rep, mesh_axes=mesh_axes)
    passes.check_precision(jaxpr, rep)
    passes.check_transfers(jaxpr, rep)
    return rep


def analyze_step(fn, args, mesh=None, subject: str = "") -> Report:
    """Trace ``fn(*args)`` to a jaxpr (jitted fns included — the pjit level
    is walked through, and its ``donated_invars`` feed the donation lint)
    and run the jaxpr passes."""
    mesh_axes = list(mesh.shape.keys()) if mesh is not None else None
    closed = jax.make_jaxpr(fn)(*args)
    return analyze_jaxpr(closed, mesh_axes=mesh_axes, subject=subject)


def check_shard_specs(mesh, specs, tree, subject: str = "",
                      where: str = "") -> Report:
    """Pass 4 standalone: PartitionSpecs vs mesh axes and value shapes."""
    rep = Report(subject=subject)
    passes.check_shard_specs(dict(mesh.shape), specs, tree, rep, where=where)
    return rep


def validate_specs_or_raise(mesh, specs, tree, where: str = "") -> None:
    """The engine's first-class pre-compile shard-spec gate: raises
    :class:`ShardSpecError` naming the offending leaf, spec and axis
    instead of letting shard_map fail with a raw spec-mismatch error.
    Always on (independent of ``graph_lint.mode``) — it replaces a crash,
    it does not add a new failure mode."""
    rep = check_shard_specs(mesh, specs, tree, where=where)
    errs = rep.errors
    if errs:
        raise ShardSpecError(
            f"invalid sharding for {where or 'shard_map operands'} "
            f"({len(errs)} problem(s)):\n"
            + "\n".join("  - " + f.message for f in errs))


def analyze_engine(engine, batch, train: bool = True,
                   include_step: bool = True) -> Report:
    """Full engine analysis for one batch format: shard-spec pass over the
    param and batch specs, then the jaxpr passes over the traced
    forward+backward (or eval) program and the boundary step program."""
    batch = tuple(batch) if isinstance(batch, (tuple, list)) else (batch,)
    rep = Report(subject="engine")

    # pass 4 first: a spec problem would make tracing fail anyway
    passes.check_shard_specs(dict(engine.mesh.shape), engine._param_specs,
                             engine.params, rep, where="params")
    passes.check_shard_specs(dict(engine.mesh.shape),
                             engine._batch_specs(batch), batch, rep,
                             where="batch")
    if rep.errors:
        return rep

    mesh_axes = list(engine.mesh.shape.keys())
    if train:
        fwdbwd = engine._ensure_fwdbwd(batch)
        traced = jax.make_jaxpr(fwdbwd)(
            engine.params, engine.loss_scale_state.cur_scale, batch)
        rep.extend(analyze_jaxpr(traced, mesh_axes=mesh_axes,
                                 subject="fwdbwd"))
        if include_step:
            # shape of the accumulated grads == shape of one micro-step's
            # grads (fp32 stacks / ZeRO partitions)
            _, grad_shapes = jax.eval_shape(
                fwdbwd, engine.params, engine.loss_scale_state.cur_scale,
                batch)
            if engine._step_fn is None:
                engine._step_fn = engine._build_step()
            master = (engine.master_flat if engine.zero_flat
                      else engine.master)
            step_tr = jax.make_jaxpr(engine._step_fn)(
                master, engine.opt_state, grad_shapes,
                engine.loss_scale_state, engine._current_hypers(),
                engine._zero_norm_w, engine._zero_gid_flat)
            rep.extend(analyze_jaxpr(step_tr, mesh_axes=mesh_axes,
                                     subject="step"))
            # master-weight precision contract (precision.MASTER_DTYPE):
            # the fp32 master is what makes bf16/fp16 training converge
            from deepspeed_tpu import precision as prec
            bad = [str(jax.tree_util.keystr(p))
                   for p, l in jax.tree_util.tree_flatten_with_path(
                       master)[0]
                   if hasattr(l, "dtype") and l.dtype != prec.MASTER_DTYPE]
            if bad:
                rep.add(
                    "precision.master-dtype", ERROR,
                    f"master weights are expected in fp32 but "
                    f"{bad[:3]}{'...' if len(bad) > 3 else ''} are not — "
                    f"low-precision masters silently stall convergence",
                    pass_name="precision")
    else:
        ev = engine._ensure_eval(batch)
        traced = jax.make_jaxpr(ev)(engine.params, batch)
        rep.extend(analyze_jaxpr(traced, mesh_axes=mesh_axes,
                                 subject="eval"))
    return rep


def train_batch_args(engine, batch):
    """The fused train_batch call tuple with the engine's CURRENT state —
    THE single owner of the step-function call protocol.  Every caller
    that needs the tuple (the tracer below, the capacity planner, the
    XLA-parity tests, the engine itself) marshals through here;
    hand-rolled copies drift silently when the signature changes.  Two
    optional trailing arguments, in this order: for a model that declares
    step scalars, the device-side totals since the last read
    (``observability.scalars.Channel.device``), which the step returns
    with itself added; with the metric spool on
    (``observability.report_window``), the spool state — the device ring
    buffer the compiled step appends this boundary's metrics into."""
    batch = tuple(batch) if isinstance(batch, (tuple, list)) else (batch,)
    master = engine.master_flat if engine.zero_flat else engine.master
    args = (engine.params, master, engine.opt_state,
            engine.loss_scale_state, engine._current_hypers(),
            engine._zero_norm_w, engine._zero_gid_flat, batch)
    return args + _trailing_args(engine)


def fused_tail(engine):
    """``((label, argument), ...)``: the optional tail both fused call
    tuples share, in order — the step scalars' totals, then the spool
    state."""
    tail = ()
    channel = getattr(engine, "_scalars", None)
    if channel is not None:
        tail += (("step_scalars", channel.device),)
    spool = getattr(engine, "_spool", None)
    if spool is not None:
        tail += (("spool", spool.state),)
    return tail


def _trailing_args(engine):
    return tuple(arg for _, arg in fused_tail(engine))


def train_many_args(engine, batches):
    """The K-fused ``train_many`` call tuple with the engine's CURRENT
    state — single owner like :func:`train_batch_args`.  ``batches`` is
    the sequence of K per-step batch tuples (separate program arguments,
    NOT a stacked tree — see ``engine._build_train_many`` for why); the
    hyper slot carries the staged ``[K, 4, G]`` block, and the tuple ends
    with :func:`train_batch_args`' optional tail (step-scalar totals, spool
    state)."""
    batches = tuple(tuple(b) if isinstance(b, (tuple, list)) else (b,)
                    for b in batches)
    k = len(batches)
    master = engine.master_flat if engine.zero_flat else engine.master
    args = (engine.params, master, engine.opt_state,
            engine.loss_scale_state, engine._stage_hypers_many(k),
            engine._zero_norm_w, engine._zero_gid_flat,
            engine._live_flag, batches)
    return args + _trailing_args(engine)


def analyze_engine_train_many(engine, batches) -> Report:
    """Jaxpr passes over the K-fused ``train_many`` program (K unrolled
    fused steps feeding each other inside one shard_map) — one trace
    covers every step's model, collectives and optimizer, so a
    rank-divergent collective introduced by the unrolling is caught
    exactly like in the single-step program."""
    batches = tuple(tuple(b) if isinstance(b, (tuple, list)) else (b,)
                    for b in batches)
    rep = Report(subject="train_many")
    passes.check_shard_specs(dict(engine.mesh.shape),
                             engine._batch_specs(batches[0]), batches[0],
                             rep, where="batch")
    if rep.errors:
        return rep
    # the CURRENT cached program only fits if it was built for this
    # (K, format) pair — otherwise build a matching one (a K=8 program
    # traced with 2 batches would die on the shard_map arg count)
    key = (len(batches), engine._batch_cache_key(batches[0]))
    fn = (engine._train_many_fn if engine._train_many_key == key
          else engine._cached_batch_fn(
              engine._train_many_fns, key,
              lambda: engine._build_train_many(batches[0], len(batches))))
    rep.extend(analyze_jaxpr(
        jax.make_jaxpr(fn)(*train_many_args(engine, batches)),
        mesh_axes=list(engine.mesh.shape.keys()), subject="train_many"))
    return rep


def step_args(engine, grads):
    """The split-API boundary step call tuple (engine._step_fn's 7-arg
    protocol) with the engine's CURRENT state — single owner, like
    :func:`train_batch_args`: the engine's ``step()``, the capacity
    planner's split branch, and the bench boundary microbench all marshal
    through here.  ``grads`` is the accumulated-grad slot (real arrays or
    ShapeDtypeStructs)."""
    master = engine.master_flat if engine.zero_flat else engine.master
    return (master, engine.opt_state, grads, engine.loss_scale_state,
            engine._current_hypers(), engine._zero_norm_w,
            engine._zero_gid_flat)


def trace_train_batch(engine, batch, fn=None):
    """Jaxpr of the fused train_batch program (args via
    :func:`train_batch_args`; the overlap microbench counts collectives
    through this too).  ``fn`` defaults to the engine's built
    ``_train_batch_fn``."""
    return jax.make_jaxpr(fn or engine._train_batch_fn)(
        *train_batch_args(engine, batch))


def lower_train_batch(engine, batch):
    """``jax.stages.Lowered`` of the engine's built fused train_batch
    program for its CURRENT state — ``.as_text()`` is what XLA is handed
    (chip_smoke.py looks for the Pallas ``tpu_custom_call`` in it)."""
    return engine._train_batch_fn.lower(*train_batch_args(engine, batch))


def analyze_engine_train_batch(engine, batch) -> Report:
    """Jaxpr passes over the fused train_batch program (scan over gas
    micro-steps feeding the boundary update) — one trace covers the model,
    the collectives AND the optimizer."""
    batch = tuple(batch) if isinstance(batch, (tuple, list)) else (batch,)
    rep = Report(subject="train_batch")
    passes.check_shard_specs(dict(engine.mesh.shape),
                             engine._batch_specs(batch), batch, rep,
                             where="batch")
    if rep.errors:
        return rep
    rep.extend(analyze_jaxpr(trace_train_batch(engine, batch),
                             mesh_axes=list(engine.mesh.shape.keys()),
                             subject="train_batch"))
    return rep


def dispatch_report(rep: Report, mode: str, where: str = "",
                    log: Optional[logging.Logger] = None,
                    label: str = "graph lint",
                    info_hint: Optional[str] = None,
                    error_cls=None) -> Report:
    """Apply a ``graph_lint.mode``-style gate: log warnings+errors in
    ``warn`` mode, raise ``error_cls`` (default :class:`GraphLintError`)
    on error findings in ``error`` mode.  The capacity planner rides the
    same dispatcher with ``label="capacity plan"`` and
    ``error_cls=MemoryPlanError`` — one gate implementation, two pass
    families."""
    log = log or logger
    if mode == "off" or not len(rep):
        return rep
    worst = rep.errors or rep.warnings
    if worst or rep.infos:
        hint = (info_hint or "engine.run_graph_lint(batch).format() "
                             "shows them")
        body = (rep.format(min_severity=WARNING) if worst else
                f"{len(rep.infos)} info-severity finding(s); {hint}")
        log.log(logging.WARNING if worst else logging.INFO,
                "%s%s: %s\n%s", label,
                f" [{where}]" if where else "", rep.summary(), body)
    if mode == "error":
        rep.raise_on_error(where=where, error_cls=error_cls)
    return rep
