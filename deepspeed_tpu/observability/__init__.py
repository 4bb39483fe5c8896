"""Telemetry — the engine's single observability layer (docs/observability.md).

Four pieces, one facade:

* :mod:`~deepspeed_tpu.observability.spool` — MetricSpool: per-boundary
  loss/grad-norm/loss-scale/skip-flag accumulated in a device-side ring
  buffer inside the compiled step, drained by ONE batched host callback
  every ``report_window`` boundaries.  Replaces every per-step host fence
  (the ROADMAP-4 prerequisite); trajectory-neutral by construction.
* :mod:`~deepspeed_tpu.observability.tracing` — programmatic
  ``jax.profiler`` capture over a configured step window, ``dstpu/*``
  TraceAnnotation spans, and watchdog-triggered hang capture;
  :mod:`~deepspeed_tpu.observability.scopes` names the device side of the
  same capture (``dstpu/*`` ``named_scope``s inside the compiled step and
  the instruction-name map a trace reader joins them by);
  :mod:`~deepspeed_tpu.observability.scalars` carries what a scope cannot
  say — counts the model takes on the device from its data (an expert
  layer's overflow passes) — out of the step beside the loss, into the
  window events (``scalars``) and the ``model`` counter group.
* :mod:`~deepspeed_tpu.observability.registry` — MetricRegistry exporter
  fan-out: engine throughput/goodput, resilience counters and
  compile-cache counters all emit through one path to TensorBoard and a
  schema-versioned JSONL event log (:mod:`~.schema`).
* goodput accounting — per-window measured step time, samples/s, optional
  MFU, and measured-vs-predicted capacity (the PR 6 planner handoff) with
  ``drift`` ratios, so prediction rot is a column, not a surprise.

Config::

    "observability": {
      "report_window": 0,          # >= 1 enables the spool
      "jsonl_path": null,          # JSONL event log (process 0)
      "trace_dir": null,           # or env DSTPU_TRACE_DIR (dst --trace_dir)
      "trace_start_step": 10,
      "trace_num_steps": 0,        # > 0 schedules a capture window
      "hang_capture": true,        # watchdog fire -> trace under trace_dir
      "hang_capture_s": 1.0,
      "planner_drift": true,       # predicted peak-HBM/boundary columns
      "flops_per_sample": null,    # enables the MFU column
      "peak_tflops_per_chip": null,
      "fleet": false,              # cross-host aggregation -> rank-0
                                   # dstpu.telemetry.fleet events
      "fleet_wait_s": 30.0,        # per-window aggregation deadline
      "straggler_factor": 2.0,     # host-time multiple of fleet median
      "spike_factor": 5.0,         # loss/grad-norm spike multiple
      "starvation_frac": 0.5,      # data-wait fraction of step time
      "health_port": 0,            # > 0 serves /healthz /status /metrics
                                   # (base + process_index; env
                                   # DSTPU_HEALTH_PORT via dst --health_port)
      "flight_recorder": 256,      # host-side event ring size (0 = off)
      "flight_recorder_dir": null  # dump destination (watchdog fire /
                                   # preemption drain / crash exit)
    }
"""

from __future__ import annotations

import logging
import os
import threading
import time
import weakref
from typing import Optional

import numpy as np

from deepspeed_tpu.observability import detectors  # noqa: F401
from deepspeed_tpu.observability import fences  # noqa: F401  (re-export)
from deepspeed_tpu.observability import fleet as fleet_mod
from deepspeed_tpu.observability import flightrec  # noqa: F401
from deepspeed_tpu.observability import health as health_mod
from deepspeed_tpu.observability import schema  # noqa: F401
from deepspeed_tpu.observability import spool as spool_mod
from deepspeed_tpu.observability import tracing
from deepspeed_tpu.observability.flightrec import RECORDER  # noqa: F401
from deepspeed_tpu.observability.registry import (JsonlSink, MetricRegistry,
                                                  TensorboardSink)
from deepspeed_tpu.observability.spool import MetricSpool
from deepspeed_tpu.observability.tracing import Tracer, annotate

logger = logging.getLogger(__name__)

__all__ = [
    "Telemetry", "MetricSpool", "MetricRegistry", "TensorboardSink",
    "JsonlSink", "Tracer", "annotate", "detectors", "fences", "fleet_mod",
    "flightrec", "health_mod", "schema", "spool_mod", "tracing", "RECORDER",
]


class Telemetry:
    """Per-engine telemetry driver.  Built by the engine at the end of
    ``__init__`` (after the summary writer and scheduler exist); holds the
    engine by weakref — the drain callback must never keep a dead engine
    alive."""

    def __init__(self, engine):
        import jax
        cfg = engine.config
        self._engine_ref = weakref.ref(engine)
        self.window = int(cfg.observability_report_window)
        self.registry = MetricRegistry()
        # with the lock sanitizer armed (DSTPU_LOCKWATCH=1 /
        # lockwatch.instrument()), its wait/held counters ride this
        # registry into every snapshot as lockwatch/lock_wait_ms.<name>
        from deepspeed_tpu.analysis import lockwatch
        if lockwatch.armed():
            lockwatch.register_metrics(self.registry)
        self._lock = threading.Lock()
        self._last_drain_ts = None      # set at first drain; window 1 is
        self._base_step = None          # unmeasured (it includes compile)
        self._skip_contract = bool(cfg.fp16_enabled
                                   or cfg.resilience_nan_sentinel)
        self._fp16 = bool(cfg.fp16_enabled)
        self._sentinel = bool(cfg.resilience_nan_sentinel)
        self._defer_overflow = None     # resolved lazily (needs scheduler)
        self._warned_sync_exception = False
        self.predictions = {}           # planner handoff (note_predictions)
        self._predictions_tried = False
        self.planner_drift = bool(cfg.observability_planner_drift)
        self.flops_per_sample = cfg.observability_flops_per_sample
        self.peak_tflops = cfg.observability_peak_tflops_per_chip
        self.measured_boundary_ms = None    # set by whoever measures it
        self.samples_per_step = (cfg.train_batch_size or 0)
        self._n_devices = jax.device_count()
        self._rank = jax.process_index()
        self._world = jax.process_count()

        # fleet-observability bookkeeping: cold-start timing for the
        # startup event, host-side pre-dispatch/data-wait accumulators
        # for the per-host straggler signal, last-event snapshots for the
        # live health endpoints
        self._built_ts = time.time()
        self._first_step_ts = None
        self.first_dispatch_s = None
        self._startup_emitted = False
        self._host_s = 0.0
        self._host_n = 0
        self._data_wait_s = 0.0
        self._data_wait_n = 0
        self.last_window_event = None
        self.last_fleet_event = None
        self.startup_event = None
        self._window_ordinal = 0

        # flight recorder: the process ring is always on (recording is a
        # locked deque append — ~free); the engine's config sizes it and
        # points the dump directory (default: next to the JSONL log, else
        # the trace dir, else cwd)
        dump_dir = (cfg.observability_flight_recorder_dir
                    or (os.path.dirname(os.path.abspath(
                        cfg.observability_jsonl_path))
                        if cfg.observability_jsonl_path else None)
                    or cfg.observability_trace_dir)
        RECORDER.configure(capacity=cfg.observability_flight_recorder,
                           rank=self._rank, dump_dir=dump_dir)
        flightrec.maybe_register_exit_dump()

        # sinks: TensorBoard rides the engine's writer, resolved LIVE at
        # emit time (rank-0 gated there; tests and users may swap the
        # writer after build); the JSONL event log writes on process 0
        self._tb = TensorboardSink(self._live_writer)
        self.registry.add_sink(self._tb)
        self.jsonl_path = None
        if (cfg.observability_jsonl_path
                and jax.process_index() == 0):
            self.jsonl_path = cfg.observability_jsonl_path
            self.registry.add_sink(JsonlSink(self.jsonl_path))

        # sources: the deduped scalar producers (legacy tag spellings kept:
        # Train/Samples/lr, Train/Resilience/*) + the detector counters
        from deepspeed_tpu.resilience import COUNTERS
        self.registry.register("resilience", COUNTERS.as_dict)
        self.registry.register("samples", self._samples_source)
        self.registry.register("observability",
                               detectors.COUNTERS.as_dict)
        # what the model declares its step is made of (a looped model: the
        # passes, exits and layer applications behind ``dstpu/loop``) and
        # what it counts on the device (its step scalars: the host-side
        # numbers since initialize); models that declare nothing have no
        # group
        if (callable(getattr(engine.module, "step_counts", None))
                or engine._scalars is not None):
            self.registry.register("model", self._model_source)
        # the flat ZeRO boundary's gradient wire: how wide the step program
        # that was built sends (16: the backward's bf16/fp16; 32: an fp32
        # accumulator) and the bytes a chip sends a step
        if engine.zero_flat:
            self.registry.register("boundary", self._boundary_source)

        # spool (report_window >= 1)
        self.spool: Optional[MetricSpool] = None
        self._anomaly: Optional[detectors.WindowAnomalyDetector] = None
        if self.window >= 1:
            self.spool = MetricSpool(self.window, self._on_window,
                                     scalars=engine._scalars)
            # pin the fresh ring state to the engine mesh (committed,
            # replicated): as plain jnp.zeros it is UNCOMMITTED, and the
            # fused train_batch's first call would hash a different
            # executable key than every later call (whose spool args are
            # the committed program outputs) — one silent re-lower per
            # run, the stability.unpinned-sharding class
            # (tests/test_dispatch_stability.py pins the fix)
            from jax.sharding import NamedSharding, PartitionSpec
            self.spool.state = jax.tree_util.tree_map(
                lambda x: jax.device_put(
                    x, NamedSharding(engine.mesh, PartitionSpec())),
                self.spool.state)
            self._anomaly = detectors.WindowAnomalyDetector(
                self._rank,
                spike_factor=cfg.observability_spike_factor,
                starvation_frac=cfg.observability_starvation_frac)
            # resolve the deferral decision NOW (the scheduler exists —
            # the engine builds Telemetry last): at report_window=1 the
            # first drain can run before any boundary bookkeeping, and a
            # lazily-unresolved flag would silently skip that window's
            # deferred skip accounting
            self.defers_overflow(engine)

        # fleet aggregation (docs/observability.md "Fleet view"): per-host
        # window reports ship OUT-OF-BAND to rank 0 over the coordination
        # service — host threads only, never a device collective, never
        # the drain-callback thread
        self.fleet: Optional[fleet_mod.FleetAggregator] = None
        if cfg.observability_fleet and self.spool is not None:
            self.fleet = fleet_mod.FleetAggregator(
                world=self._world, rank=self._rank,
                wait_s=cfg.observability_fleet_wait_s,
                straggler_factor=cfg.observability_straggler_factor,
                emit=self._emit_fleet_event)

        # live health endpoints (opt-in: health_port config key or the
        # launcher's --health_port env fallback, offset per process)
        self.health: Optional[health_mod.HealthServer] = None
        port = health_mod.resolve_health_port(
            cfg.observability_health_port)
        if port is not None:
            try:
                self.health = health_mod.HealthServer(
                    port, self, rank=self._rank)
            except OSError as e:
                # a taken port must not take down training — loudly
                # degraded, like every other telemetry failure
                logger.warning(
                    "telemetry: health endpoints DISABLED — could not "
                    "bind port %d: %s", port, e)

        # tracer (trace_dir from config or DSTPU_TRACE_DIR)
        self.tracer: Optional[Tracer] = None
        trace_dir = tracing.resolve_trace_dir(cfg.observability_trace_dir)
        if trace_dir is not None:
            self.tracer = Tracer(
                trace_dir,
                start_step=cfg.observability_trace_start_step,
                num_steps=cfg.observability_trace_num_steps,
                hang_capture_s=cfg.observability_hang_capture_s)
        self.hang_capture = bool(cfg.observability_hang_capture)

    @classmethod
    def from_engine(cls, engine) -> "Telemetry":
        """Every engine gets a Telemetry: with no ``observability`` config
        the spool/tracer stay off, but the registry still owns ALL scalar
        export (the dedup of the three legacy TensorBoard write loops —
        one path whether metrics ride windows or boundaries)."""
        return cls(engine)

    # ------------------------------------------------------------- sources
    def _live_writer(self):
        engine = self._engine_ref()
        return engine.summary_writer if engine is not None else None

    def _model_source(self) -> dict:
        engine = self._engine_ref()
        if engine is None:
            return {}
        # the gauges of the fused step program the engine runs, where one
        # was traced (engine._record_step_gauges); the module's live ones
        # — of whatever program was traced last — before that
        counts = engine._step_gauges.get("model")
        if counts is None:
            declare = getattr(engine.module, "step_counts", None)
            counts = declare() if callable(declare) else {}
        counts = dict(counts)
        if "layer_applications" in counts:
            counts["layer_applications_per_step"] = (
                counts.pop("layer_applications")
                * engine.gradient_accumulation_steps())
        if engine._scalars is not None:
            counts.update(engine._scalars.counters())
        return counts

    def _boundary_source(self) -> dict:
        engine = self._engine_ref()
        if engine is None:
            return {}
        return dict(engine._step_gauges.get("boundary")
                    or engine._boundary_wire)

    def _samples_source(self) -> dict:
        engine = self._engine_ref()
        if engine is None:
            return {}
        return {"lr": float(engine.optimizer.param_groups[0]["lr"])}

    # --------------------------------------------------------------- spool
    @property
    def spool_active(self) -> bool:
        return self.spool is not None

    def defers_overflow(self, engine) -> bool:
        """Whether the engine may SKIP the per-boundary overflow host read
        (the last per-step fence).  True whenever the spool is on — except
        under the documented exception: fp16/nan-sentinel WITH an LR
        scheduler, whose skip-on-overflow contract (no scheduler step on a
        skipped boundary) needs the flag on the host before the next
        boundary's hyperparameter staging.  There the read stays and the
        spool still batches every other metric."""
        if self.spool is None:
            return False
        if self._defer_overflow is None:
            exception = (self._skip_contract
                         and engine.lr_scheduler is not None)
            self._defer_overflow = not exception
            if exception and not self._warned_sync_exception:
                self._warned_sync_exception = True
                logger.warning(
                    "telemetry: per-boundary overflow read RETAINED — the "
                    "%s skip contract must gate lr_scheduler.step() before "
                    "the next boundary (docs/observability.md \"The "
                    "scheduler exception\"); all other metrics still spool",
                    "fp16" if self._fp16 else "nan_sentinel")
        return self._defer_overflow

    def note_fused_plan(self, plan) -> None:
        """Adopt a capacity plan the engine's build-time gate already
        computed (engine._maybe_capacity_plan) — the drift columns must
        not re-trace the fused program to learn a number that exists."""
        if self.planner_drift and "predicted_peak_hbm_gb" not in \
                self.predictions:
            self.predictions["predicted_peak_hbm_gb"] = round(
                plan.peak_bytes / 2 ** 30, 6)
            if plan.profile is not None:
                self.predictions.setdefault("predicted_profile",
                                            plan.profile.name)

    def note_predictions(self, engine, batch) -> None:
        """One-time planner handoff (best-effort): predicted per-device
        peak HBM of the fused program (reused from the analysis gate's
        plan when it ran — see :meth:`note_fused_plan`) + predicted
        boundary wire time from the split-API plan, reported next to
        measurement in every window event (``*_drift`` columns)."""
        if self._predictions_tried or not self.planner_drift:
            return
        self._predictions_tried = True
        # defensive batch normalization: the engine hands the tuple form,
        # but a bare-array batch must not silently cost the drift columns
        batch = (tuple(batch) if isinstance(batch, (tuple, list))
                 else (batch,))
        try:
            if "predicted_peak_hbm_gb" not in self.predictions:
                fused = engine.plan_capacity(batch, train=True, fused=True)
                self.predictions["predicted_peak_hbm_gb"] = round(
                    fused.peak_bytes / 2 ** 30, 6)
            gas = engine.gradient_accumulation_steps()
            lead = next(iter(
                l.shape[0] for l in _tree_leaves(batch)))
            micro = tuple(a[:lead // gas] for a in batch)
            split = engine.plan_capacity(micro, train=True, fused=False)
            if split.boundary_comm is not None:
                self.predictions["predicted_boundary_ms"] = round(
                    split.boundary_comm.predicted_time_ms(), 6)
                if split.profile is not None:
                    self.predictions.setdefault("predicted_profile",
                                                split.profile.name)
        except Exception as e:  # pragma: no cover - defensive
            logger.warning("telemetry: capacity-plan handoff skipped: %s", e)

    def _on_window(self, rows: np.ndarray, pos: int) -> None:
        """Spool delivery (runtime callback thread on async drains, caller
        thread on flush): aggregate the window, settle the deferred
        skip bookkeeping, emit through the registry, run the per-host
        anomaly detectors and hand the fleet report off."""
        n = int(rows.shape[0])
        now = time.time()
        engine = self._engine_ref()
        with self._lock:
            base = self._base_step or 0
            last_ts, self._last_drain_ts = self._last_drain_ts, now
            host_s, host_n = self._host_s, self._host_n
            self._host_s, self._host_n = 0.0, 0
            wait_s, wait_n = self._data_wait_s, self._data_wait_n
            self._data_wait_s, self._data_wait_n = 0.0, 0
        step = base + pos

        skips = int(np.sum(rows[:, spool_mod.SKIP] > 0)) \
            if self._skip_contract else 0
        if engine is not None and self._defer_overflow:
            # deferred skip-on-overflow bookkeeping (the host read this
            # replaces): counters catch up at the drain, the device-side
            # skip (untouched master/moments) already happened in-program
            engine.skipped_steps += skips
            engine.overflow = bool(rows[-1, spool_mod.SKIP] > 0)
            if skips and self._sentinel and not self._fp16:
                from deepspeed_tpu.resilience import COUNTERS
                COUNTERS.nan_skips += skips
                logger.warning(
                    "resilience: %d non-finite-gradient boundar%s skipped "
                    "in the window ending at global step %d (nan_sentinel, "
                    "spooled)", skips, "y" if skips == 1 else "ies", step)

        event = {
            "step": int(step),
            "window_steps": n,
            "loss": float(rows[-1, spool_mod.LOSS]),
            "loss_mean": float(np.mean(rows[:, spool_mod.LOSS])),
            "grad_norm": float(rows[-1, spool_mod.GRAD_NORM]),
            "loss_scale": float(rows[-1, spool_mod.LOSS_SCALE]),
            "skipped": skips,
            "ts": now,
        }
        if last_ts is not None and now > last_ts:
            elapsed = now - last_ts
            event["step_ms"] = elapsed / n * 1000.0
            if self.samples_per_step:
                sps = n * self.samples_per_step / elapsed
                event["samples_per_sec"] = sps
                if self.flops_per_sample and self.peak_tflops:
                    event["mfu"] = (
                        (sps / self._n_devices)
                        * float(self.flops_per_sample)
                        / (float(self.peak_tflops) * 1e12))
        if engine is not None and engine._scalars is not None:
            # the model's step scalars over THIS window (the drain that
            # delivered it was handed the device totals and folded them
            # first); the totals since initialize ride ``counters`` as the
            # ``model`` group
            event["scalars"] = engine._scalars.take_window()
        event.update(self._capacity_columns())
        # per-host fleet-report columns (schema v2): host-side pre-dispatch
        # time is THE straggler signal — under lockstep SPMD one slow rank
        # makes every rank's wall time slow, but only the straggler pays
        # host-side time (docs/observability.md "Fleet view")
        event["rank"] = self._rank
        event["host_ms"] = (round(host_s / host_n * 1000.0, 4)
                            if host_n else None)
        event["data_wait_ms"] = (round(wait_s / max(wait_n, n) * 1000.0, 4)
                                 if wait_n else None)
        if self._anomaly is not None:
            event["anomalies"] = self._anomaly.check_window(event)
        sample_count = (getattr(engine, "sample_count", None)
                        if engine is not None else None)
        self._maybe_emit_startup(step - n, sample_count)
        counters = self.registry.counters_snapshot()
        event.setdefault("counters", {}).update(counters)
        self.registry.emit_event(event, sample_count=sample_count)
        RECORDER.record("window", step=int(step), window_steps=n)
        with self._lock:
            self.last_window_event = event
        if self.fleet is not None:
            # enqueue only: the KV publish is a network RPC that must not
            # ride the runtime callback thread.  Ordinal = deliveries so
            # far on this rank: every rank drains at the same append
            # counts (window edges + the SPMD-synchronous flush sites),
            # so ordinals agree fleet-wide without any collective.
            with self._lock:
                self._window_ordinal += 1
                ordinal = self._window_ordinal
            self.fleet.publish(ordinal, fleet_mod.make_report(
                event, rank=self._rank, counters=counters))

    def _capacity_columns(self) -> dict:
        """Measured-vs-predicted capacity (PR 6 planner handoff)."""
        out = dict(self.predictions)
        measured = _measured_peak_hbm_gb()
        if measured is not None:
            out["measured_peak_hbm_gb"] = round(measured, 4)
            pred = out.get("predicted_peak_hbm_gb")
            if pred:
                out["hbm_drift"] = round(measured / pred, 4)
        if self.measured_boundary_ms is not None:
            out["measured_boundary_ms"] = round(self.measured_boundary_ms, 4)
            pred = out.get("predicted_boundary_ms")
            if pred:
                out["boundary_drift"] = round(
                    self.measured_boundary_ms / pred, 4)
        return out

    # --------------------------------------------------- engine-facing hooks
    def note_spool_base_step(self, global_steps: int) -> None:
        """Anchor ring positions to engine global steps (set at the first
        spooled boundary; a resumed engine anchors at its restored step)."""
        with self._lock:
            if self._base_step is None:
                self._base_step = int(global_steps)

    def rebase_steps(self, global_steps: int) -> None:
        """Re-anchor window step numbering after a checkpoint restore:
        subsequent events report ``restored step + appends since``."""
        if self.spool is None:
            return
        with self._lock:
            self._base_step = int(global_steps) - self.spool._appended

    def note_boundary_host_seconds(self, pre_s: float,
                                   total_s: float = None) -> None:
        """Engine hook, once per optimizer boundary: ``pre_s`` is the
        host-side time from entering the armed boundary region to the
        program dispatch call (two clock reads — the per-host straggler
        signal: a rank stalling in host code pays it, a rank waiting
        inside a collective does not); ``total_s`` is the whole armed
        region's wall time, kept from the FIRST boundary as the
        startup event's compile-dominated ``first_dispatch_s``."""
        now = time.time()
        with self._lock:
            if self._first_step_ts is None:
                self._first_step_ts = now
                if total_s is not None:
                    self.first_dispatch_s = float(total_s)
            self._host_s += float(pre_s)
            self._host_n += 1

    def note_data_wait_seconds(self, seconds: float) -> None:
        """Driver/loader hook: host time spent blocked waiting for the
        next batch — the data-starvation detector's signal."""
        with self._lock:
            self._data_wait_s += float(seconds)
            self._data_wait_n += 1

    def _maybe_emit_startup(self, start_step: int, sample_count) -> None:
        """One startup event per process, emitted just before the first
        window event: the cold-start cost (compile + restore +
        time-to-first-step) as recorded numbers — the first window's
        ``step_ms`` stays honestly null (it contains compile), but the
        cost itself must not be a missing value (docs/observability.md
        "The startup event")."""
        with self._lock:
            if self._startup_emitted:
                return
            self._startup_emitted = True
            first_ts = self._first_step_ts
        from deepspeed_tpu.resilience import COUNTERS
        import socket as _socket
        event = {
            "schema": schema.STARTUP_SCHEMA_ID,
            "version": 2,
            "ts": time.time(),
            "rank": self._rank,
            "host": _socket.gethostname(),
            "step": max(int(start_step), 0),
            "time_to_first_step_s": (round(first_ts - self._built_ts, 4)
                                     if first_ts is not None else None),
            "first_dispatch_s": (round(self.first_dispatch_s, 4)
                                 if self.first_dispatch_s is not None
                                 else None),
            "restore_seconds": (round(COUNTERS.restore_seconds, 4)
                                or None),
            "compile_cache_hits": COUNTERS.compile_cache_hits,
            "compile_cache_misses": COUNTERS.compile_cache_misses,
        }
        self.startup_event = event
        self.registry.emit_event(event, sample_count=sample_count)

    def _emit_fleet_event(self, event: dict) -> None:
        """Aggregator-thread callback (rank 0): route the fleet event to
        the sinks and the live endpoints."""
        with self._lock:
            self.last_fleet_event = event
        RECORDER.record("fleet_window", window=event.get("window"),
                        step=event.get("step"),
                        stragglers=event.get("stragglers"),
                        missing=event.get("missing_hosts"))
        self.registry.emit_event(event)

    # ------------------------------------------------------ health endpoints
    def healthy(self) -> bool:
        """Liveness verdict for ``/healthz``: alive and not wedged (a
        fired watchdog means the process exists but trains nothing — the
        state an orchestrator should replace)."""
        from deepspeed_tpu.resilience import COUNTERS
        return COUNTERS.watchdog_fires == 0

    def health_snapshot(self) -> dict:
        """``/status`` payload: engine step, last window/fleet events,
        counters — all host-side state, no fences."""
        engine = self._engine_ref()
        with self._lock:
            last_window = self.last_window_event
            last_fleet = self.last_fleet_event
        out = {
            "healthy": self.healthy(),
            "step": (int(engine.global_steps)
                     if engine is not None else None),
            "report_window": self.window,
            "fleet": self.fleet is not None,
            "last_window": last_window,
            "startup": self.startup_event,
            "counters": self.registry.counters_snapshot(),
        }
        if self._rank == 0 and self.fleet is not None:
            out["last_fleet"] = last_fleet
        return out

    def health_metrics(self) -> dict:
        """``/metrics`` payload (flat name -> number; the health server
        renders Prometheus text): counters + the last window's goodput +
        the rank-0 fleet roll-up."""
        engine = self._engine_ref()
        out = {k.replace("/", "_"): v
               for k, v in self.registry.counters_snapshot().items()
               if isinstance(v, (int, float))}
        if engine is not None:
            out["step"] = int(engine.global_steps)
        out["healthy"] = 1 if self.healthy() else 0
        # restart detection for the fleet router: uptime resets and the
        # generation ordinal increments on a --max_restarts relaunch
        from deepspeed_tpu.observability import health as _health
        out["process_uptime_s"] = round(_health.process_uptime_s(), 3)
        out["replica_generation"] = _health.replica_generation()
        with self._lock:
            last_window = self.last_window_event
            last_fleet = self.last_fleet_event
        if last_window:
            for name in ("loss", "loss_mean", "grad_norm", "step_ms",
                         "samples_per_sec", "host_ms", "data_wait_ms",
                         "mfu", "window_steps", "skipped"):
                val = last_window.get(name)
                if isinstance(val, (int, float)):
                    out[f"window_{name}"] = val
        if last_fleet:
            for name in ("reported_hosts", "n_hosts", "straggler_index",
                         "step_ms_max", "step_ms_median", "host_ms_max",
                         "host_ms_median", "samples_per_sec_sum",
                         "skipped_total"):
                val = last_fleet.get(name)
                if isinstance(val, (int, float)):
                    out[f"fleet_{name}"] = val
            out["fleet_stragglers"] = len(last_fleet.get("stragglers")
                                          or [])
            out["fleet_missing_hosts"] = len(
                last_fleet.get("missing_hosts") or [])
        return out

    def emit_boundary_scalars(self, sample_count) -> None:
        """Legacy-cadence TensorBoard export (spool OFF): the same source
        snapshot the window path emits, written per boundary through the
        ONE TensorBoard sink — the dedup of the three historical write
        loops, and one owner of the tag spelling (a counters-only event
        writes no ``Train/Telemetry/*`` window scalars)."""
        self._tb.emit({"step": sample_count,
                       "counters": self.registry.counters_snapshot()},
                      sample_count=sample_count)

    def maybe_trace(self, global_steps: int) -> None:
        if self.tracer is not None:
            self.tracer.maybe_window(global_steps)

    def hang_capture_hook(self):
        """The watchdog ``on_fire`` callable (None when tracing is off)."""
        if self.tracer is None or not self.hang_capture:
            return None
        return lambda: self.tracer.capture_hang()

    def flush(self, local_only: bool = False,
              fleet_timeout: float = None) -> None:
        """Drain the final (possibly partial) window synchronously — run
        end and preemption drain; the ONE deliberate telemetry fence.
        With fleet mode on, also waits (bounded) until this rank's
        reports are published / rank 0's fleet events are emitted.

        ``local_only`` skips the cross-host fleet wait: the preemption
        drain flushes the spool BEFORE the emergency checkpoint (the
        window record must cover the drained step) but must NOT spend
        the grace period waiting on a possibly-dead peer while the
        checkpoint is still unwritten — it re-flushes with a bounded
        ``fleet_timeout`` after the save is durable."""
        if self.spool is not None:
            self.spool.flush()
        if self.fleet is not None and not local_only:
            self.fleet.flush(timeout=fleet_timeout)

    def close(self) -> None:
        self.flush()
        if self.tracer is not None:
            self.tracer.stop()
        if self.fleet is not None:
            self.fleet.close()
        if self.health is not None:
            self.health.close()
        self.registry.close()


def _tree_leaves(tree):
    import jax
    return jax.tree_util.tree_leaves(tree)


def _measured_peak_hbm_gb() -> Optional[float]:
    """Per-device peak HBM from the PJRT allocator (None on backends
    without memory stats — CPU)."""
    import jax
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
    except Exception:  # pragma: no cover - defensive
        return None
    peak = stats.get("peak_bytes_in_use")
    return None if peak is None else peak / 2 ** 30
