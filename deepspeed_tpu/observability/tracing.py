"""Step tracing: programmatic jax.profiler capture + hang capture.

Two capture paths share one :class:`Tracer`:

* scheduled window — config ``observability: {trace_dir, trace_start_step,
  trace_num_steps}`` captures ``[start, start + num)`` optimizer
  boundaries (range checks, so a checkpoint resume landing mid-window
  still traces the remainder — same contract as the legacy ``profile``
  section, which this supersedes; configuring both is a config error).
* hang capture — wired as the resilience watchdog's ``on_fire`` hook: when
  a hang deadline trips, the monitor thread records a short trace under
  ``<trace_dir>/hang_*`` before the optional abort, so a wedged run leaves
  a profile of what the host was doing, not just a stack dump.

:func:`annotate` provides the ``TraceAnnotation`` spans the engine wraps
around its calls — ``fwdbwd``, ``boundary``, ``train_batch``,
``train_many``, ``eval``, ``checkpoint.save``, ``checkpoint.load`` — named
``dstpu/<span>`` in the trace viewer.  Annotations are host-side markers,
~free when no trace is active; the device-side ``dstpu/*`` names inside the
compiled step are :mod:`~deepspeed_tpu.observability.scopes`.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Optional

logger = logging.getLogger(__name__)

#: env spelling of the trace directory — how the launcher (``dst
#: --trace_dir``) hands the capture destination to every worker and
#: ``--max_restarts`` relaunch (same pattern as DSTPU_COMPILE_CACHE_DIR)
ENV_TRACE_DIR = "DSTPU_TRACE_DIR"

#: set while ANY programmatic capture is active (scheduled window, hang
#: capture, or the legacy engine profile window).  :func:`annotate` is a
#: no-op unless this is set: ``jax.profiler.start_trace`` BLOCKS while any
#: thread holds an open TraceAnnotation (measured on jax 0.4), so an
#: always-on span around a blocking engine call would deadlock the
#: watchdog's hang capture against the very hang it is trying to record.
_capture_active = threading.Event()


def note_capture_active(active: bool) -> None:
    """Profiler session bracket — called by every start/stop site (Tracer
    and the engine's legacy ``start_profile``/``stop_profile``)."""
    if active:
        _capture_active.set()
    else:
        _capture_active.clear()


def resolve_trace_dir(cfg_dir: Optional[str]) -> Optional[str]:
    """Config value beats the :data:`ENV_TRACE_DIR` fallback; multi-process
    runs get a per-process subdirectory so workers never clobber each
    other's capture files."""
    d = cfg_dir or os.environ.get(ENV_TRACE_DIR) or None
    if d is None:
        return None
    import jax
    if jax.process_count() > 1:
        d = os.path.join(d, f"proc{jax.process_index()}")
    return d


_prewarm_started = False


def _prewarm_python_tracer() -> None:
    """Import the profiler's lazy host-side dependency in the background.

    The FIRST ``jax.profiler.start_trace`` of a process triggers XLA's
    python tracer hook, which lazily imports
    ``tensorflow.python.profiler.trace`` — ~10 s when tensorflow is
    installed.  Paying that on the capture path would stall the scheduled
    window's first traced step (or worse, outlive a watchdog hang capture
    whose process aborts).  A Tracer pre-warms it on a daemon thread at
    construction; a capture arriving mid-import simply waits on the
    import lock instead of re-paying it."""
    global _prewarm_started
    if _prewarm_started:
        return
    _prewarm_started = True

    def _load():
        try:
            import tensorflow.python.profiler.trace  # noqa: F401
        except Exception:
            pass        # no tensorflow: the hook fails fast at capture

    threading.Thread(target=_load, daemon=True,
                     name="dstpu-trace-prewarm").start()


def annotate(span: str):
    """``with annotate("fwd"): ...`` — a ``dstpu/<span>`` TraceAnnotation
    while a capture is active, a nullcontext otherwise (see
    :data:`_capture_active`: an open annotation on ANY thread blocks
    ``start_trace``, so spans must never straddle a step that could hang
    before a capture begins)."""
    if not _capture_active.is_set():
        from contextlib import nullcontext
        return nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(f"dstpu/{span}")


class Tracer:
    """Owns programmatic profiler capture for one engine.  Thread-safe:
    the scheduled window runs on the training thread, hang capture on the
    watchdog monitor thread — exactly one capture may be active."""

    def __init__(self, trace_dir: str, start_step: int = 0,
                 num_steps: int = 0, hang_capture_s: float = 1.0):
        self.trace_dir = trace_dir
        self.start_step = int(start_step)
        self.end_step = self.start_step + int(num_steps)
        self.hang_capture_s = float(hang_capture_s)
        self._lock = threading.Lock()
        self._active = None     # path of the active capture, or None
        self._window_path = None    # the SCHEDULED window's capture path
        self._window_done = False
        self._atexit = False
        _prewarm_python_tracer()

    # ----------------------------------------------------------- start/stop
    def _start(self, path: str) -> bool:
        import jax
        with self._lock:
            if self._active is not None:
                return False
            try:
                jax.profiler.start_trace(path)
            except Exception as e:
                logger.warning("trace capture could not start (%s): %s",
                               path, e)
                return False
            self._active = path
            note_capture_active(True)
        if not self._atexit:
            # flush the capture even if training ends inside the window
            import atexit
            atexit.register(self.stop)
            self._atexit = True
        logger.info("telemetry: trace capture started -> %s", path)
        return True

    def stop(self) -> Optional[str]:
        import jax
        with self._lock:
            path, self._active = self._active, None
            if path is None:
                return None
            note_capture_active(False)
            try:
                jax.profiler.stop_trace()
            except Exception as e:  # pragma: no cover - defensive
                logger.warning("trace capture stop failed: %s", e)
                return None
        logger.info("telemetry: trace capture stopped (%s)", path)
        return path

    # ------------------------------------------------------ scheduled window
    def maybe_window(self, global_step: int) -> None:
        """Boundary hook: start/stop the configured capture window."""
        if self.end_step <= self.start_step:
            return
        if (self._active is None and not self._window_done
                and self.start_step <= global_step < self.end_step):
            path = os.path.join(
                self.trace_dir, f"steps_{self.start_step}_{self.end_step}")
            if self._start(path):
                self._window_path = path
        elif (self._active is not None
                and self._active == self._window_path
                and global_step >= self.end_step):
            # stop only OUR scheduled capture: a concurrent watchdog hang
            # capture (self._active holds a hang_* path) must not be
            # truncated by the next boundary's bookkeeping
            self.stop()
            self._window_path = None
            self._window_done = True

    # ----------------------------------------------------------- hang capture
    def capture_hang(self, tag: str = "") -> Optional[str]:
        """Record a short host-side trace when the watchdog fires.  Runs on
        the monitor thread while the training thread is (by definition)
        stuck; returns the capture path, or None when a capture was
        already active or could not start."""
        path = os.path.join(
            self.trace_dir,
            f"hang_{tag or 'watchdog'}_{int(time.time())}")
        if not self._start(path):
            return None
        time.sleep(self.hang_capture_s)
        return self.stop()
