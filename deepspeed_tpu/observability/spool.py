"""MetricSpool — device-side metric ring buffer, drained once per window.

The reference engine fenced the host on EVERY step to report scalars
(``deepspeed_timer.py`` ``torch.cuda.synchronize``); the per-step fence is
exactly the fixed dispatch cost the fused ``train_batch`` path exists to
avoid (WALLCLOCK §7).  The spool removes it:

* each boundary APPENDS its metrics (loss, global grad norm, loss scale,
  skip flag) into a ``[window, 4]`` ring buffer — a pure
  ``dynamic_update_index_in_dim`` compiled INTO the step program (fused
  path) or dispatched as one tiny jitted program (split API).  No host
  transfer, no fence; the step's dispatch pipelines freely.
* every ``report_window`` boundaries the engine dispatches ONE small
  drain program whose ``io_callback`` hands the whole buffer to the host
  asynchronously: the callback runs on the runtime's callback thread when
  the device reaches it — the host never waits.  (On an ordered-effects
  backend the callback serializes into the device timeline once per
  window; keep the sink light.)
* ``flush()`` is the only synchronous read — a single counted fence
  (observability/fences.py) used at run end and on a preemption drain so
  the final partial window is never dropped.
* a model's step scalars (observability/scalars.py) ride the same drain:
  the one batched callback is handed the device-side totals since the last
  read beside ``buf`` and ``pos``, and the next step starts from zeros — a
  window drain IS a read of the scalars, with no fence; ``flush()`` reads
  them in its one fence.

Trajectory neutrality: the append consumes values the step program
already computes (loss / norm / scale / overflow are existing outputs);
it adds only pure consumers, so the optimizer math is bitwise identical
with the spool on or off (pinned by tests/test_observability.py).
"""

from __future__ import annotations

import logging
import threading
from typing import Callable, Optional

import numpy as np

from deepspeed_tpu.observability import fences

logger = logging.getLogger(__name__)

#: ring-buffer channel layout ([window, N_CHANNELS] fp32)
LOSS, GRAD_NORM, LOSS_SCALE, SKIP = range(4)
N_CHANNELS = 4


def init_state(window: int):
    """Fresh device-side spool state: ``{"buf": [window, 4] f32,
    "pos": i32[]}`` (pos counts total appends; row = pos % window)."""
    import jax.numpy as jnp
    return {"buf": jnp.zeros((int(window), N_CHANNELS), jnp.float32),
            "pos": jnp.zeros((), jnp.int32)}


def append(state, loss_out, grad_norm, loss_scale, overflow):
    """Pure in-program ring append (traceable; the fused train_batch
    builder calls this INSIDE the compiled step).  ``loss_out`` may be a
    loss pytree (multi-output models record the leaf sum, matching the
    TensorBoard ``train_loss`` scalar)."""
    import jax
    import jax.numpy as jnp
    loss_sum = sum(jnp.asarray(l, jnp.float32).sum()
                   for l in jax.tree_util.tree_leaves(loss_out))
    vec = jnp.stack([
        loss_sum,
        jnp.asarray(grad_norm, jnp.float32),
        jnp.asarray(loss_scale, jnp.float32),
        jnp.asarray(overflow, jnp.float32),
    ])
    window = state["buf"].shape[0]
    row = jax.lax.rem(state["pos"], jnp.int32(window))
    return {"buf": jax.lax.dynamic_update_index_in_dim(
                state["buf"], vec, row, 0),
            "pos": state["pos"] + 1}


class MetricSpool:
    """Host-side spool driver: owns the device state, the append/drain
    programs and the window bookkeeping.

    ``on_window(rows, end_pos)`` receives the drained window as a host
    ``[n, 4]`` numpy array (append order) plus the append count at the
    window's end; it is called from the runtime callback thread on async
    drains and from the calling thread on ``flush()``.
    """

    def __init__(self, window: int,
                 on_window: Callable[[np.ndarray, int], None],
                 scalars=None):
        if window < 1:
            raise ValueError(f"spool window must be >= 1, got {window}")
        self.window = int(window)
        self._on_window = on_window
        #: the engine's step-scalar channel (``scalars.Channel``) or None:
        #: every drain hands its totals to the callback and folds them
        #: into the host-side numbers BEFORE ``on_window`` runs
        self.scalars = scalars
        self.state = init_state(window)
        self._appended = 0       # host mirror of state["pos"]
        self._drained = 0        # appends already handed to on_window
        self._lock = threading.Lock()
        self._append_jit = None
        self._drain_jit = None

    # ------------------------------------------------------------- append
    def note_append(self, new_state) -> None:
        """Adopt the step program's updated spool state (fused path: the
        append ran inside train_batch) and auto-drain on window edges."""
        self.note_appends(new_state, 1)

    def would_straddle(self, n: int) -> bool:
        """True when ``n`` further appends would cross a window edge
        MID-BATCH: the ring holds exactly one window, so an in-program
        n-append that wraps past an undrained edge overwrites rows
        before any drain can read them.  Pure K-block runs never
        straddle (config pins ``window % K == 0``); a run that mixed a
        stray single append in can — the engine flushes first
        (``train_many``; one counted fence, mixed usage only)."""
        return (self._appended % self.window) + int(n) > self.window

    def note_appends(self, new_state, n: int) -> None:
        """Adopt a state carrying ``n`` in-program appends (the K-fused
        multi-step driver appends once per optimizer step INSIDE the
        dispatch).  The config layer guarantees ``window % K == 0``, so a
        window edge can only land exactly at a block edge — ``n`` appends
        never straddle one (a straddled edge would overrun the ring
        before the drain could read it)."""
        if n > self.window:
            # unreachable through the engine (config validates window
            # alignment) — but an overrun must be loud, never silent
            raise ValueError(
                f"spool: {n} appends in one dispatch exceed the "
                f"report window ({self.window}); rows would be "
                f"overwritten before any drain could deliver them")
        self.state = new_state
        before = self._appended
        self._appended += int(n)
        # drain on every window-edge CROSSING, not only exact alignment:
        # a run mixing train_batch (1 append) and train_many (K appends)
        # can land past an edge — the drain then delivers a short window
        # rather than silently never draining again
        if before // self.window != self._appended // self.window:
            self.drain_async()

    def append_split(self, loss_out, grad_norm, loss_scale, overflow) -> None:
        """Split-API append: one tiny jitted program per boundary (the
        split path already pays per-micro dispatches; this adds one more
        small one, still zero fences)."""
        import jax
        if self._append_jit is None:
            self._append_jit = jax.jit(append)
        self.note_append(self._append_jit(self.state, loss_out, grad_norm,
                                          loss_scale, overflow))

    # -------------------------------------------------------------- drain
    def _build_drain(self):
        import jax
        from jax.experimental import io_callback

        def _spool_drain_callback(buf, pos, *handed):
            try:
                self._deliver(np.asarray(buf), int(pos), handed)
            except Exception as e:  # pragma: no cover - defensive
                logger.warning("telemetry drain failed: %s", e)

        # graph-lint allowlist marker: this is the ONE sanctioned ordered
        # host transfer in the telemetry design — one batched callback per
        # report window, never per step (analysis/passes.py
        # ``transfer.spool-drain``)
        _spool_drain_callback._dstpu_spool_drain = True
        self.drain_callback = _spool_drain_callback

        def drain(state, *handed):
            io_callback(_spool_drain_callback, None,
                        state["buf"], state["pos"], *handed, ordered=True)
            return state["pos"]

        return jax.jit(drain)

    def drain_args(self, hand_over: bool = False):
        """The drain program's call tuple over THIS PROCESS's view of the
        state (:func:`fences.host_local_view`): the ring and, with a
        step-scalar channel, ``(totals, steps, micro_steps)`` — taken from
        the channel with ``hand_over`` (a real drain: the next step then
        starts from zeros), only looked at without (a lint's trace)."""
        args = ({k: fences.host_local_view(v)
                 for k, v in self.state.items()},)
        if self.scalars is None:
            return args
        totals, steps, micro = (self.scalars.hand_over() if hand_over
                                else (self.scalars.device, 0, 0))
        return args + (
            {k: fences.host_local_view(v) for k, v in totals.items()},
            np.int32(steps), np.int32(micro))

    def drain_program(self):
        """The jitted drain program (built lazily; exposed so the engine
        can graph-lint it — the allowlisted-callback path must actually be
        the one production dispatches)."""
        if self._drain_jit is None:
            self._drain_jit = self._build_drain()
        return self._drain_jit

    def drain_async(self) -> None:
        """Dispatch the drain program: the callback fires when the device
        has produced the window's buffer — the host does NOT wait.

        The drain runs over THIS PROCESS's view of the state
        (:func:`fences.host_local_view`): a multi-host fused step program
        returns the spool state globally replicated, and jitting the
        drain over a global array runs its ``io_callback`` on ONE process
        only — every other host would never deliver a window (found
        standing up fleet aggregation, PR 9; pinned by the
        ``fleet_straggler_watchdog`` distributed leg)."""
        self.drain_program()(*self.drain_args(hand_over=True))

    def _deliver(self, buf: np.ndarray, pos: int, handed=()) -> None:
        # delivery happens UNDER the lock: the counter update and the
        # on_window call are atomic, so windows reach the sinks exactly
        # once and in append order even when a flush and a late callback
        # race (no re-entry risk — sinks never call back into the spool)
        with self._lock:
            if handed:
                self.scalars.fold(*handed)
            n = pos - self._drained
            if n <= 0:
                return
            if n > self.window:
                # unreachable by design (drains run every window edge and
                # flush barriers the outstanding callbacks first) — but an
                # overrun must lose data LOUDLY, never slice garbage
                logger.error(
                    "telemetry spool overran: %d appends undelivered with "
                    "window %d — delivering the most recent %d",
                    n, self.window, self.window)
                n = self.window
            # general ring read (wrap-safe): append (pos - n + i) lives at
            # ring row (pos - n + i) % window
            idx = [(pos - n + i) % self.window for i in range(n)]
            self._drained = pos
            self._on_window(buf[idx], pos)

    def flush(self) -> None:
        """Synchronously drain whatever the ring holds past the last
        drain — THE one deliberate fence in the telemetry layer (run end /
        preemption drain; a partial final window must not be dropped).
        An async drain may be dispatched but its callback not yet run
        (blocking on the buffer only waits for the STEP that produced it,
        not for the drain program's effect), so flush first barriers all
        outstanding ordered callbacks — without it the undelivered window
        edge would make ``pos - drained`` exceed the ring."""
        import jax
        try:
            jax.effects_barrier()
        except Exception as e:  # pragma: no cover - defensive
            logger.warning("telemetry flush: effects barrier failed: %s", e)
        state, *handed = self.drain_args(hand_over=True)
        leaves, treedef = jax.tree_util.tree_flatten(handed)
        buf, pos, *leaves = fences.read_arrays(state["buf"], state["pos"],
                                               *leaves)
        self._deliver(buf, int(pos), treedef.unflatten(leaves))
