"""Step scalars: counts a model takes ON THE DEVICE, from its data, carried
out of the compiled step without a fence.

A ``dstpu/*`` scope (observability/scopes.py) says where the device's time
went; it cannot say which way the program decided.  An expert layer chooses
between its held-row prefix and the exact worst case in a ``lax.cond``
(models/moe.py): how often it overflowed, how many rows landed, how full the
busiest expert was, are numbers only the program has.  This module is the
one channel for such numbers:

* :data:`SCALARS` is THE table: ``name -> (reduction, unit, what it
  counts)``.  ``reduction`` is ``sum`` or ``max`` and means "over the
  layers of the model, over the micro-steps of an optimizer step, over the
  steps since the last read, and over every mesh axis on which the value
  differs" (``sum`` -> ``psum``, ``max`` -> ``pmax``; a mean is a ``sum``
  divided by a count at read time).  A ``max`` entry counts something that
  is never negative: its running value starts at 0.  Every entry has a
  reader (docs/observability.md names it); nothing else goes in.
* a model RETURNS its values beside its loss, ``WithScalars(loss, {name:
  value})`` — a type of its own, so the engine can tell it from a tuple of
  losses — and says which names it returns in ``step_scalars() -> {name:
  size}`` (1: a scalar; n: a short vector, one value per pass of a looped
  model).  Values made inside a ``lax.scan``, a ``jax.checkpoint`` or a
  ``lax.cond`` cannot leave through a side collector; they leave as
  results, or ride a scan's carry (:func:`combine` joins them on the way up).
  They are integers or ``stop_gradient``ed floats: no backward sees them.
* the engine packs them into ONE fp32 vector per reduction kind
  (:meth:`Channel.pack`), reduces it over the accumulation scan and then
  over the mesh (one collective per kind per step), and adds it to the
  DEVICE-SIDE TOTALS SINCE THE LAST READ, an operand and a result of the
  fused step (``analysis.train_batch_args``).  Nothing in the step program
  transfers to the host; a model that declares nothing has no channel, and
  its step program has not one operand, result or equation more.
* reading is never per step.  ``engine.read_step_scalars()`` /
  :meth:`Channel.read`: one counted fence, folds the device totals into
  host-side Python numbers held since ``initialize`` and hands the next
  step zeros.  With the metric spool on every window drain is such a read
  with no fence at all: the drain program's one batched callback is handed
  the totals beside the ring (observability/spool.py), and the window event
  carries them as ``scalars``.  The ``model`` group of the ``MetricRegistry``
  serves the host-side numbers to every sink.  :func:`snapshot` is for a
  trace reader with no engine in hand.

Exactness.  The device adds in fp32: a sum is exact while it stays under
2**24 = 16,777,216 (about 270 steps of ``moe/held_pairs`` at 61,440 a step;
an int32 would wrap at 2**31 instead of rounding).  "Since the last read" is
what keeps the totals exact: every read moves them into Python floats
(exact to 2**53) and starts the device again at zero.  A run that never
reads and has no spool rounds its sums after 2**24.
"""

from __future__ import annotations

import threading
import weakref
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.observability import fences

SUM, MAX = "sum", "max"
KINDS = (SUM, MAX)


class Scalar(NamedTuple):
    reduction: str
    unit: str
    counts: str
    #: the value differs from shard to shard of the ``model`` axis (each
    #: expert-parallel shard routes onto its own experts), so that axis is
    #: reduced over too; a value every ``model`` shard computes alike is not
    per_model_shard: bool = False


#: THE table of step scalars (docs/observability.md "Step scalars" names the
#: reader of each)
SCALARS = {
    "moe/overflow_passes": Scalar(
        SUM, "passes", "expert-layer forward passes whose held pairs did "
        "not fit the prefix, i.e. that took the worst-case branch over all "
        "(token, choice) pairs; 0 from a layer with no branch", True),
    "moe/held_pairs": Scalar(
        SUM, "pairs", "(token, choice) pairs that landed on the experts "
        "held, over the expert layers", True),
    "moe/max_expert_rows": Scalar(
        MAX, "rows", "rows of the busiest held expert in one expert-layer "
        "pass", True),
    "loop/exit_ce": Scalar(
        SUM, "nats", "mean cross-entropy of each exit of a looped model "
        "(one value per pass), summed over micro-steps and batch shards"),
    "loop/exit_prob": Scalar(
        SUM, "1", "mean probability of each exit (one value per pass), "
        "summed over micro-steps and batch shards"),
}


def _known(name: str) -> Scalar:
    if name not in SCALARS:
        raise KeyError(f"unknown step scalar {name!r}; the table in "
                       f"observability/scalars.py has {sorted(SCALARS)}")
    return SCALARS[name]


@jax.tree_util.register_pytree_node_class
class WithScalars:
    """What a model's ``apply`` returns when it has step scalars: its
    ``loss`` (a scalar, or whatever it returned without them) and
    ``scalars`` ``{name: value}``.  Not a tuple, on purpose: the engine
    reads a tuple as several losses."""

    def __init__(self, loss, scalars):
        self.loss, self.scalars = loss, dict(scalars)

    def tree_flatten(self):
        names = tuple(sorted(self.scalars))
        return (self.loss, [self.scalars[n] for n in names]), names

    @classmethod
    def tree_unflatten(cls, names, children):
        return cls(children[0], dict(zip(names, children[1])))

    def __repr__(self):
        return f"WithScalars(loss={self.loss!r}, scalars={self.scalars!r})"


def split(out):
    """``(loss, scalars or None)`` of what a model returned."""
    if isinstance(out, WithScalars):
        return out.loss, out.scalars
    return out, None


#: how each kind reduces an axis in the program, and joins two host vectors
_ACROSS = {SUM: jnp.sum, MAX: jnp.max}
_JOIN = {SUM: np.add, MAX: np.maximum}


def _reduce(name, values, axis=None):
    return _ACROSS[_known(name).reduction](values, axis=axis)


def combine(dicts):
    """One ``{name: value}`` of several (the layers of a period): each name
    reduced as the table says over the dicts that hold it."""
    names = {name for d in dicts for name in d}
    return {name: _reduce(name, jnp.stack([d[name] for d in dicts if name
                                           in d]), axis=0)
            for name in sorted(names)}


class Channel:
    """One engine's step scalars: the layout of the two vectors, the
    device-side totals since the last read, and the host-side numbers since
    ``initialize``.  Built by the engine for a model whose ``step_scalars()``
    names something; the trace-time half (:meth:`pack`, :meth:`over_steps`,
    :meth:`over_mesh`, :meth:`add`) is pure, the host half is locked (a
    spool drain folds from the runtime's callback thread)."""

    def __init__(self, declared: dict, *, batch_shards: int = 1,
                 model_shards: int = 1, place=None):
        if not declared:
            raise ValueError("a step-scalar channel needs a declared name")
        self.declared = {name: int(size) for name, size in declared.items()}
        for name in self.declared:
            _known(name)
        #: kind -> [(name, offset, size)], names in the table's order
        self.layout = {}
        for name in SCALARS:
            if name in self.declared:
                rows = self.layout.setdefault(SCALARS[name].reduction, [])
                offset = sum(size for _, _, size in rows)
                rows.append((name, offset, self.declared[name]))
        self._sizes = {kind: sum(size for _, _, size in rows)
                       for kind, rows in self.layout.items()}
        #: kind -> bool[n]: the entries that differ over ``model`` shards
        self._own = {kind: np.concatenate([
            np.full(size, SCALARS[name].per_model_shard)
            for name, _, size in rows]) for kind, rows in self.layout.items()}
        #: shards whose values a total sums: of the batch (data x sequence)
        #: for every name, of ``model`` too for a ``per_model_shard`` name
        self.batch_shards, self.model_shards = batch_shards, model_shards
        zeros = {kind: jnp.zeros((n,), jnp.float32)
                 for kind, n in self._sizes.items()}
        #: what a step is handed after a read: made once, placed like the
        #: step's own result (committed, replicated), never written
        self._zeros = place(zeros) if place else zeros
        self.device = self._zeros
        #: the ``model`` gauges of the fused program this engine runs
        #: (``routed_rows_prefix`` ...), for :func:`snapshot`'s readers
        self.gauges = {}
        self._lock = threading.Lock()
        self._pending = [0, 0]      # steps, micro-steps in ``device``
        self._handed = 0            # drains dispatched and not yet folded
        self.steps = self.micro_steps = 0
        #: host-side vectors, laid out like the device's: since
        #: ``initialize``, and since the last window event
        self._total = self._host_zeros()
        self._window = self._host_zeros()

    def _host_zeros(self):
        return {kind: np.zeros(n, np.float64)
                for kind, n in self._sizes.items()}

    # ------------------------------------------------ inside the program
    def pack(self, scalars) -> dict:
        """``{kind: f32[n]}`` of what the model returned this micro-step;
        raises, by name, on a name the table lacks, one the model did not
        declare, a declared one it left out, or another size."""
        scalars = scalars or {}
        for name in scalars:
            _known(name)
            if name not in self.declared:
                raise KeyError(
                    f"the model returned step scalar {name!r} but its "
                    f"step_scalars() declares {sorted(self.declared)}")
        out = {}
        for kind, rows in self.layout.items():
            parts = []
            for name, _, size in rows:
                if name not in scalars:
                    raise KeyError(f"the model declares step scalar "
                                   f"{name!r} and did not return it")
                v = jnp.asarray(scalars[name]).astype(jnp.float32)
                if v.size != size:
                    raise ValueError(
                        f"step scalar {name!r}: declared size {size}, "
                        f"returned shape {v.shape}")
                parts.append(v.reshape(size))
            out[kind] = jax.lax.stop_gradient(jnp.concatenate(parts))
        return out

    @staticmethod
    def over_steps(stacked: dict) -> dict:
        """The vectors of the micro-steps of one optimizer step, stacked
        ``[gas, n]`` by the accumulation scan, reduced."""
        return {kind: _ACROSS[kind](v, axis=0) for kind, v in stacked.items()}

    def over_mesh(self, vecs: dict, batch_axes, model_axis=None) -> dict:
        """One collective per kind: over ``batch_axes`` (the loss's), and
        over ``model_axis`` (None: a size of one) where an entry differs
        from shard to shard of it.  An entry that every ``model`` shard
        computes alike is counted from the first shard alone in a ``sum``;
        a ``max`` of equal values is that value."""
        axes = ((batch_axes,) if isinstance(batch_axes, str)
                else tuple(batch_axes))
        out = {}
        for kind, v in vecs.items():
            own, over = self._own[kind], axes
            if model_axis is not None and own.any():
                over = axes + (model_axis,)
                if kind == SUM and not own.all():
                    first = jax.lax.axis_index(model_axis) == 0
                    v = jnp.where(jnp.asarray(own) | first, v, 0.0)
            out[kind] = (jax.lax.psum if kind == SUM
                         else jax.lax.pmax)(v, over)
        return out

    @staticmethod
    def add(totals: dict, step: dict) -> dict:
        """The totals since the last read with one more step in them."""
        return {kind: (totals[kind] + step[kind] if kind == SUM
                       else jnp.maximum(totals[kind], step[kind]))
                for kind in totals}

    # ------------------------------------------------------ on the host
    def note_dispatch(self, totals, steps: int, micro_steps: int) -> None:
        """Adopt a step program's result: the totals with ``steps`` more
        optimizer steps in them (not read: a handle to a device array)."""
        with self._lock:
            self.device = totals
            self._pending[0] += steps
            self._pending[1] += micro_steps

    def hand_over(self):
        """``(totals, steps, micro_steps)`` for a reader that will
        :meth:`fold` them, and zeros for the next step.  Arrays are
        immutable: a step or a drain in flight keeps what it was given."""
        with self._lock:
            out = (self.device, *self._pending)
            self.device, self._pending = self._zeros, [0, 0]
            self._handed += 1
        return out

    def fold(self, totals, steps, micro_steps) -> None:
        """Add what :meth:`hand_over` gave (now host arrays) to the
        host-side numbers."""
        with self._lock:
            self._handed -= 1
            self.steps += int(steps)
            self.micro_steps += int(micro_steps)
            for kind in self.layout:
                vec = np.asarray(totals[kind], np.float64)
                for held in (self._total, self._window):
                    held[kind] = _JOIN[kind](held[kind], vec)

    def read(self) -> dict:
        """One counted fence (none where no step ran since the last read):
        the device totals folded into the host-side numbers, which are
        returned (:meth:`host`).  Waits first for a window drain that was
        handed totals and has not delivered them."""
        with self._lock:
            waiting, pending = self._handed, self._pending[0]
        if waiting:
            jax.effects_barrier()
        if pending:
            totals, steps, micro = self.hand_over()
            kinds = list(totals)
            arrays = fences.read_arrays(*(fences.host_local_view(totals[k])
                                          for k in kinds))
            self.fold(dict(zip(kinds, arrays)), steps, micro)
        return self.host()

    def _values(self, held):
        """``{name: number, or a list for a vector entry}`` of host vectors."""
        return {name: (float(held[kind][offset]) if size == 1
                       else held[kind][offset:offset + size].tolist())
                for kind, rows in self.layout.items()
                for name, offset, size in rows}

    def host(self) -> dict:
        """The host-side numbers since ``initialize``, no fence: ``{"steps",
        "micro_steps", "batch_shards", "model_shards", "values": {name:
        number, or a list for a vector entry}, "gauges"}``."""
        with self._lock:
            return {"steps": self.steps, "micro_steps": self.micro_steps,
                    "batch_shards": self.batch_shards,
                    "model_shards": self.model_shards,
                    "values": self._values(self._total),
                    "gauges": dict(self.gauges)}

    def take_window(self) -> dict:
        """``{name: value}`` folded since this was last called (the window
        event's ``scalars``), and start the next window."""
        with self._lock:
            out = self._values(self._window)
            self._window = self._host_zeros()
        return out

    def counters(self) -> dict:
        """The host-side numbers flat, ``{name: number}`` (a vector entry
        as ``name.0``, ``name.1`` ...), with the steps they cover: what the
        ``model`` group of the registry serves."""
        with self._lock:
            out = {"scalar_steps": self.steps,
                   "scalar_micro_steps": self.micro_steps}
            for name, v in self._values(self._total).items():
                if isinstance(v, list):
                    out.update({f"{name}.{i}": x for i, x in enumerate(v)})
                else:
                    out[name] = v
        return out


def declared_by(module) -> dict:
    """``{name: size}`` a model says it returns (``step_scalars()``); empty
    for a model without the method."""
    fn = getattr(module, "step_scalars", None)
    return dict(fn()) if callable(fn) else {}


#: the channel of the engine built last (None: it declared nothing)
_last_channel = None


def remember_channel(channel) -> None:
    """Called by every engine as it is built: a weak reference to its
    channel, or None.  ``scopes.remember_step`` keeps the engine of the
    last step program reachable for the same readers."""
    global _last_channel
    _last_channel = weakref.ref(channel) if channel is not None else None


def snapshot():
    """:meth:`Channel.read` of the engine built last, for a trace reader
    with no engine in hand (one counted fence, after the capture); None
    where that engine declared nothing or is gone."""
    channel = _last_channel() if _last_channel is not None else None
    return channel.read() if channel is not None else None
