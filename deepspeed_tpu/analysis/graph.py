"""Jaxpr plumbing shared by the lint passes.

The passes never import jax internals beyond what this module wraps:

* :func:`subjaxprs` — discovery of nested jaxprs inside an
  equation (``scan``/``cond``/``jit``/``shard_map``/``remat``/custom-vjp all
  carry them under different param names; we scan every param value for
  jaxpr-shaped objects instead of hard-coding the names).
* :func:`walk` — flat recursive iteration over every equation with its
  jaxpr path (``"shard_map/scan"``).
* :func:`source_of` — "file:line (function)" of the Python frame an equation
  was traced from, so findings point at model/engine code.
* :func:`Taint` — forward dataflow marking: seed some vars (or the outputs
  of seed primitives), propagate through equations in order, with a hook to
  stop propagation (the precision pass stops at down-casts).
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

from jax._src import source_info_util as _srcinfo
from jax._src.core import Var


def is_var(x) -> bool:
    return isinstance(x, Var)


def _as_open_jaxpr(obj):
    """Jaxpr from a Jaxpr | ClosedJaxpr, else None."""
    if obj is None:
        return None
    if hasattr(obj, "eqns") and hasattr(obj, "invars"):
        return obj                       # already an open Jaxpr
    inner = getattr(obj, "jaxpr", None)  # ClosedJaxpr
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    return None


def subjaxprs(eqn) -> List[Tuple[str, object]]:
    """All nested jaxprs of one equation as ``(label, open_jaxpr)`` pairs.

    Labels are ``"<prim>"`` for a single sub-jaxpr and ``"<prim>.branchN"``
    when a param holds several (``cond`` branches).  Param values are probed
    structurally so new primitives keep working.
    """
    out: List[Tuple[str, object]] = []
    name = eqn.primitive.name
    for key, val in eqn.params.items():
        j = _as_open_jaxpr(val)
        if j is not None:
            out.append((name, j))
            continue
        if isinstance(val, (tuple, list)):
            js = [_as_open_jaxpr(v) for v in val]
            if js and all(x is not None for x in js):
                if len(js) == 1:
                    out.append((name, js[0]))
                else:
                    out.extend((f"{name}.branch{i}", x)
                               for i, x in enumerate(js))
    return out


def walk(jaxpr, path: str = "") -> Iterator[Tuple[object, str]]:
    """Yield ``(eqn, path)`` for every equation, depth-first, including all
    nested sub-jaxprs.  ``jaxpr`` may be open or closed."""
    j = _as_open_jaxpr(jaxpr)
    if j is None:
        return
    for eqn in j.eqns:
        yield eqn, path
        for label, sub in subjaxprs(eqn):
            sub_path = f"{path}/{label}" if path else label
            yield from walk(sub, sub_path)


def source_of(eqn) -> str:
    """Best-effort "file:line (function)" for an equation."""
    si = getattr(eqn, "source_info", None)
    return "" if si is None else _srcinfo.summarize(si)


def aval_of(atom):
    """The abstract value of a Var or Literal."""
    return getattr(atom, "aval", None)


def dtype_of(atom):
    aval = aval_of(atom)
    return getattr(aval, "dtype", None)


def size_of(atom) -> int:
    aval = aval_of(atom)
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    n = 1
    for d in shape:
        try:
            n *= int(d)
        except Exception:   # symbolic dims: treat as big
            return 1 << 62
    return n


class Taint:
    """Forward dataflow taint over one jaxpr level.

    Marked vars are tracked by identity.  Use :meth:`step` on each equation
    in program order; it marks the outputs when any input is marked (unless
    ``stop(eqn)`` says the equation launders the taint) and returns whether
    any input was marked.  Sub-jaxpr seeding: :meth:`seed_sub` maps the
    marking of an equation's invars onto a nested jaxpr's invars
    (tail-aligned, which matches scan/cond/pjit/shard_map operand layout
    closely enough for lint purposes).
    """

    def __init__(self, marked: Optional[set] = None):
        self.marked = set(marked or ())

    def mark(self, var) -> None:
        if is_var(var):
            self.marked.add(var)

    def is_marked(self, atom) -> bool:
        return is_var(atom) and atom in self.marked

    def any_marked(self, atoms: Sequence) -> bool:
        return any(self.is_marked(a) for a in atoms)

    def step(self, eqn, stop=None) -> bool:
        hit = self.any_marked(eqn.invars)
        if hit and not (stop is not None and stop(eqn)):
            for v in eqn.outvars:
                self.mark(v)
        return hit

    def seed_sub(self, eqn, sub_jaxpr) -> "Taint":
        sub = _as_open_jaxpr(sub_jaxpr)
        sub_in = list(sub.invars)
        outer_in = list(eqn.invars)
        t = Taint()
        # tail-align: scan prepends consts, cond prepends the predicate —
        # in both cases the trailing operands line up positionally
        k = min(len(sub_in), len(outer_in))
        for sv, ov in zip(sub_in[len(sub_in) - k:],
                          outer_in[len(outer_in) - k:]):
            if self.is_marked(ov):
                t.mark(sv)
        return t

    def propagate_out(self, eqn, sub_jaxpr, sub_taint: "Taint") -> None:
        """Carry a sub-jaxpr's output marking back onto the equation's
        outvars (tail-aligned, like :meth:`seed_sub`), so taint computed
        inside cond/scan/pjit bodies survives into the enclosing level."""
        sub = _as_open_jaxpr(sub_jaxpr)
        sub_out = list(sub.outvars)
        outer_out = list(eqn.outvars)
        k = min(len(sub_out), len(outer_out))
        for sv, ov in zip(sub_out[len(sub_out) - k:],
                          outer_out[len(outer_out) - k:]):
            if sub_taint.is_marked(sv):
                self.mark(ov)


class AxisTaint:
    """Per-axis rank-dependence tracking for the collective pass.

    Each var maps to the set of mesh axes whose *rank identity* its value
    depends on: ``axis_index(a)`` seeds ``{a}``, ordinary equations union
    their inputs' sets, and a full-axis reduction (``psum``/``pmax``/... with
    ``axis_index_groups=None``) REMOVES the reduced axes — its result is
    replicated over them, so a predicate built from it cannot diverge
    (the global-vote pattern: ``cond(psum(flag) > 0, ...)`` is uniform).
    """

    def __init__(self):
        self.axes = {}            # Var -> frozenset of axis names

    def mark(self, var, axes) -> None:
        if is_var(var) and axes:
            self.axes[var] = frozenset(self.axes.get(var, frozenset())
                                       | frozenset(axes))

    def axes_of(self, atom) -> frozenset:
        if is_var(atom):
            return self.axes.get(atom, frozenset())
        return frozenset()

    def union_in(self, eqn) -> frozenset:
        out = frozenset()
        for a in eqn.invars:
            out |= self.axes_of(a)
        return out

    def step(self, eqn, removed=()) -> None:
        axes = self.union_in(eqn) - frozenset(removed)
        for v in eqn.outvars:
            self.mark(v, axes)

    def seed_sub(self, eqn, sub_jaxpr) -> "AxisTaint":
        sub = _as_open_jaxpr(sub_jaxpr)
        sub_in = list(sub.invars)
        outer_in = list(eqn.invars)
        t = AxisTaint()
        k = min(len(sub_in), len(outer_in))
        for sv, ov in zip(sub_in[len(sub_in) - k:],
                          outer_in[len(outer_in) - k:]):
            t.mark(sv, self.axes_of(ov))
        return t

    def propagate_out(self, eqn, sub_jaxpr, sub_taint: "AxisTaint") -> None:
        sub = _as_open_jaxpr(sub_jaxpr)
        sub_out = list(sub.outvars)
        outer_out = list(eqn.outvars)
        k = min(len(sub_out), len(outer_out))
        for sv, ov in zip(sub_out[len(sub_out) - k:],
                          outer_out[len(outer_out) - k:]):
            self.mark(ov, sub_taint.axes_of(sv))
