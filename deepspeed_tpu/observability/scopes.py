"""Names inside the compiled step: ``dstpu/*`` device scopes and the map
that joins them to a device trace.

``jax.named_scope`` is trace-time metadata: it changes no value, no fusion
and no instruction count, and it reaches the compiled program as each HLO
instruction's ``metadata={op_name="jit(local)/.../dstpu/attn/dot_general"}``.
The profiler's device lines (``XLA Ops``) name an event by the instruction,
not by its ``op_name``, so a reader of a trace needs the map
``instruction name -> (scope, phase)`` the program hands out here
(:func:`step_scope_map`); XProf/Perfetto show the same ``op_name`` per event
for people (docs/observability.md).

Every scope name comes from :data:`SCOPES`; :func:`scope` refuses any other,
so a typo is an error and never a silent new scope.  A nested scope spells
its whole path again (``dstpu/boundary/update`` entered inside
``dstpu/boundary`` reads ``.../dstpu/boundary/dstpu/boundary/update/...``),
which makes the innermost scope of an ``op_name`` simply the LAST name of
the table found in it, wherever the call sites nest.

The phase is read off jax's own name stack (spellings of jax 0.9, pinned by
tests/test_step_scopes.py): ``transpose(`` marks the backward pass,
``rematted_computation`` the forward replayed inside a ``jax.checkpoint``
region during the backward (it sits under ``transpose(`` too, so it is
asked first), anything else is forward.

What the compiler makes itself carries no ``op_name`` — on a TPU the copy
loops, re-tilings and rewritten collectives of the ZeRO boundary, most of
that boundary's time: :func:`parse` places such an instruction with the data
it works on (its first scoped operand, else the instruction that calls its
computation) and never overrides an ``op_name`` that is there.
"""

from __future__ import annotations

import functools
import re

PREFIX = "dstpu/"

#: THE table of device scopes (docs/observability.md says where each sits)
SCOPES = (
    "embed", "block", "attn", "ffn", "norm", "head",
    "boundary", "boundary/reduce", "boundary/update", "boundary/gather",
    # a looped (weight-shared depth) model: the pass loop, the rotation of
    # q and k inside ``attn``, and the exit gate's part of the loss
    "loop", "rope", "exit",
    # a hybrid (state-space / attention) stack: the whole Mamba mixer with
    # its scan and its convolution inside, the windowed and the
    # cross-decoder attention cores inside ``attn``, and the Gated Memory
    # Unit (the hand-over of the memory and the shared keys and values is
    # slices of an axis of length one: no instruction, so no scope)
    "ssm", "scan", "conv", "swa", "xattn", "gmu",
    # a latent-attention / expert stack: the latent projections and the
    # latent's norm inside ``attn``; the whole expert layer, with its
    # routing (scores, top-k, gates, sort, the two gathers, balance loss)
    # and its grouped matmuls inside (the shared experts run under ``ffn``)
    "mla", "moe", "route", "experts",
    # a linear-attention / attention hybrid: the whole Gated DeltaNet mixer
    # (its convolution runs under ``conv``) and the chunked gated delta
    # rule inside it; the gated softmax attention is ``attn``
    "gdn", "delta",
    # a Mamba-2 / attention hybrid: the whole Mamba-2 mixer (its
    # convolution runs under ``conv``) and the chunked state-space duality
    # inside it
    "mamba2", "ssd",
)

FORWARD, BACKWARD, REPLAY = "forward", "backward", "replay"


def _known(name: str) -> str:
    if name not in SCOPES:
        raise KeyError(f"unknown device scope {name!r}; the table in "
                       f"observability/scopes.py has {SCOPES}")
    return name


def scope(name: str):
    """``jax.named_scope("dstpu/<name>")`` for a name of :data:`SCOPES`."""
    import jax
    return jax.named_scope(PREFIX + _known(name))


def scoped(name: str):
    """Decorator: the whole function runs under ``scope(name)``."""
    _known(name)

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            # looked up per call, so a test can patch ``scope`` away
            with scope(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco


# ------------------------------------------------------------- the map

#: longest first, so ``dstpu/boundary/update`` wins over ``dstpu/boundary``
#: where both start at one place
_SCOPE_IN_OP_NAME = re.compile("|".join(
    re.escape(PREFIX + s) + r"(?![A-Za-z0-9_])"
    for s in sorted(SCOPES, key=len, reverse=True)))
_INSTRUCTION = re.compile(r"\s*(?:ROOT\s+)?%?([^\s=(){},]+)\s+=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def classify(op_name: str):
    """``(scope, phase)`` of one ``op_name``: the innermost (last) table
    name in it, ``""`` where it holds none; the phase as the module
    docstring says."""
    found = _SCOPE_IN_OP_NAME.findall(op_name)
    if "rematted_computation" in op_name:
        phase = REPLAY
    elif "transpose(" in op_name:
        phase = BACKWARD
    else:
        phase = FORWARD
    return (found[-1] if found else ""), phase


#: ``%name`` of a computation an instruction calls: a loop's body and
#: condition, a call's or a reduction's callee, a conditional's branches, a
#: fusion's fused computation
_CALLEE = re.compile(r"(?:body|condition|to_apply|calls|true_computation|"
                     r"false_computation)=%?([^\s,(){}]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([^\s,(){}]+)")
_OPCODE = re.compile(r" [a-z][a-z0-9-]*\(")
#: "%body (p: f32[8]) -> f32[8] {", "ENTRY %main (...) -> ... {"
_COMPUTATION = re.compile(r"(?:ENTRY\s+)?%?(\S+)\s+\(")
_UNKNOWN = ("", "")


def _operands(line: str, start: int):
    """``(operand names, end of the operand list)`` of the instruction
    whose result shape starts at ``start``: what stands between the
    parentheses the opcode opens (a tuple shape has parentheses of its own
    before it, and shapes printed with the operands nest more)."""
    opcode = _OPCODE.search(line, start - 1)
    if not opcode:
        return [], start
    open_at = opcode.end() - 1
    depth, i = 0, open_at
    while 0 <= i < len(line):
        depth += {"(": 1, ")": -1}.get(line[i], 0)
        if depth == 0:
            break
        i += 1
    return _OPERAND.findall(line, open_at, max(i, open_at)), i


def _of_operands(out: dict, operands, otherwise):
    """What the first operand with a scope has; ``otherwise`` without one."""
    return next((out[o] for o in operands if out.get(o, _UNKNOWN)[0]),
                otherwise)


def parse(hlo_text: str) -> dict:
    """``{instruction_name: (scope, phase)}`` for every instruction of
    OPTIMIZED HLO text (``compiled.as_text()``).

    An instruction with an ``op_name`` in its own ``metadata`` is what that
    says (:func:`classify`).  A fusion carries the metadata of its root
    instruction — that is XLA's rule, and so a fusion counts whole under its
    root's scope.

    An instruction the compiler made itself has no metadata: the copies,
    re-tilings and slices around a collective, a reduce-scatter rewritten
    as all-reduce + slice, the loops it turns a large copy into.  Such an
    instruction is placed with the data it works on: it takes the scope and
    phase of the first of its operands that has a scope, and one whose
    operands have none takes those of the instruction that calls its
    computation (a loop's body and condition follow their ``while``).  What
    is left maps to ``("", "")``.  Instructions that have an ``op_name``
    outside every scope stay outside: nothing is inherited over metadata.

    Relies on the order ``as_text()`` prints in: operands before their
    users, called computations before their callers."""
    out, pending = {}, []          # pending: (name, operands, computation)
    caller, computation = {}, None
    for line in hlo_text.splitlines():
        if line[:1] not in (" ", "\t", ""):
            header = _COMPUTATION.match(line)
            if header:
                computation = header.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        operands, end = _operands(line, m.end())
        for callee in _CALLEE.findall(line, end):
            caller[callee] = name
        for group in _BRANCHES.findall(line, end):
            for callee in _OPERAND.findall(group):
                caller[callee] = name
        op = _OP_NAME.search(line, end)
        if op:
            out[name] = classify(op.group(1))
            continue
        out[name] = _of_operands(out, operands, _UNKNOWN)
        if not out[name][0]:
            pending.append((name, operands, computation))
    # callers come after their callees in the text: resolve back to front,
    # so a computation's caller is final before its instructions ask for it
    by_computation = {}
    for item in pending:
        by_computation.setdefault(item[2], []).append(item)
    for comp in reversed(list(by_computation)):
        inherited = out.get(caller.get(comp), _UNKNOWN)
        for name, operands, _ in by_computation[comp]:
            out[name] = _of_operands(out, operands, inherited)
    return out


#: the jitted step program ``train_batch`` last built and its arguments as
#: ``ShapeDtypeStruct``s — remembered, never lowered, until a reader asks
_last_step = None


def remember_step(fn, args) -> None:
    """Called by the engine when it builds ``train_batch``'s program:
    keeps the jitted function and the shapes, dtypes and shardings of its
    arguments.  No ``lower``, no ``compile``, no text.

    The function closes over its engine, so that engine — and the device
    memory of its state — stays reachable until the next program is
    remembered or :func:`forget_step` is called: a reader asks for the map
    after the capture, when the caller may hold the engine no more.  Code
    that drops an engine to free device memory calls :func:`forget_step`
    first."""
    import jax
    import numpy as np

    def abstract(x):
        # an uncommitted array goes where jit puts it: no sharding to pin;
        # neither has a tracer (a caller tracing ``train_batch`` itself)
        placed = (isinstance(x, jax.Array)
                  and not isinstance(x, jax.core.Tracer) and x.committed)
        sharding = x.sharding if placed else None
        dtype = jax.dtypes.canonicalize_dtype(
            getattr(x, "dtype", None) or np.result_type(x))
        return jax.ShapeDtypeStruct(np.shape(x), dtype, sharding=sharding)

    global _last_step
    _last_step = (fn, jax.tree_util.tree_map(abstract, args))


def forget_step() -> None:
    """Let go of the remembered step program (and so of its engine)."""
    global _last_step
    _last_step = None


#: any compiler option makes ``Lowered.compile`` compile THIS lowering anew
#: (jax then neither reuses the executable it holds nor finds the plain
#: entry of the persistent cache); this one changes nothing it produces
_COMPILE_ANEW = {"xla_embed_ir_in_executable": False}


def step_scope_map():
    """The scope map of the last step program an engine built (None before
    any was, or after :func:`forget_step`): the optimized HLO of the
    executable that runs, parsed.  Lowering again from the remembered
    shapes finds that executable in jax's own caches — no compile.

    One case compiles: jax's persistent-cache key leaves metadata out, so
    the executable that runs may have been compiled by a program WITHOUT
    these scopes (an older commit sharing the cache directory) and carry
    its ``op_name``s.  A step program always has ``dstpu/boundary``; where
    the text holds no scope at all, the map's copy is compiled anew from
    this program's lowering (seconds, this call only; XLA's instruction
    names do not follow the metadata, but for the few it takes from the
    name stack).  For trace readers, after the capture: the step path
    never calls this."""
    if _last_step is None:
        return None
    fn, args = _last_step
    lowered = fn.lower(*args)
    names = parse(lowered.compile().as_text())
    if not any(scope for scope, _ in names.values()):
        names = parse(lowered.compile(
            compiler_options=_COMPILE_ANEW).as_text())
    return names
