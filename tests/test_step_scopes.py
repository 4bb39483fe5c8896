"""Device scopes inside the step program (observability/scopes.py).

* ``parse``/``classify`` on hand-written optimized-HLO lines;
* the spellings jax 0.9 gives the phases, pinned from a tiny compile;
* tiny BERT and GPT-2, ZeRO-0 on one device and ZeRO-1 on four: the compiled
  step's map holds every scope of the table, every collective of the
  boundary lies under ``dstpu/boundary``, and the scopes move no value and
  no structure (losses and instruction counts identical with every scope a
  ``nullcontext``);
* with tracing off the step path only REMEMBERS the program: it never builds
  the map and asks the compiler for nothing more.

Each tiny engine is compiled once per module, in a fixture.
"""

import collections
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu import analysis
from deepspeed_tpu.observability import scopes
from deepspeed_tpu.parallel.topology import make_mesh
from deepspeed_tpu.resilience import COUNTERS
from deepspeed_tpu.utils import compile_cache

L = "{1,0:T(8,128)(2,1)}"
P = "jit(local)/jvp(dstpu/head)"                 # a prefix jax really gives
B = "jit(local)/dstpu/boundary/dstpu/boundary"
HLO = f"""\
HloModule jit_local, entry_computation_layout={{(f32[8]{{0}})->f32[8]{{0}}}}

%fused_computation.1 (param_0: bf16[8,128]) -> bf16[8,128] {{
  %param_0 = bf16[8,128]{L} parameter(0)
  ROOT %multiply.5 = bf16[8,128]{L} multiply(%param_0, %param_0), metadata={{op_name="jit(local)/while/body/closed_call/dstpu/block/dstpu/ffn/mul" source_file="t.py" source_line=3}}
}}

%wide.body (wide.p: (u32[], f32[40], f32[1,4,10])) -> (u32[], f32[40], f32[1,4,10]) {{
  %wide.p = (u32[]{{:T(128)}}, f32[40]{{0}}, f32[1,4,10]{{2,1,0}}) parameter(0)
  %get-tuple-element.3 = f32[40]{{0}} get-tuple-element(%wide.p), index=1
  %dynamic-slice.33 = f32[10]{{0}} dynamic-slice(%get-tuple-element.3, %c), dynamic_slice_sizes={{10}}
  ROOT %dynamic-update-slice.86 = f32[1,4,10]{{2,1,0}} dynamic-update-slice(%dynamic-slice.33, %c)
}}

%wide.cond (wide.q: (u32[], f32[40], f32[1,4,10])) -> pred[] {{
  %wide.q = (u32[]{{:T(128)}}, f32[40]{{0}}, f32[1,4,10]{{2,1,0}}) parameter(0)
  ROOT %compare.1 = pred[] compare(%wide.q, %wide.q), direction=LT
}}

ENTRY %main.9 (p: bf16[8,128]) -> bf16[8,128] {{
  %p = bf16[8,128]{L} parameter(0), metadata={{op_name="params[\\'wte\\']"}}
  %copy.1 = bf16[8,128]{{0,1:T(8,128)(2,1)}} copy(%p)
  %fusion.1 = bf16[8,128]{L} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(local)/while/body/closed_call/dstpu/block/dstpu/ffn/mul"}}
  %closed_call.3 = (bf16[16,128,64]{{2,1,0}}, f32[16,1,128]{{2,1,0}}) custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={{op_name="jit(local)/jvp()/while/body/closed_call/dstpu/block/dstpu/attn/pallas_call"}}
  %rematted_computation.4 = (bf16[16,128,64]{{2,1,0}}, f32[16,1,128]{{2,1,0}}) custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={{op_name="jit(local)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/dstpu/block/dstpu/attn/pallas_call"}}
  %checkpoint.5 = (bf16[16,128,64]{{2,1,0}}) custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={{op_name="jit(local)/transpose(jvp())/while/body/closed_call/checkpoint/dstpu/block/dstpu/attn/pallas_call"}}
  %while.2 = (s32[], bf16[8,128]{L}) while(%tuple.1), condition=%cond, body=%body, metadata={{op_name="jit(local)/jvp()/while"}}
  %copy.7 = bf16[8,128]{L} copy(%checkpoint.5)
  %concatenate.18 = f32[40]{{0}} concatenate(%copy.7, %copy.7), dimensions={{0}}, metadata={{op_name="{B}/reduce/dstpu/boundary/concatenate"}}
  %broadcast.314 = f32[1,4,10]{{2,1,0}} broadcast(%constant.1), dimensions={{}}
  %tuple.250 = (u32[]{{:T(128)}}, f32[40]{{0}}, f32[1,4,10]{{2,1,0}}) tuple(%constant.1, %concatenate.18, %broadcast.314)
  %while.65 = (u32[]{{:T(128)}}, f32[40]{{0}}, f32[1,4,10]{{2,1,0}}) while(%tuple.250), condition=%wide.cond, body=%wide.body
  %fusion.208 = f32[4,10]{{1,0}} fusion(%while.65), kind=kLoop, calls=%fc, metadata={{op_name="{B}/reduce/slice"}}
  %all-reduce.8 = f32[4,10]{{1,0}} all-reduce(%fusion.208), replica_groups={{{{0,1}}}}, to_apply=%add
  %convert_reduce_fusion.17 = (f32[32,128]{{1,0}}, bf16[32,128,1024]{{2,1,0}}) fusion(%p), kind=kOutput, calls=%fd, metadata={{op_name="{P}/dstpu/norm/reduce_sum"}}
  ROOT %fusion.7 = bf16[8,128]{L} fusion(%all-reduce.8), kind=kLoop, calls=%fe, metadata={{op_name="{B}/update/mul"}}
}}
"""


def test_parse_reads_op_names_and_places_what_the_compiler_made():
    got = scopes.parse(HLO)
    assert got == {
        # a fusion carries its root's metadata, and counts whole under it;
        # what is inside never is an event of its own (it follows the fusion)
        "fusion.1": ("dstpu/ffn", "forward"),
        "multiply.5": ("dstpu/ffn", "forward"),
        "param_0": ("dstpu/ffn", "forward"),
        # an argument and the compiler's copy of it: outside every scope
        "p": ("", "forward"),
        "copy.1": ("", ""),
        # the three Pallas calls of one layer: forward, replay, backward
        "closed_call.3": ("dstpu/attn", "forward"),
        "rematted_computation.4": ("dstpu/attn", "replay"),
        "checkpoint.5": ("dstpu/attn", "backward"),
        # a scan outside every scope keeps its own (scopeless) op_name
        "while.2": ("", "forward"),
        # no metadata: placed with the first operand that has a scope
        "copy.7": ("dstpu/attn", "backward"),
        # the flatten called from inside boundary/reduce: the innermost
        # (last) scope of the name stack is the plain boundary again
        "concatenate.18": ("dstpu/boundary", "forward"),
        "broadcast.314": ("", ""),
        "tuple.250": ("dstpu/boundary", "forward"),
        # a copy the compiler turned into a loop: the while follows its
        # operand; body and condition follow the while that calls them,
        # unless an operand inside already says more
        "while.65": ("dstpu/boundary", "forward"),
        "wide.p": ("dstpu/boundary", "forward"),
        "get-tuple-element.3": ("dstpu/boundary", "forward"),
        "dynamic-slice.33": ("dstpu/boundary", "forward"),
        "dynamic-update-slice.86": ("dstpu/boundary", "forward"),
        "wide.q": ("dstpu/boundary", "forward"),
        "compare.1": ("dstpu/boundary", "forward"),
        "fusion.208": ("dstpu/boundary/reduce", "forward"),
        # a reduce-scatter rewritten as slice + all-reduce lost its metadata
        "all-reduce.8": ("dstpu/boundary/reduce", "forward"),
        "convert_reduce_fusion.17": ("dstpu/norm", "forward"),
        "fusion.7": ("dstpu/boundary/update", "forward"),
    }


@pytest.mark.parametrize("op_name,want", [
    ("jit(local)/jvp(dstpu/embed)/dstpu/norm/rsqrt",
     ("dstpu/norm", "forward")),
    ("jit(local)/transpose(jvp(dstpu/head))/dstpu/norm/mul",
     ("dstpu/norm", "backward")),
    ("jit(local)/while/body/transpose(jvp())/while/body/closed_call/"
     "checkpoint/rematted_computation/dstpu/block/add",
     ("dstpu/block", "replay")),
    # the primitive `transpose` is not the transform `transpose(`
    ("jit(local)/jvp()/dstpu/block/dstpu/attn/transpose",
     ("dstpu/attn", "forward")),
    # a name that only starts like a scope of the table is none
    ("jit(local)/dstpu/boundary_extra/add", ("", "forward")),
    ("jit(local)/dstpu/boundary/dstpu/boundary/gather/all_gather",
     ("dstpu/boundary/gather", "forward")),
])
def test_classify(op_name, want):
    assert scopes.classify(op_name) == want


def test_an_unknown_scope_is_an_error():
    with pytest.raises(KeyError, match="optimiser"):
        scopes.scope("optimiser")
    with pytest.raises(KeyError, match="boundary/updat"):
        scopes.scoped("boundary/updat")
    assert all(scopes.scope(name) is not None for name in scopes.SCOPES)


def test_the_phases_are_spelled_as_this_jax_spells_them():
    """``transpose(`` for the backward pass and ``rematted_computation``
    for the replay are jax's spellings, not ours: a jax that renames them
    must fail here and not silently report every phase as forward."""
    def body(c, w):
        with scopes.scope("block"):
            return c + jnp.tanh(c @ w), None

    def loss(ws, x):
        y, _ = jax.lax.scan(jax.checkpoint(body), x, ws)
        with scopes.scope("head"):
            return jnp.sum(y ** 2)

    text = jax.jit(jax.grad(loss)).lower(
        jnp.ones((3, 8, 8)), jnp.ones((4, 8))).compile().as_text()
    found = set(scopes.parse(text).values())
    assert {("dstpu/block", "forward"), ("dstpu/block", "replay"),
            ("dstpu/block", "backward"), ("dstpu/head", "forward")} <= found
    assert ("dstpu/head", "replay") not in found


# ------------------------------------------------------------ tiny engines

TINY = dict(vocab_size=64, max_seq_len=16, num_layers=2, hidden_size=32,
            num_heads=2)
CASES = ["bert-zero0-1dev", "bert-zero1-4dev", "gpt2-zero0-1dev",
         "gpt2-zero1-4dev"]


def build(case):
    """(engine, batch) of one case; the batch's rows scale with the mesh."""
    family, zero, devs = case.split("-")
    n = int(devs[0])
    gas = 2 if family == "bert" else 1
    rows = 4 * gas * n
    rng = np.random.default_rng(7)
    ids = rng.integers(0, 64, size=(rows, 16)).astype(np.int32)
    if family == "bert":
        from deepspeed_tpu.models.bert import BertForPreTraining
        model = BertForPreTraining.from_size(
            "tiny", **TINY, remat_policy="selective")
        pos = np.sort(rng.permuted(np.tile(np.arange(16), (rows, 1)),
                                   axis=1)[:, :3], axis=1).astype(np.int32)
        batch = (ids, np.ones_like(ids), np.zeros_like(ids), pos,
                 np.take_along_axis(ids, pos, 1),
                 np.ones(pos.shape, np.float32))
    else:
        from deepspeed_tpu.models.gpt2 import GPT2
        model = GPT2.from_size("tiny", **TINY)
        batch = (ids, ids)
    config = {"train_batch_size": rows, "gradient_accumulation_steps": gas,
              "steps_per_print": 10 ** 9, "bf16": {"enabled": True},
              "gradient_clipping": 1.0,
              "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}
    if zero == "zero1":
        config["zero_optimization"] = {"stage": 1}
    engine, _, _, _ = ds.initialize(
        model=model, config=config, mesh=make_mesh(devices=jax.devices()[:n]))
    return engine, batch


def run(case):
    """Two steps; (losses, optimized HLO text of the step program)."""
    engine, batch = build(case)
    losses = [float(engine.train_batch(batch)) for _ in range(2)]
    text = engine._train_batch_fn.lower(
        *analysis.train_batch_args(engine, batch)).compile().as_text()
    return losses, text


@pytest.fixture(scope="module")
def scoped_runs():
    """Each case once, with the scopes on; its map through the module's own
    path (the program remembered by ``train_batch``)."""
    out = {}
    for case in CASES:
        losses, text = run(case)
        out[case] = (losses, text, scopes.step_scope_map())
    return out


@pytest.mark.parametrize("case", CASES)
def test_the_step_holds_every_scope_of_the_table(scoped_runs, case):
    _, text, names = scoped_runs[case]
    assert names == scopes.parse(text)     # the remembered program is it
    found = {scope for scope, _ in names.values() if scope}
    # the pass loop, the rotary rotation and the exit gate are a looped
    # model's (tests/test_looped_model.py finds them in its step); the
    # state-space mixer, the windowed and cross-decoder cores, the Gated
    # Memory Unit and the hand-over a hybrid stack's, the latent
    # projections and the expert layer's three a latent-attention / expert
    # stack's, the Gated DeltaNet mixer and its delta rule a linear-attention
    # hybrid's, the Mamba-2 mixer and its state-space duality a Mamba-2
    # hybrid's (all below)
    want = {scopes.PREFIX + s for s in scopes.SCOPES} - {
        "dstpu/loop", "dstpu/rope", "dstpu/exit"} - HYBRID_SCOPES - (
            LATENT_MOE_SCOPES) - DELTA_MOE_SCOPES - SSD_HYBRID_SCOPES
    if "zero0" in case:
        # nothing to gather: the cast to the compute dtype is the update's
        # last instruction there, and under its scope
        want.remove("dstpu/boundary/gather")
    assert found == want
    phases = {(s, p) for s, p in names.values() if s}
    for scope in ("dstpu/attn", "dstpu/ffn", "dstpu/norm"):
        assert {(scope, "forward"), (scope, "replay"),
                (scope, "backward")} <= phases
    assert ("dstpu/head", "backward") in phases
    # the boundary is no part of the differentiated program
    assert {p for s, p in phases if s.startswith("dstpu/boundary")} == {
        "forward"}


HYBRID_SCOPES = {"dstpu/ssm", "dstpu/scan", "dstpu/conv", "dstpu/swa",
                 "dstpu/xattn", "dstpu/gmu"}


def hybrid_step_map(policy):
    from deepspeed_tpu.models import HybridLM
    engine, _, _, _ = ds.initialize(
        model=HybridLM.from_size("tiny"),
        mesh=make_mesh(devices=jax.devices()[:1]),
        config={"train_batch_size": 2, "steps_per_print": 10 ** 9,
                "bf16": {"enabled": True}, "gradient_clipping": 1.0,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "activation_checkpointing": {"enabled": True,
                                             "policy": policy}})
    doc = np.random.default_rng(7).integers(0, 512, size=(2, 33),
                                            dtype=np.int32)
    engine.train_batch((doc[:, :-1].copy(), doc[:, 1:].copy()))
    return scopes.step_scope_map()


def test_a_hybrid_step_holds_its_scopes_in_every_phase():
    """A ``HybridLM`` step: the Mamba mixer with its scan and convolution,
    the windowed and the cross-decoder attention cores and the Gated Memory
    Unit run forward, replayed and backward.  (The hand-over of the memory
    and the shared keys and values out of the source segment's scan is
    slices of an axis of length one, which the compiler turns into
    bitcasts: it costs no instruction and has no scope.)  The scan's backward runs each block of
    steps again beside its backward steps (``replay`` under either policy);
    ``selective`` keeps the scan's output and boundary states, so it replays
    a whole forward scan less than ``full``."""
    count = {}
    for policy in ("full", "selective"):
        names = hybrid_step_map(policy)
        phases = {(s, p) for s, p in names.values() if s}
        for scope in sorted(HYBRID_SCOPES):
            assert {(scope, "forward"), (scope, "replay"),
                    (scope, "backward")} <= phases, (policy, scope)
        assert not {"dstpu/loop", "dstpu/rope", "dstpu/exit"} & {
            s for s, _ in phases}
        count[policy] = {
            phase: sum(1 for v in names.values()
                       if v == ("dstpu/scan", phase))
            for phase in ("forward", "replay")}
    assert HYBRID_SCOPES <= {scopes.PREFIX + s for s in scopes.SCOPES}
    assert (count["full"]["replay"] - count["selective"]["replay"]
            > 0.5 * count["full"]["forward"])


LATENT_MOE_SCOPES = {"dstpu/mla", "dstpu/moe", "dstpu/route",
                     "dstpu/experts"}


def latent_moe_step_map(policy):
    from deepspeed_tpu.models import LatentMoELM
    engine, _, _, _ = ds.initialize(
        model=LatentMoELM.from_size("tiny", experts_held=(4, 4)),
        mesh=make_mesh(devices=jax.devices()[:1]),
        config={"train_batch_size": 2, "steps_per_print": 10 ** 9,
                "bf16": {"enabled": True}, "gradient_clipping": 1.0,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "activation_checkpointing": {"enabled": True,
                                             "policy": policy}})
    doc = np.random.default_rng(7).integers(0, 512, size=(2, 65),
                                            dtype=np.int32)
    engine.train_batch((doc[:, :-1].copy(), doc[:, 1:].copy()))
    return scopes.step_scope_map(), engine


@pytest.mark.parametrize("policy", ["full", "selective"])
def test_a_latent_attention_expert_step_holds_its_four_scopes(policy):
    """A ``LatentMoELM`` step: the latent projections (``mla``, inside
    ``attn``), the expert layer's own glue (``moe``), its routing
    (``route``) and its grouped matmuls (``experts``) run forward and
    backward — the custom backward passes of the two gathers under
    ``route`` too — and replayed.  The rotation keeps ``rope``, the shared
    experts and the dense MLP ``ffn``."""
    names, engine = latent_moe_step_map(policy)
    phases = {(s, p) for s, p in names.values() if s}
    for scope in sorted(LATENT_MOE_SCOPES):
        assert {(scope, "forward"), (scope, "backward")} <= phases, scope
    assert {("dstpu/rope", "forward"), ("dstpu/ffn", "forward"),
            ("dstpu/attn", "forward"), ("dstpu/head", "backward")} <= phases
    assert LATENT_MOE_SCOPES <= {scopes.PREFIX + s for s in scopes.SCOPES}
    replayed = {s for s, p in phases if p == "replay"}
    # the layer's own glue, one sum, feeds no gradient: never replayed;
    # the rest is, under either policy — ``selective`` replays the sort,
    # the latent's norm and the experts' activation, no product (the jaxpr
    # counts them: tests/test_latent_moe_model.py)
    assert {"dstpu/mla", "dstpu/route", "dstpu/experts"} <= replayed
    assert not {"dstpu/ssm", "dstpu/loop", "dstpu/exit"} & {
        s for s, _ in phases}
    group = engine._telemetry.registry.collect()["model"]
    # the step scalars beside the gauges: host-side numbers, so nothing of
    # the one step dispatched until somebody reads (no fence on this path)
    counted = {k: group.pop(k) for k in list(group)
               if k.startswith(("moe/", "scalar_"))}
    assert counted == {"scalar_steps": 0, "scalar_micro_steps": 0,
                       "moe/overflow_passes": 0.0, "moe/held_pairs": 0.0,
                       "moe/max_expert_rows": 0.0}
    read = engine.read_step_scalars()
    assert read["steps"] == 1 and read["values"]["moe/overflow_passes"] == 0
    # about a quarter of 2 layers x 384 pairs landed on 4 of 16 experts
    assert 100 < read["values"]["moe/held_pairs"] < 2 * 256
    assert group == {
        "layers_dense": 1, "layers_moe": 2, "experts_total": 16,
        "experts_held": 4, "experts_per_token": 3, "latent_rank": 32,
        "qk_head_dim": 32, "v_head_dim": 16,
        # 2 x 64 tokens x 3 choices; the share's prefix, written when the
        # step program was traced
        "routed_rows_prefix": 256, "routed_rows_all": 384,
        "layer_applications_per_step": 3}


_COLLECTIVE = re.compile(
    r"%?(\S+) = (\S+) (all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start)?\(")


@pytest.mark.parametrize("case", [c for c in CASES if c.endswith("4dev")])
def test_the_boundarys_collectives_lie_under_the_boundary(scoped_runs, case):
    """Every collective that crosses devices lies under a part of
    ``dstpu/boundary``, but for the mean of the reported loss (a scalar,
    model side, under no scope).  Collectives over the model axis have one
    participant here and are the model's own."""
    _, text, names = scoped_runs[case]
    kinds = set()
    for line in text.splitlines():
        m = _COLLECTIVE.match(line.strip().removeprefix("ROOT "))
        if not m or "replica_groups={{0}," in line:
            continue
        name, shape, kind = m.group(1), m.group(2), m.group(3)
        scope = names[name][0]
        if not scope:
            assert shape.startswith("f32[]"), line
            continue
        assert scope.startswith("dstpu/boundary/"), line
        kinds.add((kind, scope))
    assert ("all-gather", "dstpu/boundary/gather") in kinds
    assert {s for k, s in kinds if k != "all-gather"} == {
        "dstpu/boundary/reduce"}


def opcodes(text):
    """How many instructions of each opcode the HLO text holds."""
    found = (re.search(r" ([a-z][a-z0-9-]*)\(", line.partition(" = ")[2])
             for line in text.splitlines() if " = " in line)
    return collections.Counter(m.group(1) for m in found if m)


@pytest.mark.parametrize("case", CASES)
def test_scopes_move_no_value_and_no_structure(scoped_runs, case,
                                               monkeypatch):
    losses, text, names = scoped_runs[case]
    monkeypatch.setattr(scopes, "scope",
                        lambda name: contextlib.nullcontext())
    plain_losses, plain_text = run(case)
    plain = scopes.parse(plain_text)
    assert not any(scope for scope, _ in plain.values())
    assert plain_losses == losses                       # bit for bit
    # the same instructions: as many, and as many of each opcode (XLA takes
    # some instruction NAMES from the op_name, so those may differ)
    assert len(plain) == len(names)
    assert opcodes(plain_text) == opcodes(text)


def test_tracing_off_only_remembers_the_program(tmp_path, monkeypatch):
    """An untraced ``train_batch`` loop never builds the map, and asks the
    compiler for exactly what it asks for with ``remember_step`` taken
    out (compile requests = persistent-cache hits + misses)."""
    called = []
    monkeypatch.setattr(scopes, "step_scope_map",
                        lambda: called.append(1))

    def requests_of_three_steps():
        jax.clear_caches()
        before = COUNTERS.compile_cache_hits + COUNTERS.compile_cache_misses
        engine, batch = build("gpt2-zero0-1dev")
        for _ in range(3):
            engine.train_batch(batch).block_until_ready()
        return (COUNTERS.compile_cache_hits + COUNTERS.compile_cache_misses
                - before)

    compile_cache.enable(str(tmp_path / "cc"))
    try:
        with_memory = requests_of_three_steps()
        assert scopes._last_step is not None
        monkeypatch.setattr(scopes, "remember_step", lambda fn, args: None)
        without = requests_of_three_steps()
    finally:
        compile_cache.disable()
    assert with_memory == without > 0
    assert not called


def test_a_cache_entry_of_a_scopeless_program_does_not_blind_the_map(
        tmp_path, monkeypatch):
    """jax's persistent-cache key leaves metadata out: a program without the
    scopes (the parent commit, on a machine that shares the cache) leaves
    an executable that this program then runs, ``op_name``s and all.  The
    map is then made from a compile of this program's own lowering."""
    case = "gpt2-zero0-1dev"
    compile_cache.enable(str(tmp_path / "cc"))
    try:
        with monkeypatch.context() as patched:
            patched.setattr(scopes, "scope",
                            lambda name: contextlib.nullcontext())
            engine, batch = build(case)
            engine.train_batch(batch).block_until_ready()
        del engine
        jax.clear_caches()
        hits = COUNTERS.compile_cache_hits
        engine, batch = build(case)
        engine.train_batch(batch).block_until_ready()
        assert COUNTERS.compile_cache_hits > hits      # the stale executable
        stale = engine._train_batch_fn.lower(
            *analysis.train_batch_args(engine, batch)).compile().as_text()
        assert "dstpu/" not in stale
        names = scopes.step_scope_map()
    finally:
        compile_cache.disable()
    assert {s for s, _ in names.values()} >= {
        "dstpu/attn", "dstpu/boundary/update", "dstpu/head"}
    assert set(names) == set(scopes.parse(stale))     # the same instructions


def test_remembering_takes_tracers_and_host_values(monkeypatch):
    """``benchmark.aot_fit`` traces ``train_batch`` itself, so the arguments
    may be tracers; a batch is ``numpy``; an uncommitted array pins no
    sharding."""
    monkeypatch.setattr(scopes, "_last_step", None)
    fn = jax.jit(lambda a, b: a + b[0])
    placed = jax.device_put(jnp.ones(3), jax.devices()[1])

    def outer(t):
        scopes.remember_step(fn, (t, (np.ones(3, np.int64), 2.0)))
        return t

    jax.jit(outer)(jnp.ones(3, jnp.bfloat16))
    (t, (host, scalar)) = scopes._last_step[1]
    assert (t.shape, t.dtype, t.sharding) == ((3,), jnp.bfloat16, None)
    assert (host.dtype, scalar.shape) == (np.int32, ())
    scopes.remember_step(fn, (placed, (jnp.ones(3),)))
    (a, (b,)) = scopes._last_step[1]
    assert a.sharding == placed.sharding and b.sharding is None
    assert scopes.step_scope_map() is not None
    scopes.forget_step()
    assert scopes.step_scope_map() is None


DELTA_MOE_SCOPES = {"dstpu/gdn", "dstpu/delta"}


def test_a_gated_deltanet_step_holds_its_two_scopes(policy="full"):
    """A ``DeltaMoELM`` step: the Gated DeltaNet mixer (``gdn``) and the
    chunked gated delta rule inside it (``delta``) run forward and backward;
    the convolution keeps ``conv``, the gated attention ``attn`` with its
    rotation under ``rope``, the expert layer ``moe`` / ``route`` /
    ``experts`` and the shared expert ``ffn``.  Under ``full`` (the cell's
    policy) the rule's forward is replayed beside its backward."""
    from deepspeed_tpu.models import DeltaMoELM
    engine, _, _, _ = ds.initialize(
        model=DeltaMoELM.from_size("tiny", experts_held=(4, 4)),
        mesh=make_mesh(devices=jax.devices()[:1]),
        config={"train_batch_size": 2, "steps_per_print": 10 ** 9,
                "bf16": {"enabled": True}, "gradient_clipping": 1.0,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "activation_checkpointing": {"enabled": True,
                                             "policy": policy}})
    doc = np.random.default_rng(7).integers(0, 512, size=(2, 129),
                                            dtype=np.int32)
    engine.train_batch((doc[:, :-1].copy(), doc[:, 1:].copy()))
    names = scopes.step_scope_map()
    phases = {(s, p) for s, p in names.values() if s}
    for scope in sorted(DELTA_MOE_SCOPES | {"dstpu/conv", "dstpu/attn",
                                            "dstpu/route", "dstpu/experts"}):
        assert {(scope, "forward"), (scope, "backward")} <= phases, scope
    assert {("dstpu/rope", "forward"), ("dstpu/ffn", "forward"),
            ("dstpu/moe", "forward"), ("dstpu/head", "backward")} <= phases
    assert DELTA_MOE_SCOPES <= {scopes.PREFIX + s for s in scopes.SCOPES}
    replayed = {s for s, p in phases if p == "replay"}
    assert {"dstpu/gdn", "dstpu/delta"} <= replayed
    assert not {"dstpu/ssm", "dstpu/scan", "dstpu/mla", "dstpu/loop"} & {
        s for s, _ in phases}


SSD_HYBRID_SCOPES = {"dstpu/mamba2", "dstpu/ssd"}


def test_a_mamba2_step_holds_its_two_scopes(policy="full"):
    """A ``Mamba2HybridLM`` step: the Mamba-2 mixer (``mamba2``) and the
    chunked state-space duality inside it (``ssd``) run forward and
    backward, and under ``full`` (the cell's policy) forward again beside
    the backward; the convolution keeps ``conv``, the position-free
    attention ``attn`` (no ``rope``), the MLPs ``ffn``."""
    from deepspeed_tpu.models import Mamba2HybridLM
    engine, _, _, _ = ds.initialize(
        model=Mamba2HybridLM.from_size("tiny"),
        mesh=make_mesh(devices=jax.devices()[:1]),
        config={"train_batch_size": 2, "steps_per_print": 10 ** 9,
                "bf16": {"enabled": True}, "gradient_clipping": 1.0,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "activation_checkpointing": {"enabled": True,
                                             "policy": policy}})
    doc = np.random.default_rng(7).integers(0, 512, size=(2, 129),
                                            dtype=np.int32)
    engine.train_batch((doc[:, :-1].copy(), doc[:, 1:].copy()))
    names = scopes.step_scope_map()
    phases = {(s, p) for s, p in names.values() if s}
    for scope in sorted(SSD_HYBRID_SCOPES | {"dstpu/conv", "dstpu/attn",
                                             "dstpu/ffn"}):
        assert {(scope, "forward"), (scope, "replay"),
                (scope, "backward")} <= phases, scope
    assert ("dstpu/head", "backward") in phases
    assert SSD_HYBRID_SCOPES <= {scopes.PREFIX + s for s in scopes.SCOPES}
    assert not {"dstpu/rope", "dstpu/ssm", "dstpu/scan", "dstpu/gdn",
                "dstpu/delta"} & {s for s, _ in phases}
