"""The boundary of every ZeRO stage against a boundary written out here.

One step program per stage, nothing a user can toggle:

* stage 0: one ``psum`` per gradient leaf, the update on the whole tree,
  the cast to the compute dtype;
* stage 1: flat gradient, in the dtype it comes in (the backward's at
  gas 1, the fp32 accumulator's otherwise) -> every rank's unreduced piece
  of the owned partition, widened to fp32 and summed in the order
  ``comm.reduce_scatter_grads``' docstring states -> the update on the
  partition -> one all-gather in the compute dtype;
* stage 2: the same, the reduction run per micro-step (on the backward's
  own dtype) so that the accumulator is the partition;
* stage 3: every partitioned leaf gathered where it is used (the layer
  scan gathers one layer at a time), gradients scattered by the gather's
  transpose, the update on the shards.

``_plain_step`` restates each of them with ``jax.lax`` collectives and the
model's plain ``apply``: nothing of ``engine._make_step_local``,
``_make_fused_local``, ``_scatter_grads_local``, ``parallel/comm.py``,
``zero.flatten_tree`` / ``unflatten_tree``, ``zero3.gather_tree`` or
``transformer.scan_layers``' per-layer gather runs there.  The optimizer
and the loss-scale arithmetic are the engine's; at stages 0-2 so are the
gradients of a micro-batch.  ``params``, master and both moments are
compared BITWISE after two steps.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu import analysis as graph_lint
from deepspeed_tpu.analysis import commplan
from deepspeed_tpu.analysis import graph as jaxpr_graph
from deepspeed_tpu.ops import optim as optim_mod
from deepspeed_tpu.models import GPT2
from deepspeed_tpu.parallel import comm
from deepspeed_tpu.parallel.topology import make_mesh

VOCAB, SEQ = 64, 16
STAGES = [0, 1, 2, 3]


def tiny_gpt2(remat=False):
    # recomputation off but where a case is about it: the boundary is
    # orthogonal to it and the programs compile ~2x faster on the CPU mesh
    return GPT2.from_size("tiny", vocab_size=VOCAB, max_seq_len=SEQ,
                          num_layers=2, hidden_size=32, num_heads=4,
                          remat=remat)


def lm_batch(batch, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, VOCAB, size=(batch, SEQ)).astype(np.int32)
    labels = np.roll(toks, -1, axis=1)
    labels[:, -1] = -1
    return toks, labels


def make_engine(stage, gas=1, pps=None, fp16=True, mp=1, remat=False, dp=4,
                seed=7):
    zero = {"stage": stage}
    if pps:
        zero["parameter_parallel_size"] = pps
    prec = ({"fp16": {"enabled": True, "initial_scale_power": 8}}
            if fp16 else {"bf16": {"enabled": True}})
    model = tiny_gpt2(remat)
    engine, _, _, _ = deepspeed_tpu.initialize(
        config={"train_batch_size": 8 * gas,
                "gradient_accumulation_steps": gas,
                "steps_per_print": 10 ** 6,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": zero, **prec},
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(seed)),
        mesh=make_mesh(model_parallel_size=mp,
                       devices=jax.devices()[:dp * mp]))
    return engine


def host(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


def state_of(engine):
    """(params, master, first moment, second moment) on the host."""
    master = engine.master_flat if engine.zero_flat else engine.master
    return host((engine.params, master, engine.opt_state.m,
                 engine.opt_state.v))


def assert_bitwise(got, want, msg=""):
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_leaves_with_path(want)]
    got, want = (jax.tree_util.tree_leaves(t) for t in (got, want))
    assert len(got) == len(want) == len(paths)
    for path, g, w in zip(paths, got, want):
        assert g.dtype == w.dtype, (msg, path)
        np.testing.assert_array_equal(g, w, err_msg=f"{msg} {path}")


# ------------------------------------- the boundary, written out plainly

def _plain_step(engine, batch):
    """One optimizer step on batches of ``batch``'s format with the
    boundary of ``engine``'s stage written out (see the module docstring);
    ``engine`` lends its layout, optimizer and loss scale and is never
    stepped.  Returns ``step((params, master, opt_state), batch)``."""
    stage, gas = engine.zero_stage, engine.gradient_accumulation_steps()
    world, pps, mp = engine.dp_world_size, engine.zero_pps, engine.mp_world_size
    opt, meta = engine.base_optimizer, engine.flat_meta
    cdt = engine.policy.compute_dtype
    fp16 = engine.config.fp16_enabled
    rows = bool(engine._zero_state_axes)       # ZeRO x MP: [1, part] blocks
    dims = engine._zero3_dims
    assert engine.clip_grad == 0
    tmap = jax.tree_util.tree_map

    if stage == 3:
        plain_model = tiny_gpt2(engine.module.config.remat)
        assert plain_model.zero3_dims is None

        def loss_and_grads(shards, scale, batch_args):
            def loss_fn(shards):
                # the restatement's own gather: every partitioned leaf
                # whole before the model runs, the model none the wiser
                whole = tmap(
                    lambda x, d: x if d < 0 else jax.lax.all_gather(
                        x, "data", axis=d, tiled=True), shards, dims)
                return plain_model.apply(whole, *batch_args).astype(
                    jnp.float32) * (scale / gas)
            grads = engine._psum_model_replicated(jax.grad(loss_fn)(shards))
            return None, tmap(
                lambda g: (g / float(mp)).astype(jnp.float32), grads)
    else:
        # the flat boundary takes a micro-batch's gradients in the dtype
        # the backward wrote; stage 0 reduces them in fp32
        loss_and_grads = engine._make_loss_and_grads(widen=stage == 0)

    def flatten(grads):
        """One buffer in the gradients' own dtype: what goes on the wire."""
        leaves = meta.treedef.flatten_up_to(grads)
        dtype = jnp.result_type(*leaves)
        pieces = [g.reshape(-1).astype(dtype) for g in leaves]
        pieces.append(jnp.zeros((meta.padded - meta.total,), dtype))
        return jnp.concatenate(pieces)

    # Sub-groups of pps consecutive ranks own the partitions (pps = world:
    # one group).  The sum over ranks runs within a sub-group, then across
    # the sub-groups; at stage 2 the accumulator sits between the two.
    def within(flat):
        """This rank's partition of ``flat`` summed over its sub-group, as
        ``comm.reduce_scatter_grads``' docstring states it: every rank's
        piece of the partition crosses unreduced, in ``flat``'s dtype, is
        widened to fp32 and added left to right as own piece + the piece
        heard in step 1 + in step 2 + ...: from rank me^1, me^2, ... of
        the sub-group where pps is a power of two, from rank me-1, me-2,
        ... (mod pps) where it is not."""
        rank, part = jax.lax.axis_index("data"), meta.partition
        me, first = rank % pps, (rank // pps) * pps
        every = jax.lax.all_gather(flat, "data")        # [world, padded]
        assert every.dtype == flat.dtype                # nothing summed yet
        total = None
        for r in range(pps):
            heard = me ^ r if pps & (pps - 1) == 0 else (me - r) % pps
            piece = jax.lax.dynamic_slice(
                every, (first + heard, me * part), (1, part))[0]
            piece = piece.astype(jnp.float32)
            total = piece if total is None else total + piece
        return total

    def across(own):
        """``own`` summed over the ranks that hold the same partition."""
        if pps == world:
            return own
        every = jax.lax.all_gather(own, "data").reshape(
            (world // pps, pps) + own.shape)
        same = jax.lax.dynamic_index_in_dim(
            every, jax.lax.axis_index("data") % pps, axis=1, keepdims=False)
        return sum(same[g] for g in range(world // pps))

    def reduce_micro(grads):
        """What a micro-step's gradients are before they are summed: at
        stage 2 the reduced partition; an accumulator adds in fp32."""
        if stage == 2:
            return within(flatten(grads)) / world
        return grads if gas == 1 else tmap(
            lambda g: g.astype(jnp.float32), grads)

    def reduce_sum(acc):
        """The summed gradients, as the update takes them."""
        if stage == 0:
            return tmap(lambda g: jax.lax.psum(g, "data") / world, acc)
        if stage == 1:
            return across(within(flatten(acc))) / world
        if stage == 2:
            return across(acc)
        # stage 3: partitioned leaves arrive summed and scattered (the
        # gather's transpose); the others are local gradients
        return tmap(lambda g, d: g / world if d >= 0
                    else jax.lax.psum(g, "data") / world, acc, dims)

    def local(params, master, opt_state, ls_state, hypers, batch_args):
        scale = ls_state.cur_scale
        if gas == 1:
            acc = reduce_micro(loss_and_grads(params, scale, batch_args)[1])
        else:
            micro = tmap(lambda x: x.reshape(
                (gas, x.shape[0] // gas) + x.shape[1:]), batch_args)
            zeros = (jnp.zeros((meta.partition,), jnp.float32) if stage == 2
                     else tmap(lambda p: jnp.zeros(p.shape, jnp.float32),
                               params))
            acc, _ = jax.lax.scan(
                lambda acc, mb: (tmap(jnp.add, acc, reduce_micro(
                    loss_and_grads(params, scale, mb)[1])), None),
                zeros, micro)
        grads = reduce_sum(acc)

        old = (master, opt_state)
        if meta is not None:
            if rows:
                master = master[0]
                opt_state = optim_mod.OptimizerState(
                    step=opt_state.step, m=tmap(lambda x: x[0], opt_state.m),
                    v=tmap(lambda x: x[0], opt_state.v))
            master, grads = {"flat": master}, {"flat": grads}
        new_master, new_opt = opt.update(
            master, grads, opt_state,
            lr=hypers[0][0], beta1=hypers[1][0], beta2=hypers[2][0],
            weight_decay=hypers[3][0],
            combined_scale=scale if fp16 else 1.0)
        if meta is not None:
            new_master = new_master["flat"]
            if rows:
                new_master = new_master[None]
                new_opt = optim_mod.OptimizerState(
                    step=new_opt.step, m=tmap(lambda x: x[None], new_opt.m),
                    v=tmap(lambda x: x[None], new_opt.v))
        if fp16:
            # skip on overflow, agreed over every axis the state spans
            finite = jnp.all(jnp.stack([
                jnp.all(jnp.isfinite(g))
                for g in jax.tree_util.tree_leaves(grads)]))
            bad = 1.0 - finite.astype(jnp.float32)
            for axis in ("data",) + (("model",) if mp > 1 else ()):
                bad = jax.lax.pmax(bad, axis)
            new_master, new_opt = tmap(
                lambda new, was: jnp.where(bad > 0, was, new),
                (new_master, new_opt), old)

        if meta is None:
            params = tmap(lambda m: m.astype(cdt), new_master)
        else:
            own = new_master[0] if rows else new_master
            # every rank's partition in rank order: the first pps of them
            # are the whole buffer (the sub-groups hold copies)
            whole = jax.lax.all_gather(own, "data", axis=0, tiled=True)
            leaves, offset = [], 0
            for shape, size in zip(meta.shapes, meta.sizes):
                leaves.append(whole[offset:offset + size].reshape(
                    shape).astype(cdt))
                offset += size
            params = meta.treedef.unflatten(leaves)
        return params, new_master, new_opt

    master_spec, opt_spec, ls_spec = engine._step_specs()
    fn = jax.jit(jax.shard_map(
        local, mesh=engine.mesh,
        in_specs=(engine._param_specs, master_spec, opt_spec, ls_spec,
                  P(), engine._batch_specs(batch)),
        out_specs=(engine._param_specs, master_spec, opt_spec),
        check_vma=False))
    return lambda state, batch: fn(
        *state, engine.loss_scale_state, engine._current_hypers(), batch)


def _against_restatement(steps=2, first_moment_ulps=0, **layout):
    """Step an engine with ``train_batch`` and a twin's initial state with
    ``_plain_step``; compare everything the boundary writes, bitwise.
    ``first_moment_ulps``: the one quantity a case may name as not
    bit-exact, and by how much, in units in the last place of the
    buffer's largest element."""
    engine, plain = make_engine(**layout), make_engine(**layout)
    gas = engine.gradient_accumulation_steps()
    assert engine.zero_stage == layout["stage"]
    step = _plain_step(plain, lm_batch(8 * gas))
    state = (plain.params,
             plain.master_flat if plain.zero_flat else plain.master,
             plain.opt_state)
    for i in range(steps):
        engine.train_batch(lm_batch(8 * gas, seed=i))
        state = step(state, lm_batch(8 * gas, seed=i))
    # no skipped step, no loss-scale move: the plain form has neither
    assert engine.skipped_steps == 0
    assert (float(engine.loss_scale_state.cur_scale)
            == float(plain.loss_scale_state.cur_scale))
    params, master, opt_state = state
    assert int(opt_state.step) == int(engine.opt_state.step) == steps
    want = host((params, master, opt_state.m, opt_state.v))
    got = state_of(engine)
    if first_moment_ulps:
        for g, w in zip(*(jax.tree_util.tree_leaves(s[2])
                          for s in (got, want))):
            assert (np.abs(g - w).max()
                    <= first_moment_ulps * np.spacing(np.abs(w).max()))
        got, want = got[:2] + got[3:], want[:2] + want[3:]
    assert_bitwise(got, want, str(layout))
    # the comparison is of numbers that moved
    assert all(np.asarray(x, np.float32).any()
               for x in jax.tree_util.tree_leaves(want[-1]))
    return engine


@pytest.mark.parametrize("gas", [1, 2], ids=["gas1", "gas2"])
@pytest.mark.parametrize("fp16", [False, True], ids=["bf16", "fp16"])
@pytest.mark.parametrize("stage", STAGES)
def test_boundary_matches_plain_restatement(stage, fp16, gas):
    """``params``, master and both moments after two ``train_batch`` steps
    at dp=4 are BITWISE what the plainly written boundary of the stage
    gives from the same initial state, with and without gradient
    accumulation.  Stage 3 runs with recomputation on: the gather replays
    in the backward, inside the layer scan, and must still deliver the
    gradients an up-front gather's transpose delivers."""
    engine = _against_restatement(stage=stage, fp16=fp16, gas=gas,
                                  remat=stage == 3)
    assert engine.dp_world_size == 4
    assert engine.zero_flat == (stage in (1, 2)) and engine.zero3 == (stage == 3)


@pytest.mark.parametrize("stage,layout", [
    (1, {"mp": 2, "fp16": False}), (1, {"mp": 2}), (3, {"mp": 2}),
    (1, {"pps": 2}), (2, {"pps": 2})],
    ids=["zero1-mp2-bf16", "zero1-mp2-fp16", "zero3-mp2", "zero1-pps2of4",
         "zero2-pps2of4"])
def test_boundary_composes_with_layout(stage, layout):
    """ZeRO x tensor parallelism (the [1, partition] blocks of the
    [model, local] layout; at stage 3 the data axis beside the model
    axis) and ``parameter_parallel_size`` sub-groups (2 of dp=4: the
    reduction within a sub-group, then across) against the restatement.

    Not bit-exact in one quantity: at ZeRO-1 x mp=2 under fp16 a third
    of the FIRST MOMENT's elements differ from the second step on, by at
    most one unit in the last place of the buffer's largest element (on
    XLA-CPU ``b1*m + (1-b1)*g*(1/scale)`` contracts into a fused
    multiply-add in one of the two programs and not in the other: the
    [1, partition] block's slicing changes the fusion; at step 1, m is 0
    and nothing shows).  The gradient that enters it is the same: the
    second moment, the master and ``params`` are bitwise equal, and under
    bf16, where no unscale multiplies, so is the first moment."""
    engine = _against_restatement(
        stage=stage, gas=2 if stage == 2 else 1,
        first_moment_ulps=int((stage, layout) == (1, {"mp": 2})), **layout)
    assert engine.mp_world_size == layout.get("mp", 1)
    assert engine.zero_pps == layout.get("pps", 4)


# ------------------------------------------------ the two APIs, and resume

@pytest.mark.parametrize("stage", STAGES)
def test_split_api_equals_train_batch(stage):
    """``engine(); backward(); step()`` and ``train_batch`` run the same
    boundary: losses and state bitwise equal after two steps (gas 1, where
    both assign the same rows to the same shard)."""
    fused, split = make_engine(stage), make_engine(stage)
    for i in range(2):
        batch = lm_batch(8, seed=i)
        want = fused.train_batch(batch)
        loss = split(*batch)
        split.backward(loss)
        split.step()
        assert float(loss) == float(want), (stage, i)
    assert_bitwise(state_of(split), state_of(fused), f"stage {stage}")


@pytest.mark.parametrize("stage", STAGES)
def test_resume_equals_uninterrupted(stage, tmp_path):
    """Save after step 1, load into a fresh engine (another seed: every
    value must come from the checkpoint), step 2: bitwise the
    uninterrupted run's loss and state."""
    whole, saver = make_engine(stage), make_engine(stage)
    for engine in (whole, saver):
        engine.train_batch(lm_batch(8, seed=0))
    saver.save_checkpoint(str(tmp_path), tag="step1")
    resumed = make_engine(stage, seed=11)
    resumed.load_checkpoint(str(tmp_path), tag="step1")
    assert resumed.global_steps == 1
    want = whole.train_batch(lm_batch(8, seed=1))
    got = resumed.train_batch(lm_batch(8, seed=1))
    assert float(got) == float(want)
    assert_bitwise(state_of(resumed), state_of(whole), f"stage {stage}")


# ------------------------------------ the step program under the lint

@pytest.mark.parametrize("stage", [1, 2, 3])
def test_step_program_lints_clean(stage):
    """The boundary's collective sequence is rank-uniform: the graph lint
    has no error-severity finding on the training step program."""
    rep = make_engine(stage).run_graph_lint(lm_batch(8), train=True)
    assert not rep.errors, f"stage {stage}:\n" + rep.format()


# ------------------------------ the wire: what crosses it, and how wide

NARROW = (jnp.bfloat16, jnp.float16)
SUMS = ("psum", "psum_invariant")       # what a pmean lowers to, too


@pytest.mark.parametrize("stage,gas,bits", [(1, 1, 16), (1, 2, 32),
                                            (2, 1, 16), (2, 2, 16)],
                         ids=["zero1-gas1", "zero1-gas2", "zero2-gas1",
                              "zero2-gas2"])
def test_gradient_wire_is_as_wide_as_the_tree(stage, gas, bits):
    """The step program's jaxpr, read: the gradient is flattened in the
    dtype it comes in — no fp32 array of ``FlatMeta.padded`` elements
    exists before the exchange where the backward's bf16 tree reaches the
    boundary (ZeRO 1 at gas 1, ZeRO 2 per micro-step), and one does where
    an fp32 accumulator does (ZeRO 1 at gas 2); the exchange is group - 1
    permutes of one partition each, no ``psum_scatter`` is left, and no
    summing collective over the data axis takes a bf16/fp16 operand (the
    model's own tensor-parallel ``psum`` of activations is not the
    boundary's).  The
    ``boundary`` gauges and the capacity planner's wire count say the
    same bytes."""
    engine = make_engine(stage, gas=gas, fp16=False)
    batch = lm_batch(8 * gas)
    jaxpr = graph_lint.trace_train_batch(
        engine, batch, fn=engine._build_train_batch(batch))
    meta, group = engine.flat_meta, engine.zero_pps
    # the per-rank program: inside the shard_map local shapes are what a
    # chip holds (outside it the master itself is a global f32[padded])
    [body] = [eqn.params["jaxpr"] for eqn, _ in jaxpr_graph.walk(jaxpr)
              if eqn.primitive.name == "shard_map"]
    eqns = [eqn for eqn, _ in jaxpr_graph.walk(body)]
    names = [eqn.primitive.name for eqn in eqns]

    sent = [eqn.invars[0].aval for eqn in eqns
            if eqn.primitive.name == "ppermute"]
    assert len(sent) == group - 1
    assert all(a.shape == (meta.partition,) and 8 * a.dtype.itemsize == bits
               for a in sent)
    before = eqns[:names.index("ppermute")]
    wide_flat = [v.aval for eqn in before for v in eqn.outvars
                 if getattr(v.aval, "shape", None) == (meta.padded,)
                 and v.aval.dtype == jnp.float32]
    assert bool(wide_flat) == (bits == 32)

    assert "psum_scatter" not in names and "reduce_scatter" not in names
    for eqn in eqns:
        if eqn.primitive.name in SUMS and "data" in eqn.params["axes"]:
            assert not [v for v in eqn.invars
                        if jaxpr_graph.dtype_of(v) in NARROW], eqn

    sends = gas if stage == 2 else 1
    wire = sends * (group - 1) * meta.partition * bits // 8
    assert engine._telemetry.registry.collect()["boundary"] == {
        "wire_bits": bits, "wire_bytes_per_step": wire}
    plan = commplan.analyze_comm(jaxpr, dict(engine.mesh.shape))
    assert sum(c.bytes_total for c in plan.costs
               if c.primitive == "ppermute") == wire


def test_boundary_gauges_only_where_the_boundary_is_flat():
    """ZeRO 0 and 3 have no flat gradient buffer and no ``boundary``
    group; a flat engine that has built no program yet reports nothing."""
    for stage in (0, 3):
        registry = make_engine(stage)._telemetry.registry
        assert "boundary" not in registry.collect()
    assert make_engine(1)._telemetry.registry.collect()["boundary"] == {}


# ------------------------- the exchange alone: comm.reduce_scatter_grads

@pytest.mark.parametrize("knobs", [
    {"fp32_allreduce": True},
    {"prescale_gradients": True},
    {"prescale_gradients": True, "gradient_predivide_factor": 2.0}],
    ids=["fp32_allreduce", "prescale", "prescale-predivide2"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("world,pps", [(4, 4), (4, 2), (6, 6), (6, 3)],
                         ids=["dp4", "pps2of4", "dp6", "pps3of6"])
def test_reduce_scatter_grads_is_the_written_out_fp32_mean(world, pps, dtype,
                                                           knobs):
    """``comm.reduce_scatter_grads`` on a dp=4 and a dp=6 mesh, whole and
    in two sub-groups: every rank's partition is, bit for bit, the mean
    written out on the host in fp32 in the order the docstring states
    (own piece + the pieces heard in steps 1, 2, ...: from ranks me^1,
    me^2, ... of a power-of-two group, from me-1, me-2, ... mod the group
    otherwise; then the two sub-groups' sums, which have one order), with
    the reference's scaling (deepspeed_light.py:819-849) applied to the
    widened pieces.  The data make the order show far above the last bit:
    of every element's values over the ranks one is +2**26, one -2**26 and
    the others are standard normal, so a normal value survives only if it
    is added while the running sum is small.  Where the world is no power
    of two the division by it is one XLA rewrites into a multiplication by
    the reciprocal: there the comparison allows the last bit."""
    part = 2 * 1024
    rng = np.random.default_rng(pps)
    values = rng.standard_normal((world, pps * part))
    big = rng.permuted(np.tile(np.arange(world)[:, None], (1, pps * part)),
                       axis=0)[:2]
    values[big[0], np.arange(pps * part)] = 2.0 ** 26
    values[big[1], np.arange(pps * part)] = -2.0 ** 26
    per_rank = jnp.asarray(values, dtype)
    got = jax.jit(jax.shard_map(
        lambda flat: comm.reduce_scatter_grads(
            flat[0], "data", world, partition_group_size=pps, **knobs)[None],
        mesh=make_mesh(devices=jax.devices()[:world]),
        in_specs=P("data"), out_specs=P("data"), check_vma=False))(per_rank)
    assert got.dtype == jnp.float32 and got.shape == (world, part)

    x = np.asarray(per_rank).astype(np.float32)
    pre = np.float32(knobs.get("gradient_predivide_factor", 1.0)
                     if knobs.get("prescale_gradients") else 1.0)

    def group_sum(rank, reverse=False):
        me, first = rank % pps, (rank // pps) * pps
        heard = [me ^ r if pps & (pps - 1) == 0 else (me - r) % pps
                 for r in range(pps)]
        if reverse:
            heard.reverse()
        pieces = [x[first + h, me * part:(me + 1) * part] / pre
                  for h in heard]
        total = pieces[0]
        for piece in pieces[1:]:
            total = total + piece
        return total

    for rank in range(world):
        total = group_sum(rank)
        if pps < world:
            total = group_sum(rank % pps) + group_sum(rank % pps + pps)
        want = total / np.float32(world / pre)
        if world & (world - 1) == 0:
            np.testing.assert_array_equal(np.asarray(got[rank]), want,
                                          err_msg=f"rank {rank}")
        else:
            np.testing.assert_array_max_ulp(np.asarray(got[rank]), want,
                                            maxulp=1)
        # another order of the same pieces is another result (of more
        # than two: fp32 addition commutes)
        assert pps == 2 or (group_sum(rank, reverse=True)
                            != group_sum(rank)).any()
    if dtype == jnp.bfloat16:
        # a sum in the wire's dtype would have lost bits
        narrow = np.asarray(per_rank)[:, :part]
        assert (narrow.sum(0).astype(np.float32)
                != x[:, :part].sum(0)).any()


# ------------------------------------------- geometry of the flat buffer

def test_partitions_are_whole_tiles():
    """Each rank's partition is a whole number of the TPU's 1-D tiles
    (1024 elements in f32, bf16 and fp16): what lets each rank's piece
    leave in place in the gradient exchange and land in place in the
    all-gather (PERF.md, PR 25 and PR 32;
    with 128 the compiled boundary re-tiled full-size buffers in
    unaligned dynamic-update-slice loops)."""
    from deepspeed_tpu import zero as zero_mod

    tile = zero_mod.FLAT_ALIGN
    assert tile % 1024 == 0
    for engine in (make_engine(1, dp=8), make_engine(1, pps=4, dp=8),
                   make_engine(2, mp=2)):
        meta = engine.flat_meta
        assert meta.partition % tile == 0
        assert meta.padded == meta.partition * engine.zero_pps
        assert 0 <= meta.padded - meta.total < tile * engine.zero_pps


# ------------------------------------- the stage-0 reduction's arithmetic

@pytest.mark.parametrize("knobs", [
    {"fp32_allreduce": True},
    {"prescale_gradients": True},
    {"prescale_gradients": True, "gradient_predivide_factor": 2.0}],
    ids=["fp32_allreduce", "prescale", "prescale-predivide2"])
def test_allreduce_grads_is_the_written_out_mean(knobs):
    """``comm.allreduce_grads``: one reduction per leaf, equal to the mean
    over the ranks written out on the host with the reference's scaling
    (deepspeed_light.py:819-849).  Integer-valued gradients: every sum in
    fp32 is exact whatever its order, and a bf16 sum of eight values up
    to 255 is not, so ``fp32_allreduce`` is seen to widen the wire."""
    world = 8
    mesh = make_mesh()
    rng = np.random.default_rng(0)
    dtype = jnp.bfloat16 if knobs.get("fp32_allreduce") else jnp.float32
    per_rank = {"big": rng.integers(0, 256, size=(world, 40, 33)),
                "small": rng.integers(0, 256, size=(world, 7))}
    grads = jax.tree_util.tree_map(lambda x: jnp.asarray(x, dtype), per_rank)
    spec = {"big": P("data"), "small": P("data")}
    got = jax.jit(jax.shard_map(
        lambda g: comm.allreduce_grads(g, "data", world, **knobs),
        mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False))(grads)

    pre = knobs.get("gradient_predivide_factor", 1.0)
    for name in per_rank:
        x = np.asarray(grads[name]).astype(np.float32)
        if knobs.get("prescale_gradients"):
            mean = sum(x[r] / np.float32(pre) for r in range(world))
            mean = mean / np.float32(world / pre)
        else:
            mean = sum(x[r] for r in range(world)) / np.float32(world)
        want = np.asarray(jnp.asarray(mean).astype(dtype))
        out = np.asarray(got[name])
        assert out.dtype == want.dtype and out.shape == (world,) + want.shape
        for r in range(world):          # every rank holds the mean
            np.testing.assert_array_equal(out[r], want, err_msg=name)
    if knobs.get("fp32_allreduce"):
        # a sum in the gradients' own dtype would have lost bits
        x = np.asarray(grads["big"])
        narrow = x[0]
        for r in range(1, world):
            narrow = narrow + x[r]
        assert narrow.dtype == x.dtype
        assert (narrow.astype(np.float32)
                != np.asarray(grads["big"]).astype(np.float32).sum(0)).any()
