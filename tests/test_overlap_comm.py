"""``zero_optimization.overlap_comm`` / ``comm_bucket_mb`` /
``DSTPU_OVERLAP``: what the knobs govern, and what they no longer do.

ZeRO stage 1 and 2 build ONE boundary whatever the knobs say: the flat
gradient reduces in one contiguous collective onto the owned partition,
the update runs shard-locally, and the updated partition returns in one
all-gather in the compute dtype (PERF.md, PR 25: the bucketed
``[group, partition]`` form bought no overlap on the chip and spent most
of the step re-tiling full-size buffers).  The stage-1/2 bit-exact tests
below therefore guard that the knobs change nothing there, across grad
accumulation, sub-group tiling and checkpoint resume with the knob
toggled, and ``test_boundary_matches_plain_restatement`` pins the values
against a boundary written out in the test itself.

The knobs still govern stage 0 (``comm.allreduce_grads`` chunks leaves
above the bucket size into independent psums) and stage 3: the ZeRO-3
prefetch (transformer.scan_layers) scans layer PAIRS issuing
both gathers up front — the second hides under the first block's compute,
the carry stays activations-only (gathered weights in the carry would be
saved as per-iteration scan residuals, resurrecting the full unsharded
weight set in the backward), and a scheduling barrier between the blocks
keeps the program bitwise with the on-demand path.
"""

import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.config import DeepSpeedConfigError
from deepspeed_tpu.models import GPT2
from deepspeed_tpu.parallel import comm
from deepspeed_tpu.parallel.topology import make_mesh

VOCAB, SEQ = 64, 16
#: small enough that the tiny model's partition splits into several
#: buckets (0.004 MB -> 1024 fp32 elements per bucket)
BUCKET_MB = 0.004


def tiny_gpt2(layers=2, remat=False):
    # remat off by default: the boundary tests exercise the collective/
    # update tiling, which is orthogonal to activation checkpointing, and
    # the un-rematted programs compile ~2x faster on the CPU mesh.  The
    # ZeRO-3 prefetch tests turn it back on — the remat-replayed gather
    # is exactly what they pin.
    return GPT2.from_size("tiny", vocab_size=VOCAB, max_seq_len=SEQ,
                          num_layers=layers, hidden_size=32, num_heads=4,
                          remat=remat)


def lm_batch(batch, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, VOCAB, size=(batch, SEQ)).astype(np.int32)
    labels = np.roll(toks, -1, axis=1)
    labels[:, -1] = -1
    return toks, labels


def make_engine(stage, overlap, gas=1, pps=None, layers=2, fp16=True,
                bucket_mb=BUCKET_MB, mp=1, remat=False, dp=None):
    zero = {"stage": stage, "overlap_comm": overlap,
            "comm_bucket_mb": bucket_mb}
    if pps:
        zero["parameter_parallel_size"] = pps
    prec = ({"fp16": {"enabled": True, "initial_scale_power": 8}}
            if fp16 else {"bf16": {"enabled": True}})
    model = tiny_gpt2(layers, remat=remat)
    devices = jax.devices()[:dp * mp] if dp else None
    engine, _, _, _ = deepspeed_tpu.initialize(
        config={"train_batch_size": 8 * gas,
                "gradient_accumulation_steps": gas,
                "steps_per_print": 10 ** 6,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": zero, **prec},
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(7)),
        mesh=make_mesh(model_parallel_size=mp, devices=devices))
    return engine


def run_fused(engine, steps=2):
    gas = engine.gradient_accumulation_steps()
    return [float(engine.train_batch(lm_batch(8 * gas, seed=i)))
            for i in range(steps)]


def assert_params_bitwise(a, b, msg=""):
    for (pa, la), (_, lb) in zip(
            jax.tree_util.tree_leaves_with_path(a),
            jax.tree_util.tree_leaves_with_path(b)):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb),
            err_msg=f"{msg} {jax.tree_util.keystr(pa)}")


def host_params(engine):
    return jax.tree_util.tree_map(np.asarray, engine.params)


# ------------------------------------------------------- bucket geometry

def test_bucket_bounds():
    # covers [0, total), aligned starts, <= one aligned step each
    assert comm.bucket_bounds(1024, 4096) == ((0, 1024),)
    assert comm.bucket_bounds(1024, 256) == (
        (0, 256), (256, 512), (512, 768), (768, 1024))
    # bucket_elems floors to the 128 lane; sub-lane requests clamp to 128
    assert comm.bucket_bounds(256, 1) == ((0, 128), (128, 256))
    # non-multiple totals: the tail bucket is short
    assert comm.bucket_bounds(640, 256) == ((0, 256), (256, 512), (512, 640))
    for total, be in ((1024, 256), (640, 333), (128, 1)):
        bounds = comm.bucket_bounds(total, be)
        assert bounds[0][0] == 0 and bounds[-1][1] == total
        assert all(s < e for s, e in bounds)
        assert all(a[1] == b[0] for a, b in zip(bounds, bounds[1:]))
        assert all(s % 128 == 0 for s, _ in bounds)


def test_config_knobs():
    """What the knobs still govern: the chunked psum of stage 0 (and of
    stage 3's replicated leaves) and the stage-3 prefetch.  A stage-1/2
    engine reads them and builds the same boundary either way
    (``test_one_boundary_whatever_the_knobs``)."""
    e = make_engine(0, True, bucket_mb=0.5)
    assert e.overlap_comm and e.comm_bucket_elems == 0.5 * (1 << 20) // 4
    chunked, whole = make_engine(0, True), make_engine(0, False)
    big = max(x.size for x in jax.tree_util.tree_leaves(chunked.params))
    psums = {engine.overlap_comm: _step_collective_counts(
        engine, lm_batch(8))["psum"] for engine in (chunked, whole)}
    # the tiny model's largest leaf splits into several chunks at
    # BUCKET_MB with the knob on; with it off every leaf is one psum
    assert big > chunked.comm_bucket_elems and psums[True] > psums[False], (
        big, psums)
    assert make_engine(3, True).module.zero3_prefetch
    assert not make_engine(3, False).module.zero3_prefetch
    with pytest.raises(DeepSpeedConfigError, match="comm_bucket_mb"):
        make_engine(1, True, bucket_mb=0)
    with pytest.raises(DeepSpeedConfigError, match="comm_bucket_mb"):
        make_engine(1, True, bucket_mb="huge")
    # a zeroed-out bucket with overlap already off is a valid spelling of
    # "disabled", not a config error
    assert not make_engine(1, False, bucket_mb=0).overlap_comm


def test_dstpu_overlap_env(monkeypatch):
    monkeypatch.setenv("DSTPU_OVERLAP", "off")
    assert not make_engine(1, True).overlap_comm
    monkeypatch.setenv("DSTPU_OVERLAP", "on")
    assert make_engine(1, False).overlap_comm
    monkeypatch.setenv("DSTPU_OVERLAP", "sideways")
    with pytest.raises(DeepSpeedConfigError, match="DSTPU_OVERLAP"):
        make_engine(1, True)


# ------------------------------------------------- bit-exactness, fused

@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_overlap_bitexact_fused(stage):
    """train_batch trajectories and final params are BITWISE identical
    with the knob on and off: chunked vs whole-leaf psums at stage 0, one
    and the same boundary at stages 1 and 2, prefetched vs on-demand
    gathers at stage 3."""
    remat = stage == 3    # stage 3: pin the remat-replayed prefetched bwd
    eo = make_engine(stage, True, remat=remat)
    es = make_engine(stage, False, remat=remat)
    assert eo.overlap_comm and not es.overlap_comm
    lo, ls = run_fused(eo), run_fused(es)
    assert lo == ls, (stage, lo, ls)
    assert_params_bitwise(host_params(eo), host_params(es),
                          f"stage {stage}")


def test_overlap_bitexact_gas_boundary():
    """gas > 1 (stage 2 — the stage where the scatter runs INSIDE the
    accumulation loop): the knob changes nothing at the gas boundary."""
    eo, es = make_engine(2, True, gas=2), make_engine(2, False, gas=2)
    assert run_fused(eo) == run_fused(es)
    assert_params_bitwise(host_params(eo), host_params(es), "stage 2 gas 2")


@pytest.mark.slow
def test_overlap_bitexact_split_api():
    """Split API (forward/backward/step): the knob changes nothing.
    (slow tier: beyond the tier-1 matrix — the boundary program under
    test is the same _make_step_local the fused legs pin.)"""
    def run(overlap):
        engine = make_engine(1, overlap)
        out = []
        for i in range(3):
            loss = engine(*lm_batch(8, seed=i))
            engine.backward(loss)
            engine.step()
            out.append(float(loss))
        return out, host_params(engine)

    lo, po = run(True)
    ls, ps = run(False)
    assert lo == ls
    assert_params_bitwise(po, ps, "split API")


@pytest.mark.slow
def test_overlap_bitexact_zero_x_mp():
    """ZeRO-1 x tensor parallelism: the [S, local] row layout runs the
    same boundary on its squeezed 1-D partition whatever the knob says.
    (slow tier: the zero_2d path also runs overlap-on in the MULTICHIP
    dryrun's zero-1 tp=2 leg.)"""
    eo, es = make_engine(1, True, mp=2), make_engine(1, False, mp=2)
    assert run_fused(eo, steps=2) == run_fused(es, steps=2)
    assert_params_bitwise(host_params(eo), host_params(es), "mp=2")


def test_overlap_bitexact_pps_subgroups():
    """parameter_parallel_size < dp (axis_index_groups collectives): the
    knob changes nothing."""
    eo, es = make_engine(1, True, pps=4), make_engine(1, False, pps=4)
    assert run_fused(eo) == run_fused(es)
    assert_params_bitwise(host_params(eo), host_params(es), "pps=4")


def test_overlap_bitexact_zero3_prefetch_bf16():
    """ZeRO-3 prefetched gathers vs on-demand, bf16 (the dtype where a
    non-uniform scan body showed ulp drift): bitwise over 3 steps."""
    eo = make_engine(3, True, fp16=False, remat=True)
    es = make_engine(3, False, fp16=False, remat=True)
    assert eo.module.zero3_prefetch and not es.module.zero3_prefetch
    assert run_fused(eo) == run_fused(es)
    assert_params_bitwise(host_params(eo), host_params(es), "zero3 bf16")


# ------------------------------------------------- program-shape evidence

def _step_collectives(engine, batch):
    """The collective equations of the fused step program (static jaxpr
    evidence), as ``(primitive name, equation)`` pairs."""
    from deepspeed_tpu import analysis
    from deepspeed_tpu.analysis import graph as G

    jaxpr = analysis.trace_train_batch(
        engine, batch, fn=engine._build_train_batch(batch))
    # lax.psum_scatter binds the primitive jax calls reduce_scatter
    return [(eqn.primitive.name, eqn) for eqn, _ in G.walk(jaxpr.jaxpr)
            if eqn.primitive.name in ("psum", "reduce_scatter",
                                      "all_gather")]


def _step_collective_counts(engine, batch):
    return collections.Counter(
        name for name, _ in _step_collectives(engine, batch))


@pytest.mark.parametrize("bucket_mb", [BUCKET_MB, 32])
@pytest.mark.parametrize("overlap", [True, False])
@pytest.mark.parametrize("stage", [1, 2])
def test_one_boundary_whatever_the_knobs(stage, overlap, bucket_mb):
    """A stage-1/2 engine builds ONE boundary program whatever
    ``overlap_comm`` / ``comm_bucket_mb`` say: exactly one reduce-scatter
    of the whole flat gradient and one all-gather of the whole updated
    partition over the data axis, the gather in the compute dtype (the
    tiny model's partition would split into several buckets at
    ``BUCKET_MB``)."""
    engine = make_engine(stage, overlap, bucket_mb=bucket_mb)
    meta = engine.flat_meta
    assert meta.partition > engine.comm_bucket_elems or bucket_mb == 32
    found = _step_collectives(engine, lm_batch(8))
    scatters = [e for name, e in found if name == "reduce_scatter"]
    gathers = [e for name, e in found if name == "all_gather"]
    assert len(scatters) == 1 and len(gathers) == 1, found
    scatter, gather = scatters[0], gathers[0]
    assert scatter.params["axis_name"] == ("data",)
    assert gather.params["axis_name"] == ("data",)
    assert scatter.invars[0].aval.shape == (meta.padded,)
    assert scatter.invars[0].aval.dtype == jnp.float32
    assert gather.invars[0].aval.shape == (meta.partition,)
    assert gather.invars[0].aval.dtype == engine.policy.compute_dtype
    assert gather.outvars[0].aval.shape == (meta.padded,)


def test_partitions_are_whole_tiles():
    """Each rank's partition is a whole number of the TPU's 1-D tiles
    (1024 elements in f32, bf16 and fp16): what lets each rank's piece
    of the all-reduce and of the all-gather land in place (PERF.md, PR 25;
    with 128 the compiled boundary re-tiled full-size buffers in
    unaligned dynamic-update-slice loops)."""
    from deepspeed_tpu import zero as zero_mod

    tile = zero_mod.FLAT_ALIGN
    assert tile % 1024 == 0
    for engine in (make_engine(1, True), make_engine(1, True, pps=4),
                   make_engine(2, True, mp=2)):
        meta = engine.flat_meta
        assert meta.partition % tile == 0
        assert meta.padded == meta.partition * engine.zero_pps
        assert 0 <= meta.padded - meta.total < tile * engine.zero_pps


# ------------------------------------- the boundary, written out plainly

def _plain_boundary_step(engine, batch):
    """One optimizer step (on batches of ``batch``'s format) with the ZeRO-1 boundary re-stated plainly
    (the parent commit's ``overlap_comm: false`` path is the model):
    flatten -> psum -> own slice -> the engine's optimizer update ->
    all_gather in fp32 -> unflatten and cast, in one shard_map.  Nothing
    of ``engine._make_step_local``, ``_scatter_grads_local``,
    ``parallel/comm.py`` or ``zero.flatten_tree``/``unflatten_tree`` runs
    here; gradients, optimizer and loss-scale arithmetic are the
    engine's."""
    from jax.sharding import PartitionSpec as P

    meta, opt = engine.flat_meta, engine.base_optimizer
    world, part = engine.dp_world_size, engine.flat_meta.partition
    cdt = engine.policy.compute_dtype
    fp16 = engine.config.fp16_enabled
    loss_and_grads = engine._make_loss_and_grads()
    assert engine.clip_grad == 0 and engine.gradient_accumulation_steps() == 1

    def local(params, master, opt_state, ls_state, hypers, batch_args):
        _, grads = loss_and_grads(params, ls_state.cur_scale, batch_args)
        pieces = [g.reshape(-1).astype(jnp.float32)
                  for g in meta.treedef.flatten_up_to(grads)]
        pieces.append(jnp.zeros((meta.padded - meta.total,), jnp.float32))
        reduced = jax.lax.psum(jnp.concatenate(pieces), "data") / world
        own = jax.lax.dynamic_slice_in_dim(
            reduced, jax.lax.axis_index("data") * part, part)
        new_p, new_opt = opt.update(
            {"flat": master}, {"flat": own}, opt_state,
            lr=hypers[0][0], beta1=hypers[1][0], beta2=hypers[2][0],
            weight_decay=hypers[3][0],
            combined_scale=ls_state.cur_scale if fp16 else 1.0)
        if fp16:
            # skip-on-overflow, agreed over the data axis
            bad = jax.lax.pmax(
                1.0 - jnp.all(jnp.isfinite(own)).astype(jnp.float32),
                "data") > 0
            new_p, new_opt = jax.tree_util.tree_map(
                lambda new, old: jnp.where(bad, old, new),
                (new_p, new_opt), ({"flat": master}, opt_state))
        full = jax.lax.all_gather(new_p["flat"], "data", axis=0, tiled=True)
        leaves, offset = [], 0
        for shape, size in zip(meta.shapes, meta.sizes):
            leaves.append(
                full[offset:offset + size].reshape(shape).astype(cdt))
            offset += size
        return meta.treedef.unflatten(leaves), new_p["flat"], new_opt

    master_spec, opt_spec, ls_spec = engine._step_specs()
    fn = jax.jit(jax.shard_map(
        local, mesh=engine.mesh,
        in_specs=(engine._param_specs, master_spec, opt_spec, ls_spec,
                  P(), engine._batch_specs(batch)),
        out_specs=(engine._param_specs, master_spec, opt_spec),
        check_vma=False))
    return lambda state, batch: fn(
        *state, engine.loss_scale_state, engine._current_hypers(), batch)


@pytest.mark.parametrize("fp16", [False, True], ids=["bf16", "fp16"])
def test_boundary_matches_plain_restatement(fp16):
    """``params``, ``master_flat`` and both moments after two
    ``train_batch`` steps at dp=4 ZeRO-1 are BITWISE what the plainly
    written boundary gives from the same initial state: the contiguous
    reduce-scatter, the tile-aligned partitions and the compute-dtype
    gather move the same numbers, not fewer bits."""
    engine = make_engine(1, True, fp16=fp16, dp=4)
    plain = make_engine(1, False, fp16=fp16, dp=4)   # never stepped
    assert engine.dp_world_size == 4 and engine.zero_flat
    step = _plain_boundary_step(plain, lm_batch(8))
    state = (plain.params, plain.master_flat, plain.opt_state)
    for i in range(2):
        engine.train_batch(lm_batch(8, seed=i))
        state = step(state, lm_batch(8, seed=i))
    # no skipped step, no loss-scale move: the plain form has neither
    assert engine.skipped_steps == 0
    assert (float(engine.loss_scale_state.cur_scale)
            == float(plain.loss_scale_state.cur_scale))
    params, master, opt_state = state
    assert int(opt_state.step) == int(engine.opt_state.step) == 2
    assert_params_bitwise(host_params(engine),
                          jax.tree_util.tree_map(np.asarray, params),
                          "params")
    for name, got, want in (
            ("master_flat", engine.master_flat, master),
            ("m", engine.opt_state.m["flat"], opt_state.m["flat"]),
            ("v", engine.opt_state.v["flat"], opt_state.v["flat"])):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=name)
    assert np.asarray(master).any() and np.asarray(opt_state.v["flat"]).any()


def test_zero3_prefetch_memory_envelope():
    """The prefetch scan's residuals must NOT hold gathered weights: a
    gathered layer threaded through the scan carry would be saved per
    iteration, resurrecting the full unsharded weight set in the backward
    (the review-caught failure mode).  Pinned via XLA's memory analysis:
    prefetch temp memory stays within on-demand + ~2 gathered layers.
    The same contract is asserted STATICALLY at engine level by the
    capacity planner — tests/test_memplan.py
    test_zero3_prefetch_envelope_is_computed pins the planner's computed
    two-layer envelope and its traced-program prediction without a
    compile."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu import zero3 as Z
    from deepspeed_tpu.models import transformer as T

    L_ = 8
    cfg = T.TransformerConfig(vocab_size=256, max_seq_len=8,
                              hidden_size=256, num_layers=L_, num_heads=4)
    blocks = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), T.init_block_params(cfg,
                                                              jax.random.PRNGKey(1)))
    specs = T.block_partition_specs()
    dims = Z.choose_dims(blocks, specs, {"data": 8, "model": 1}, 8,
                         min_dims=jax.tree_util.tree_map(lambda _: 1,
                                                         blocks))
    aspecs = Z.augment_specs(specs, dims)
    mesh = make_mesh()
    x = jax.random.normal(jax.random.PRNGKey(2),
                          (1, 8, 256)).astype(jnp.bfloat16)

    def temp_bytes(prefetch):
        def local(b, xx):
            y = T.stack_apply(xx, b, cfg, z3_dims=dims,
                              z3_prefetch=prefetch)
            return jnp.sum(y.astype(jnp.float32) ** 2)
        f = jax.jit(jax.shard_map(
            lambda b, xx: jax.value_and_grad(local)(b, xx), mesh=mesh,
            in_specs=(aspecs, P()), out_specs=(P(), aspecs),
            check_vma=False))
        bp = jax.tree_util.tree_map(
            lambda v, s: jax.device_put(
                v, jax.sharding.NamedSharding(mesh, s)), blocks, aspecs)
        return f.lower(bp, x).compile().memory_analysis().temp_size_in_bytes

    gathered_layer = sum(
        int(np.prod(l.shape[1:])) * 2    # bf16
        for l in jax.tree_util.tree_leaves(blocks))
    on_demand, prefetch = temp_bytes(False), temp_bytes(True)
    # two transient layers + scheduling slack, NOT L x gathered-layer
    budget = on_demand + 3 * gathered_layer
    assert prefetch <= budget, (
        f"prefetch temp {prefetch} exceeds on-demand {on_demand} + 3 "
        f"gathered layers ({gathered_layer} each): scan residuals are "
        f"holding gathered weights")


def test_lint_clean_with_overlap():
    """Graph-lint regression: the boundary's and the prefetch's
    collective sequences are rank-uniform — zero error-severity findings
    on the overlap-on step programs at every stage."""
    for stage in (1, 2, 3):
        engine = make_engine(stage, True)
        rep = engine.run_graph_lint(lm_batch(8), train=True)
        assert not rep.errors, f"stage {stage}:\n" + rep.format()


# ------------------------------------------------------- resume parity

def test_resume_with_overlap_toggled(tmp_path):
    """State layouts do not depend on the knob, so a checkpoint saved
    with overlap ON resumes bit-compatibly with overlap OFF — the resumed
    trajectory matches the unbroken run."""
    ref = run_fused(make_engine(1, False), steps=5)
    saver = make_engine(1, True)
    run_fused(saver, steps=3)
    saver.save_checkpoint(str(tmp_path), tag="ov1")
    resumed = make_engine(1, False)   # overlap toggled off
    resumed.load_checkpoint(str(tmp_path), tag="ov1")
    post = [float(resumed.train_batch(lm_batch(8, seed=i)))
            for i in (3, 4)]
    np.testing.assert_allclose(post, ref[3:], rtol=1e-6, atol=1e-7)
    # stage 3's persistent layout is likewise untouched by overlap (the
    # prefetch only reorders gathers); its resume parity is pinned by
    # tests/test_zero3.py::test_zero3_checkpoint_resume_parity running
    # with the default overlap_comm=true


# ------------------------------------------------- bucketed plain psum

def test_allreduce_grads_bucketed_matches_monolithic():
    """comm.allreduce_grads(bucket_elems=...) chunks big leaves into
    independent psums — elementwise identical to the whole-leaf psum."""
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh()
    rng = np.random.default_rng(0)
    grads = {"big": jnp.asarray(rng.normal(size=(8, 40, 33)),
                                jnp.float32),
             "small": jnp.asarray(rng.normal(size=(8, 7)), jnp.float32)}

    def run(bucket_elems):
        def local(g):
            return comm.allreduce_grads(
                g, "data", 8, fp32_allreduce=True,
                prescale_gradients=True, gradient_predivide_factor=2.0,
                bucket_elems=bucket_elems)
        f = jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=({"big": P("data"), "small": P("data")},),
            out_specs={"big": P("data"), "small": P("data")},
            check_vma=False))
        return jax.tree_util.tree_map(np.asarray, f(grads))

    assert_params_bitwise(run(200), run(None), "bucketed psum")
