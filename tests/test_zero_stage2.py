"""ZeRO stage 2: gradient partitioning (beyond the reference's v0.1.0).

Each micro-step's gradients reduce-scatter onto the owned flat partition
INSIDE the accumulation loop, so the grad-accumulation buffer shrinks
from full model size to ``1/pps``.  Linearity makes per-micro
scatter-then-accumulate equal the stage-1 accumulate-then-scatter, so
stage 2 must reproduce stage-1 trajectories exactly (same collectives,
reordered) — pinned here along with the memory claim and composition
with MP / parameter-parallel sub-groups / checkpointing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.config import DeepSpeedConfigError
from deepspeed_tpu.models import GPT2
from deepspeed_tpu.parallel.topology import make_mesh

pytestmark = pytest.mark.slow

VOCAB, SEQ = 64, 16


def tiny_gpt2():
    return GPT2.from_size("tiny", vocab_size=VOCAB, max_seq_len=SEQ,
                          num_layers=2, hidden_size=32, num_heads=4)


def lm_batch(batch, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, VOCAB, size=(batch, SEQ)).astype(np.int32)
    labels = np.roll(toks, -1, axis=1)
    labels[:, -1] = -1
    return toks, labels


def make_engine(stage, mp=1, gas=1, pps=None, **cfg_over):
    zero = {"stage": stage}
    if pps:
        zero["parameter_parallel_size"] = pps
    cfg = {
        "train_batch_size": 8 * gas,
        "gradient_accumulation_steps": gas,
        "steps_per_print": 10 ** 6,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": zero,
        "fp16": {"enabled": True, "initial_scale_power": 8},
    }
    cfg.update(cfg_over)
    model = tiny_gpt2()
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=cfg, model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(7)),
        mesh=make_mesh(model_parallel_size=mp))
    return engine


def run_fused(engine, steps=4, gas=1):
    return [float(engine.train_batch(lm_batch(8 * gas, seed=i)))
            for i in range(steps)]


@pytest.mark.parametrize("gas", [1, 2])
def test_stage2_matches_stage1_fused(gas):
    """Fused train_batch: stage-2 trajectory == stage-1 (the per-micro
    scatter must commute with accumulation)."""
    ref = run_fused(make_engine(1, gas=gas), gas=gas)
    e2 = make_engine(2, gas=gas)
    assert e2.zero_stage == 2
    got = run_fused(e2, gas=gas)
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=1e-3)


def test_stage2_matches_stage1_split_api():
    """Split API: backward() accumulates the flat PARTITION, step()
    consumes it — trajectory parity with stage 1."""
    acc_shapes = {}

    def run_split(stage):
        engine = make_engine(stage)
        out = []
        for i in range(4):
            loss = engine(*lm_batch(8, seed=i))
            engine.backward(loss)
            acc_shapes[stage] = jax.tree_util.tree_map(
                lambda a: a.shape, engine._acc)
            engine.step()
            out.append(float(loss))
        return out, engine

    ref, _ = run_split(1)
    got, e2 = run_split(2)
    # the stage-2 accumulator really is the flat partition, not a tree
    assert acc_shapes[2] == (e2.flat_meta.padded,), acc_shapes[2]
    assert len(jax.tree_util.tree_leaves(acc_shapes[1])) > 1
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=1e-3)


def test_stage2_with_mp_and_pps():
    """Stage 2 composes with tensor parallelism and parameter-parallel
    sub-groups (the [S, local] rows scatter per micro like the 1-D
    layout)."""
    ref = run_fused(make_engine(1, mp=2))
    got = run_fused(make_engine(2, mp=2))
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=1e-3)

    ref = run_fused(make_engine(1, pps=2))
    got = run_fused(make_engine(2, pps=2))
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=1e-3)


def test_stage2_with_pipeline():
    """Stage 2 under pp=2: the per-(stage, shard) [1, part] rows scatter
    per micro and match the stage-1 trajectory."""
    from deepspeed_tpu.models import GPT2Pipelined

    def run(stage):
        model = GPT2Pipelined.from_size(
            "tiny", vocab_size=VOCAB, max_seq_len=SEQ, num_layers=2,
            hidden_size=32, num_heads=4, num_micro_batches=2)
        engine, _, _, _ = deepspeed_tpu.initialize(
            config={"train_batch_size": 8, "steps_per_print": 10 ** 6,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": stage},
                    "fp16": {"enabled": True, "initial_scale_power": 8}},
            model=model,
            model_parameters=model.init_params(jax.random.PRNGKey(7)),
            mesh=make_mesh(pipeline_parallel_size=2))
        return run_fused(engine)

    np.testing.assert_allclose(run(2), run(1), rtol=2e-3, atol=1e-3)


def test_stage2_shrinks_grad_accumulator():
    """The point of stage 2: the LIVE grad accumulator a device holds
    between micro-steps is the 1/dp flat partition, not a replicated
    full-size fp32 grad tree.  Measured on real device buffers (the
    split API holds the accumulator across backward() calls)."""
    from test_zero_memory import device_bytes

    dev = jax.devices()[0]
    e1, e2 = make_engine(1), make_engine(2)
    for e in (e1, e2):
        loss = e(*lm_batch(8))
        e.backward(loss)
    full = device_bytes(e1._acc, dev)
    part = device_bytes(e2._acc, dev)
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(e1.params))
    dp = e2.dp_world_size
    assert full == 4 * n_params, (full, n_params)      # replicated fp32
    assert part == 4 * e2.flat_meta.padded // dp, part  # owned partition
    assert part <= full // dp + 4 * 1024
    # both engines still step correctly from their accumulators
    e1.step()
    e2.step()
    assert e1.global_steps == 1 and e2.global_steps == 1


def test_stage2_checkpoint_resume(tmp_path):
    """Optimizer-state layout is identical to stage 1, so save/resume is
    unchanged — resumed trajectory matches the unbroken run."""
    ref = run_fused(make_engine(2), steps=6)
    saver = make_engine(2)
    run_fused(saver, steps=3)
    saver.save_checkpoint(str(tmp_path), tag="s2")
    resumed = make_engine(2)
    resumed.load_checkpoint(str(tmp_path), tag="s2")
    post = [float(resumed.train_batch(lm_batch(8, seed=i)))
            for i in (3, 4, 5)]
    np.testing.assert_allclose(post, ref[3:], rtol=1e-5)


def test_stage2_with_param_groups():
    """Stage 2 x param_groups: the per-element gid expansion applies to
    the per-micro scattered partition — an lr=0 group stays frozen."""
    model = tiny_gpt2()
    engine, _, _, _ = deepspeed_tpu.initialize(
        config={"train_batch_size": 16, "gradient_accumulation_steps": 2,
                "steps_per_print": 10 ** 6,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 2},
                "bf16": {"enabled": True}},
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(7)),
        param_groups=[{"params": "wpe", "lr": 0.0}],
        mesh=make_mesh())
    init_wpe = np.asarray(model.init_params(
        jax.random.PRNGKey(7))["wpe"], np.float32)
    for i in range(2):
        engine.train_batch(lm_batch(16, seed=i))
    got = np.asarray(engine.params["wpe"], np.float32)
    np.testing.assert_allclose(got, init_wpe, atol=1e-3)
    assert not np.allclose(
        np.asarray(engine.params["wte"], np.float32),
        np.asarray(model.init_params(jax.random.PRNGKey(7))["wte"],
                   np.float32), atol=1e-4)


@pytest.mark.fast
def test_stage4_rejected():
    # stage 3 exists now (tests/test_zero3.py); the config guard moves to
    # the first unimplemented stage
    with pytest.raises(DeepSpeedConfigError, match="stage"):
        make_engine(4)
