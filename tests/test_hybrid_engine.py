"""``deepspeed_tpu.models.HybridLM`` through ``deepspeed_tpu.initialize`` →
``engine.train_batch``: it trains, reports its stack to the ``model``
telemetry group, gives the one-device loss under tensor parallelism, ZeRO-1
and ZeRO-3, and the engine hands its ``validate`` the sequence-parallel
degree.  Tiny sizes, CPU.  (The model against its reference:
tests/test_hybrid_model.py.)"""

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import HybridLM
from deepspeed_tpu.parallel.topology import make_mesh

SEQ = 48


def lm_batch(rows, vocab=512, seed=0, seq=SEQ):
    doc = np.random.default_rng(seed).integers(
        0, vocab, size=(rows, seq + 1), dtype=np.int32)
    return doc[:, :-1].copy(), doc[:, 1:].copy()


def tiny(**over):
    return HybridLM.from_size("tiny", **over)


def engine_config(rows, **over):
    return {"train_batch_size": rows, "steps_per_print": 10 ** 9,
            "bf16": {"enabled": True}, "gradient_clipping": 1.0,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}, **over}


@pytest.fixture(scope="module")
def trained():
    model = tiny()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, mesh=make_mesh(devices=jax.devices()[:1]),
        config=engine_config(2, activation_checkpointing={
            "enabled": True, "policy": "full"}))
    batch = lm_batch(2)
    return engine, [float(engine.train_batch(batch)) for _ in range(3)]


def test_trains_through_initialize_and_train_batch(trained):
    engine, losses = trained
    assert engine.module.config.remat_policy == "full"
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert abs(losses[0] - np.log(512)) < 0.5


def test_model_telemetry_group_reports_the_stack(trained):
    engine, _ = trained
    assert engine._telemetry.registry.collect()["model"] == {
        "layers_mamba": 3, "layers_swa": 2, "layers_full": 1,
        "layers_gmu": 2, "layers_cross": 2, "attention_window": 8,
        "kv_group": 2, "ssm_state_bytes_per_row": 4 * 128 * 4,
        "layer_applications_per_step": 10}


def two_losses(layout):
    over, mesh = {}, make_mesh(devices=jax.devices()[:1])
    if layout == "tp2":
        mesh = make_mesh(model_parallel_size=2, devices=jax.devices()[:2])
    elif layout.startswith("dp2"):
        mesh = make_mesh(devices=jax.devices()[:2])
        over["zero_optimization"] = {"stage": int(layout[-1])}
    model = tiny()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=engine_config(4, **over), mesh=mesh,
        model_parameters=model.init_params(jax.random.PRNGKey(3)))
    batch = lm_batch(4)
    return [float(engine.train_batch(batch)) for _ in range(2)]


@pytest.fixture(scope="module")
def one_device_losses():
    return two_losses("one")


@pytest.mark.parametrize("layout", ["tp2", "dp2-zero1", "dp2-zero3"])
def test_other_layouts_agree_with_one_device(layout, one_device_losses):
    """Tensor parallelism (whole groups of four query heads, the
    state-space channels, the vocabulary), ZeRO-1 and ZeRO-3 (a period of
    layers gathered at a time) give the one-device loss."""
    np.testing.assert_allclose(two_losses(layout), one_device_losses,
                               rtol=2e-3)


def test_the_engine_refuses_context_parallelism():
    with pytest.raises(ValueError, match="context parallelism"):
        deepspeed_tpu.initialize(
            model=tiny(), config=engine_config(2),
            mesh=make_mesh(context_parallel_size=2,
                           devices=jax.devices()[:2]))
