"""``deepspeed_tpu.models.LatentMoELM`` through ``deepspeed_tpu.initialize``
→ ``engine.train_batch``: it trains, the router's correction bias gets a
zero gradient and stays put through Adam steps, tensor / expert parallelism
over the ``model`` axis and ZeRO-1 give the one-device loss, and the engine
hands its ``validate`` the sequence-parallel degree.  Tiny sizes, CPU.  (The
model against its reference: tests/test_latent_moe_model.py.)"""

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import LatentMoELM
from deepspeed_tpu.parallel.topology import make_mesh

SEQ = 64


def lm_batch(rows, vocab=512, seed=0, seq=SEQ):
    doc = np.random.default_rng(seed).integers(
        0, vocab, size=(rows, seq + 1), dtype=np.int32)
    return doc[:, :-1].copy(), doc[:, 1:].copy()


def tiny(**over):
    return LatentMoELM.from_size("tiny", **{"experts_held": (4, 4), **over})


def engine_config(rows, **over):
    return {"train_batch_size": rows, "steps_per_print": 10 ** 9,
            "bf16": {"enabled": True}, "gradient_clipping": 1.0,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}, **over}


def biases(tree):
    return [np.asarray(stacked["router_b"], np.float32)
            for stacked in tree["blocks"][1].values()]


@pytest.fixture(scope="module")
def trained():
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=tiny(), mesh=make_mesh(devices=jax.devices()[:1]),
        config=engine_config(2, activation_checkpointing={
            "enabled": True, "policy": "selective"}))
    batch = lm_batch(2)
    before = jax.tree_util.tree_map(np.asarray, engine.master)
    losses = [float(engine.train_batch(batch)) for _ in range(3)]
    return engine, losses, before


def test_trains_through_initialize_and_train_batch(trained):
    engine, losses, _ = trained
    assert engine.module.config.remat_policy == "selective"
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    # ln(512) + two layers' balance loss of ~alpha each
    assert abs(losses[0] - np.log(512)) < 0.5


def test_the_correction_bias_stays_put_through_three_adam_steps(trained):
    """Its gradient is identically zero, so Adam's update is 0 / (0 + eps):
    the fp32 master and the compute copy hold the zeros they started with,
    while the router's weights beside it have moved."""
    engine, _, before = trained
    for tree in (engine.master, engine.params):
        for b in biases(tree):
            assert b.shape == (2, 16) and not b.any()
    moments = engine.opt_state
    for b in biases(moments.m) + biases(moments.v):
        assert not b.any()
    was = before["blocks"][1]["l0"]["router_w"]
    now = np.asarray(engine.master["blocks"][1]["l0"]["router_w"])
    assert np.abs(now - was).max() > 1e-4


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


@pytest.mark.parametrize("layout", ["tp2", "dp2-zero1"])
def test_other_layouts_agree_with_one_device(layout, one_device_losses):
    """The ``model`` axis (heads, the FFN widths, the vocabulary, and the
    four experts held as two and two: each shard computes its own experts'
    part and a ``psum`` adds them) and ZeRO-1 give the one-device loss."""
    np.testing.assert_allclose(two_losses(layout), one_device_losses,
                               rtol=3e-3)


def test_the_engine_refuses_context_parallelism():
    with pytest.raises(ValueError, match="context parallelism"):
        deepspeed_tpu.initialize(
            model=tiny(), config=engine_config(2),
            mesh=make_mesh(context_parallel_size=2,
                           devices=jax.devices()[:2]))
