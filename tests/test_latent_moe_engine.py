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


# ------------------------------------------------------- the step scalars

def biased(params, experts=(4, 5, 6)):
    """``params`` with every expert layer's correction bias sending every
    token to ``experts`` (all of them held by the share (4, 4))."""
    import jax.numpy as jnp
    for stacked in params["blocks"][1].values():
        stacked["router_b"] = jnp.zeros_like(stacked["router_b"]).at[
            :, jnp.asarray(experts)].set(10.0)
    return params


def one_step(gas, bias, monkeypatch=None, **over):
    model = tiny()
    params = model.init_params(jax.random.PRNGKey(3))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, mesh=make_mesh(devices=jax.devices()[:1]),
        model_parameters=biased(params) if bias else params,
        config=engine_config(2 * gas, gradient_accumulation_steps=gas,
                             **over))
    loss = float(engine.train_batch(lm_batch(2 * gas)))
    return engine, loss


@pytest.mark.parametrize("gas", [1, 2])
def test_a_biased_router_overflows_every_layer_and_is_still_exact(
        gas, monkeypatch):
    """Every token on experts 4, 5, 6: all 384 pairs of a micro-batch land
    on the share, past its 256-row prefix, in both expert layers — the
    counter reads layers x gas a step, every pair is counted, the busiest
    expert has a row per token; loss and update are those of the layer
    with no prefix at all (``routed_part`` over all pairs: both branches
    are exact, and the counter changes neither)."""
    from deepspeed_tpu.models import moe as M
    pairs = 2 * SEQ * 3
    assert M.prefix_rows(pairs, 4, 16) == 256 < pairs
    engine, loss = one_step(gas, bias=True)
    read = engine.read_step_scalars()
    assert read["values"] == {"moe/overflow_passes": 2 * gas,
                              "moe/held_pairs": 2 * gas * pairs,
                              "moe/max_expert_rows": 2 * SEQ}
    assert (read["steps"], read["micro_steps"]) == (1, gas)
    monkeypatch.setattr(M, "prefix_rows", lambda pairs, *_: pairs)
    unbranched, want = one_step(gas, bias=True)
    assert unbranched.read_step_scalars()["values"][
        "moe/overflow_passes"] == 0            # no branch, no overflow
    assert loss == pytest.approx(want, rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(engine.master),
                    jax.tree_util.tree_leaves(unbranched.master)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


def test_an_unbiased_router_stays_in_the_prefix():
    engine, _ = one_step(2, bias=False)
    values = engine.read_step_scalars()["values"]
    assert values["moe/overflow_passes"] == 0
    # 2 layers x 2 micro-steps, about a quarter of 384 pairs each
    assert 4 * 50 < values["moe/held_pairs"] < 4 * 160
    assert 96 / 4 <= values["moe/max_expert_rows"] <= 2 * SEQ


def test_expert_parallel_shards_count_their_own_passes():
    """The four held experts as two and two over a ``model`` axis: each
    shard routes onto its own experts and takes its own branch, so the
    sums run over the shards too — under the bias onto experts 4, 5, 6 the
    first shard (4, 5) gets 256 pairs a layer against a prefix of 128 and
    overflows, the second (6, 7) gets 128 and does not."""
    model = tiny()
    params = biased(model.init_params(jax.random.PRNGKey(3)))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=engine_config(2),
        mesh=make_mesh(model_parallel_size=2, devices=jax.devices()[:2]))
    engine.train_batch(lm_batch(2))
    read = engine.read_step_scalars()
    assert (read["batch_shards"], read["model_shards"]) == (1, 2)
    assert read["values"] == {"moe/overflow_passes": 2,
                              "moe/held_pairs": 2 * 384,
                              "moe/max_expert_rows": 2 * SEQ}
    assert read["gauges"]["routed_rows_prefix"] == 128


def test_gauges_belong_to_the_fused_program_they_were_traced_for():
    """``routed_rows_*`` and the boundary's ``wire_bits`` are recorded when
    the fused step is traced; a later split-API trace at another shape (it
    writes the module's and the engine's live values) leaves the
    registry's alone."""
    engine, _ = one_step(1, bias=False,
                         zero_optimization={"stage": 1})
    groups = engine.telemetry.registry.collect()
    assert (groups["model"]["routed_rows_prefix"],
            groups["model"]["routed_rows_all"]) == (256, 384)
    assert groups["boundary"]["wire_bits"] == 16
    loss = engine(*lm_batch(4, seq=32))         # the split API's programs
    engine.backward(loss)
    engine.step()
    assert engine.module.routed_rows == (128, 384) or \
        engine.module.routed_rows[1] == 4 * 32 * 3
    assert engine._boundary_wire["wire_bits"] == 32
    after = engine.telemetry.registry.collect()
    assert (after["model"]["routed_rows_prefix"],
            after["model"]["routed_rows_all"]) == (256, 384)
    assert after["boundary"] == groups["boundary"]
    assert engine.read_step_scalars()["gauges"]["routed_rows_prefix"] == 256
