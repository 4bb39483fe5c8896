"""``deepspeed_tpu.models.DeltaMoELM`` (Gated DeltaNet and gated-attention
layers over dropless expert layers, held as a share of the experts) against
the plain reference ``benchmark/reference/qwen3_next.py``, which imports
nothing of the program: the loss and every gradient leaf in float32, for the
whole layer and for a share, under every recomputation policy.  Tiny sizes,
CPU: hidden 64, 4 query / 2 key-value heads of 16 (4 rotated), 2 key / 4
value heads of 8 x 8 state, 16 experts top-3 of width 32, one period of four
layers, 128 tokens a row.  (The layers one by one:
tests/test_delta_moe_layers.py; through the engine:
tests/test_delta_moe_engine.py.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmark import cell as cells
from deepspeed_tpu.models import DeltaMoELM
from deepspeed_tpu.observability import scalars
from deepspeed_tpu.parallel.topology import make_mesh

SEQ = 128
CELL = "qwen3-next.ep16-seq16384"
#: of a gradient leaf's largest entry.  Float32 ITSELF is good to 1e-4 on
#: this stack, not to 1e-5: the float32 reference is 9.0e-5 off the same
#: reference computed in float64 at these sizes, the program 1.07e-4 off it
#: and 1.36e-4 off the float32 reference (PR 37, read once by hand on the
#: share (4, 4)).  A DeltaNet layer's output is normalised per head after a
#: sum over the past that mostly cancels (an 8 x 8 state), so its rounding
#: is magnified, and two such layers follow each other; one layer at a time
#: — and the rule, the mixers and the expert layer each alone — holds 1e-5
#: (tests/test_delta_moe_layers.py, tests/test_delta_rule.py).
GRADIENT_BAND = 5e-4


def moved(params, seed=1):
    """Every leaf off its initial value: a swapped or dropped leaf shows
    (the zero-centred norms' offsets and ``A_log`` among them)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return treedef.unflatten(
        [x + 0.05 * jax.random.normal(k, x.shape) for x, k in
         zip(leaves, keys)])


@pytest.fixture(scope="module")
def family():
    return cells.load(CELL)


def setting(family, held, policy=None, seed=0, period=4):
    """``period``: layers in the one period held (4 as published: three
    Gated DeltaNet layers and a full one; 2: one of each, half the compile
    for the cases that differ by recomputation policy alone)."""
    fam = family.family
    config = {**fam.tiny(family.config), "rehearsal_seq": SEQ,
              "first_routed_held": held[0], "n_routed_held": held[1],
              "full_attention_interval": period,
              "layers_held": list(range(period))}
    model = fam.build_model(config, {"seq": SEQ})
    model = dataclasses.replace(model, config=dataclasses.replace(
        model.config, remat=policy is not None,
        remat_policy=policy or "full"))
    params = moved(model.init_params(jax.random.PRNGKey(seed)))
    batch = fam.make_batch(np.random.default_rng(seed), 2, config,
                           {"seq": SEQ})
    return fam, config, model, params, batch


def value_and_grads(model, params, batch):
    mesh = make_mesh(devices=jax.devices()[:1])
    loss = lambda p, t, l: scalars.split(model.apply(p, t, l))[0]
    return jax.jit(jax.shard_map(
        jax.value_and_grad(loss), mesh=mesh, in_specs=(P(),) * 3,
        out_specs=P(), check_vma=False))(params, *batch)


@pytest.fixture(scope="module")
def wanted(family):
    """The reference's loss, parts and gradient per share, once."""
    made = {}

    def of(held, period):
        if (held, period) not in made:
            fam, config, _, params, batch = setting(family, held,
                                                    period=period)

            def reference(p):
                total, balance, pairs = fam.reference_parts(p, batch, config)
                return total, (balance, pairs)

            with jax.default_matmul_precision("highest"):
                made[held, period] = jax.jit(jax.value_and_grad(
                    reference, has_aux=True))(params)
        return made[held, period]

    return of


@pytest.mark.parametrize("held,policy,period", [
    ((0, 16), None, 4), ((4, 4), "full", 2), ((4, 4), "selective", 2),
    ((4, 4), "dots", 2)])
def test_loss_and_every_gradient_agree_with_the_reference(family, wanted,
                                                          held, policy,
                                                          period):
    """The whole layer over the published period of four, and the second of
    four shares over a period of two (a DeltaNet layer and a full one)
    under each recomputation policy (the share's prefix, 512 rows,
    is under its 768 pairs: the branch and its own ``jax.checkpoint`` sit
    inside the layer's), in float32: the loss to 1e-5, every gradient leaf
    — in the reference's layout, ``A_log``, ``dt_bias``, the convolution,
    the shared expert's gate, the router and both kinds of norm among them
    — to ``GRADIENT_BAND`` of its largest entry."""
    fam, config, model, params, batch = setting(family, held, policy,
                                                period=period)
    assert model.config.experts_held == held
    assert model.config.segments == (
        (("gdn", "gdn", "gdn", "full")[4 - period:], 1),)
    (want, (balance, pairs)), want_grads = wanted(held, period)
    with jax.default_matmul_precision("highest"):
        loss, grads = value_and_grads(model, params, batch)
    assert float(loss) == pytest.approx(float(want), abs=1e-5)
    assert 2e-3 < float(balance) / period < 5e-3    # ~c k a layer, k = 3
    share = int(pairs) / (period * batch[0].size * 3)
    assert share == 1.0 if held == (0, 16) else 0.15 < share < 0.35
    got = fam.to_reference(grads, config)
    want_grads = fam.to_reference(want_grads, config)
    assert len(jax.tree_util.tree_leaves(got)) == (
        3 + (period - 1) * 17 + 16)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0,
            atol=GRADIENT_BAND * scale,
            err_msg=jax.tree_util.keystr(path))


def test_the_model_counts_what_its_layers_decided(family):
    """``apply`` returns the loss WITH the expert layers' step scalars, and
    its gauges describe the program it traced."""
    _, _, model, params, batch = setting(family, (4, 4), "full")
    mesh = make_mesh(devices=jax.devices()[:1])
    out = jax.jit(jax.shard_map(
        lambda p, t, l: model.apply(p, t, l).scalars, mesh=mesh,
        in_specs=(P(),) * 3, out_specs=P(), check_vma=False))(params, *batch)
    pairs = 2 * SEQ * 3
    assert int(out["moe/overflow_passes"]) == 0
    assert 0.15 < int(out["moe/held_pairs"]) / (4 * pairs) < 0.35
    assert pairs / 16 < int(out["moe/max_expert_rows"]) < pairs / 4
    assert model.step_scalars() == {"moe/overflow_passes": 1,
                                    "moe/held_pairs": 1,
                                    "moe/max_expert_rows": 1}
    counts = model.step_counts()
    assert counts == {
        "layers_gdn": 3, "layers_full": 1, "layers_moe": 4,
        "layer_applications": 4, "experts_total": 16, "experts_held": 4,
        "experts_per_token": 3, "routed_rows_prefix": 512,
        "routed_rows_all": pairs, "delta_chunk": 64,
        "delta_chunks_per_sequence": 2, "delta_kernel": 0,
        "delta_state_bytes_per_layer": 4 * 2 * 4 * 8 * 8}


def test_the_published_shape_by_eval_shape():
    """The defaults are the published model: 48 layers in 12 periods, 79.7B
    parameters ("80B"), about 3B of them active a token ("A3B")."""
    model = DeltaMoELM.from_size("tiny")
    assert model.config.kinds == ("gdn", "gdn", "gdn", "full")
    from deepspeed_tpu.models import DeltaMoEConfig
    cfg = DeltaMoEConfig()
    assert cfg.kinds == ("gdn", "gdn", "gdn", "full") * 12
    assert cfg.qkv_columns == 8192
    shapes = jax.eval_shape(DeltaMoELM(cfg).init_params,
                            jax.random.PRNGKey(0))
    total = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
    assert 79e9 < total < 81e9
    expert = 3 * 2048 * 512
    active = total - 48 * (512 - 10) * expert
    assert 3.5e9 < active < 4.1e9 and 2.9e9 < active - 2 * 151936 * 2048 < 3.4e9
    with pytest.raises(ValueError, match="whole groups"):
        dataclasses.replace(cfg, value_heads=24).validate()
    with pytest.raises(ValueError, match="rotary_dim"):
        dataclasses.replace(cfg, rotary_dim=300).validate()
    with pytest.raises(ValueError, match="not divisible by mp 4"):
        cfg.validate(mp_size=4)                     # two key/value heads
