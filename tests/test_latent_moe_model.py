"""``deepspeed_tpu.models.LatentMoELM`` (latent attention + dropless expert
layers, held as a share of the experts) against the plain reference
``benchmark/reference/kimi_moe.py``, which imports nothing of the program.
Tiny sizes, CPU: hidden 64, 2 heads of 24 + 8 / 16, latent 32, 16 experts
top-3 of width 48, 1 dense + 2 expert layers, 128 tokens a row.  (The expert
layer alone: tests/test_moe_dropless.py; the attention and its kernel:
tests/test_latent_attention.py; through the engine:
tests/test_latent_moe_engine.py — files of their own, so that a run by files
spreads the compiles over the workers.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmark import cell as cells
from deepspeed_tpu.models import LatentMoEConfig, LatentMoELM
from deepspeed_tpu.models import moe as M
from deepspeed_tpu.observability import scalars
from deepspeed_tpu.parallel.topology import make_mesh

SEQ = 128
CELL = "kimi-vl-a3b.ep8-seq8192"


def moved(params, seed=1):
    """Every leaf off its initial value: a swapped or dropped leaf shows."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return treedef.unflatten(
        [x + 0.05 * jax.random.normal(k, x.shape) for x, k in
         zip(leaves, keys)])


def on_one_device(fn, *args):
    mesh = make_mesh(devices=jax.devices()[:1])
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P(),) * len(args), out_specs=P(),
        check_vma=False))(*args)


@pytest.fixture(scope="module")
def family():
    return cells.load(CELL)


def setting(family, held, seed=0):
    fam = family.family
    config = {**fam.tiny(family.config), "rehearsal_seq": SEQ,
              "first_routed_held": held[0], "n_routed_held": held[1]}
    model = fam.build_model(config, {"seq": SEQ})
    params = moved(model.init_params(jax.random.PRNGKey(seed)))
    batch = fam.make_batch(np.random.default_rng(seed), 2, config,
                           {"seq": SEQ})
    return fam, config, model, params, batch


def loss_of(model):
    """``apply``'s loss alone (a stack with an expert layer returns it with
    its step scalars, ``observability.scalars.WithScalars``)."""
    return lambda p, t, l: scalars.split(model.apply(p, t, l))[0]


def value_and_grads(model, params, batch):
    return on_one_device(jax.value_and_grad(loss_of(model)), params, *batch)


def assert_grads_close(got, want, rtol, atol_share):
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=rtol,
            atol=atol_share * scale + 1e-12,
            err_msg=jax.tree_util.keystr(path))


# -------------------------------------------------- against the reference

@pytest.mark.parametrize("held", [(0, 16), (4, 4)])
def test_loss_and_every_gradient_agree_with_the_reference(family, held):
    """The whole layer and the second of four shares, in float32: the loss
    to 1e-5, every gradient leaf to 1e-5 of its largest entry."""
    fam, config, model, params, batch = setting(family, held)
    assert model.config.experts_held == held
    assert model.config.segments == ((("dense",), 1), (("moe",), 2))
    def reference(p):
        total, balance, pairs = fam.reference_parts(p, batch, config)
        return total, (balance, pairs)

    with jax.default_matmul_precision("highest"):
        loss, grads = value_and_grads(model, params, batch)
        (want, (balance, pairs)), want_grads = jax.jit(
            jax.value_and_grad(reference, has_aux=True))(params)
    assert float(loss) == pytest.approx(float(want), abs=1e-5)
    assert 0.5e-3 < float(balance) / 2 < 2e-3       # ~alpha a layer
    share = int(pairs) / (2 * batch[0].size * 3)
    assert share == 1.0 if held == (0, 16) else 0.15 < share < 0.35
    assert_grads_close(grads, want_grads, rtol=1e-4, atol_share=1e-5)
    # the correction bias: a leaf of the tree, a zero gradient
    for stacked in grads["blocks"][1].values():
        assert float(jnp.max(jnp.abs(stacked["router_b"]))) == 0.0


def test_bf16_stays_in_a_band_round_the_float32_reference(family):
    """Weights and activations in bfloat16 (the engine's policy), the
    router's arithmetic in float32.  Read over three seeds: the loss -7e-4
    to +2.3e-3 off the float32 reference's 6.3, the worst leaf's gradient
    5% to 13% off in norm (bf16 keeps 8 bits, and a near-tied top-3 choice
    that flips moves a token's rows from one expert's gradient to
    another's).  The band: 1e-2 on the loss, 25% of a leaf's norm."""
    fam, config, model, params, batch = setting(family, (4, 4))
    low = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    loss, grads = value_and_grads(model, low, batch)
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: fam.reference_parts(p, batch, config)[0]))(params)
    assert float(loss) == pytest.approx(float(want), abs=1e-2)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(want_grads)):
        assert a.dtype == jnp.bfloat16
        off = float(jnp.linalg.norm((a.astype(jnp.float32) - b).ravel()))
        assert off <= 0.25 * float(jnp.linalg.norm(b.ravel())), (
            jax.tree_util.keystr(path))


@pytest.mark.parametrize("policy", ["selective", "full", "dots"])
def test_recomputation_policies_give_the_unrecomputed_gradients(family,
                                                                policy):
    """On a share whose prefix (512 rows) is under its 768 pairs, so with
    the branch — and each branch's own ``jax.checkpoint`` — inside the
    layer's."""
    _, _, model, params, batch = setting(family, (4, 4))
    assert M.prefix_rows(2 * SEQ * 3, 4, 16) == 512
    plain = dataclasses.replace(model, config=dataclasses.replace(
        model.config, remat=False))
    again = dataclasses.replace(model, config=dataclasses.replace(
        model.config, remat_policy=policy))
    with jax.default_matmul_precision("highest"):
        want = value_and_grads(plain, params, batch)
        got = value_and_grads(again, params, batch)
    assert float(got[0]) == pytest.approx(float(want[0]), abs=1e-6)
    assert_grads_close(got[1], want[1], rtol=1e-4, atol_share=1e-5)


def test_selective_saves_the_grouped_matmuls_and_the_latent_projections(
        family):
    """Under ``selective`` the backward replays neither a grouped matmul's
    gate / up product nor a latent projection: both carry names the policy
    keeps (``ffn1``, ``qkv``)."""
    _, _, model, params, batch = setting(family, (4, 4))

    def count(policy, primitive):
        m = dataclasses.replace(model, config=dataclasses.replace(
            model.config, remat_policy=policy))
        mesh = make_mesh(devices=jax.devices()[:1])
        text = str(jax.make_jaxpr(jax.shard_map(
            jax.grad(loss_of(m)), mesh=mesh,
            in_specs=(P(),) * 3, out_specs=P(), check_vma=False))(
                params, *batch))
        return text.count(primitive + "[")

    # per expert layer and branch (the prefix's, the overflow's): 3
    # forward products and 6 in the backward (input and weight gradients)
    # under either policy; "full" also replays the three products,
    # "selective" keeps the gate and the up one — the policy finds their
    # names inside the branch and inside the branch's own checkpoint
    full, selective = (count(p, "ragged_dot_general")
                       for p in ("full", "selective"))
    assert (full, selective) == (2 * 12, 2 * 10)
    assert count("full", "dot_general") > count("selective", "dot_general")


def test_the_step_holds_no_host_callback(family):
    """The branch is taken on the device: no ``debug_callback``,
    ``io_callback`` or ``pure_callback`` (a ``debug.print`` is one) in the
    gradient's jaxpr — a program with a host callback is not written to
    the persistent compile cache."""
    _, _, model, params, batch = setting(family, (4, 4))
    mesh = make_mesh(devices=jax.devices()[:1])
    text = str(jax.make_jaxpr(jax.shard_map(
        jax.grad(loss_of(model)), mesh=mesh,
        in_specs=(P(),) * 3, out_specs=P(), check_vma=False))(
            params, *batch))
    assert " cond[" in text
    assert "callback" not in text


# ------------------------------------------------------- what is refused

def test_validate_refuses_what_is_not_built():
    model = LatentMoELM.from_size("tiny", experts_held=(4, 4))
    model.validate(mp_size=2)
    with pytest.raises(ValueError, match="sequence / context parallelism"):
        model.validate(sp_size=2)
    with pytest.raises(ValueError, match="pipeline stages"):
        model.validate(pp_size=2)
    with pytest.raises(ValueError, match="experts held 3 not divisible"):
        LatentMoELM.from_size("tiny", experts_held=(4, 3)).validate(mp_size=2)
    with pytest.raises(ValueError, match="first, count"):
        LatentMoEConfig(num_experts=16, experts_held=(14, 4)).validate()
    with pytest.raises(ValueError, match="experts_per_token"):
        LatentMoEConfig(num_experts=4, experts_held=(0, 4),
                        experts_per_token=6).validate()
    with pytest.raises(ValueError, match="a period of"):
        LatentMoEConfig(segments=((("dense", "swa"), 1),)).validate()
    with pytest.raises(NotImplementedError, match="latent kind of page"):
        model.kv_cache_dims()


def test_step_counts_and_the_published_sizes():
    counts = LatentMoELM(LatentMoEConfig()).step_counts()
    assert counts == {
        "layers_dense": 1, "layers_moe": 26, "layer_applications": 27,
        "experts_total": 64, "experts_held": 64, "experts_per_token": 6,
        "latent_rank": 512, "qk_head_dim": 192, "v_head_dim": 128,
        "routed_rows_prefix": 0, "routed_rows_all": 0}    # nothing traced
    # the cell's share and micro-batch: a quarter of the 98,304 pairs
    cell = LatentMoELM(LatentMoEConfig(
        experts_held=(0, 8), vocab_size=512,
        segments=((("dense",), 1), (("moe",), 1))))
    ids = jax.ShapeDtypeStruct((2, 8192), jnp.int32)
    jax.eval_shape(lambda p, t, l: on_one_device(cell.apply, p, t, l),
                   jax.eval_shape(cell.init_params, jax.random.PRNGKey(0)),
                   ids, ids)
    counts = cell.step_counts()
    assert (counts["routed_rows_prefix"], counts["routed_rows_all"]) == (
        24576, 98304)
    # what the expert layers count on the device, and a dense stack nothing
    assert cell.step_scalars() == {"moe/overflow_passes": 1,
                                   "moe/held_pairs": 1,
                                   "moe/max_expert_rows": 1}
    assert LatentMoELM(LatentMoEConfig(
        segments=((("dense",), 2),))).step_scalars() == {}
    shapes = jax.eval_shape(
        LatentMoELM.from_size("tiny", experts_held=(4, 4)).init_params,
        jax.random.PRNGKey(0))
    moe = shapes["blocks"][1]["l0"]
    assert moe["exp_gate_w"].shape == (2, 4, 64, 48)
    assert moe["router_w"].shape == (2, 64, 16)       # all 16, not the 4
    assert moe["router_b"].shape == (2, 16)
    assert moe["gate_w"].shape == (2, 64, 96)         # 2 shared experts
    assert shapes["blocks"][0]["l0"]["kv_a_w"].shape == (1, 64, 32 + 8)
    assert shapes["head"].shape == shapes["wte"].shape == (512, 64)
