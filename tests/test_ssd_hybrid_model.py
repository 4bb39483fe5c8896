"""``deepspeed_tpu.models.Mamba2HybridLM`` (Mamba-2 layers and position-free
attention over dense SwiGLU MLPs, with Granite's scaled embedding, residual
and logit paths) against the plain reference
``benchmark/reference/granite_hybrid.py``, which imports nothing of the
program: the loss and every gradient leaf in float32, with no recomputation
and under ``full``; that the comparison sees each multiplier, the
attention's scale and the gate's place before the norm; the model through
``deepspeed_tpu.initialize`` -> ``engine.train_batch`` with its gauges; and
``validate``.  Tiny sizes, CPU: hidden 64, Mamba-2 with 8 heads of 16 and
one B / C group of 8 in chunks of 32, 4 query / 2 key-value heads of 16,
depths 4-6 of the published layout (Mamba-2, attention, Mamba-2), 128
tokens a row."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from benchmark import cell as cells
from benchmark.reference import granite_hybrid as reference
from deepspeed_tpu.models import Mamba2HybridConfig, Mamba2HybridLM
from deepspeed_tpu.observability import scalars
from deepspeed_tpu.ops import ssd
from deepspeed_tpu.parallel.topology import make_mesh

SEQ = 128
CELL = "granite-4.0-h-micro.seq8192"
#: of the loss, and of a gradient leaf's largest entry: float32 against
#: float32
TOLERANCE = 1e-5


def moved(params, seed=1):
    """Every leaf off its initial value: a swapped or dropped leaf shows
    (the norms' scales, D and the convolution's bias among them)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return treedef.unflatten(
        [x + 0.05 * jax.random.normal(k, x.shape) for x, k in
         zip(leaves, keys)])


@pytest.fixture(scope="module")
def family():
    return cells.load(CELL)


@pytest.fixture(autouse=True)
def four_chunks(monkeypatch):
    """Chunks of 32: four a row, so the state crosses chunks."""
    monkeypatch.setattr(ssd, "SSD_CHUNK", 32)


def setting(family, policy=None, seed=0):
    fam = family.family
    config = {**fam.tiny(family.config), "rehearsal_seq": SEQ}
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
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.shard_map(
            jax.value_and_grad(loss), mesh=mesh, in_specs=(P(),) * 3,
            out_specs=P(), check_vma=False))(params, *batch)


def reference_value_and_grads(fam, config, params, batch, **change):
    """The reference's loss and gradient in ITS layout; ``change``
    overrides an argument of ``reference.loss``."""
    sz = fam.sizes(config)
    kwargs = dict(kinds=fam.kinds_held(config),
                  heads=(sz["heads"], sz["kv_heads"]),
                  head_dim=sz["head_dim"],
                  ssm=(sz["ssm_heads"], sz["ssm_head_dim"], sz["state"],
                       sz["groups"]),
                  multipliers=fam.multipliers(config),
                  eps=config["rms_norm_eps"])
    kwargs.update(change)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: reference.loss(p, batch, **kwargs)))(
                fam.to_reference(params, config))


def worst(fam, config, grads, want):
    """The largest gap of a gradient leaf, over that leaf's largest entry,
    and the leaf."""
    got = jax.tree_util.tree_flatten_with_path(
        fam.to_reference(grads, config))[0]
    gaps = []
    for (path, a), b in zip(got, jax.tree_util.tree_leaves(want),
                            strict=True):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, jax.tree_util.keystr(path)
        gaps.append((float(jnp.max(jnp.abs(a - b))) / scale,
                     jax.tree_util.keystr(path)))
    return max(gaps)


@pytest.fixture(scope="module")
def exact(family):
    """The engine's and the reference's loss and gradient with no
    recomputation, once."""
    fam, config, model, params, batch = setting(family)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ssd, "SSD_CHUNK", 32)
        return (fam, config, params, batch,
                value_and_grads(model, params, batch),
                reference_value_and_grads(fam, config, params, batch))


@pytest.mark.parametrize("policy", [None, "full"])
def test_loss_and_every_gradient_agree_with_the_reference(family, exact,
                                                          policy):
    """In float32 the loss to 1e-5 and every gradient leaf — the table
    (embedding and tied head), the convolution and its bias, A_log, D,
    dt_bias, the gated norm's scale, the four attention projections and the
    MLPs among them, 34 in all — to 1e-5 of its largest entry."""
    fam, config, params, batch, _, (want, want_grads) = exact
    _, _, model, _, _ = setting(family, policy)
    assert model.config.segments == ((("mamba2",), 1), (("attention",), 1),
                                     (("mamba2",), 1))
    loss, grads = value_and_grads(model, params, batch)
    assert float(loss) == pytest.approx(float(want), abs=TOLERANCE)
    assert len(jax.tree_util.tree_leaves(want_grads)) == 2 + 2 * 12 + 8
    gap, leaf = worst(fam, config, grads, want_grads)
    assert gap < TOLERANCE, leaf


def gate_after_norm(u, p, heads, head_dim, state, groups, eps, bits):
    """``reference.mamba2`` with the norm taken BEFORE the gate (Mamba-2's
    ``norm_before_gate``), a change of the published order."""
    Bt, T, _ = u.shape
    E = heads * head_dim
    proj = reference.matmul(u, p["in_proj"], bits)
    z, xbc, dt = proj[..., :E], proj[..., E:-heads], proj[..., -heads:]
    xbc = reference.silu(reference.causal_conv(xbc, p["conv"],
                                               p["conv_b"])).astype(u.dtype)
    x = xbc[..., :E].reshape(Bt, T, heads, head_dim)
    B = xbc[..., E:E + groups * state].reshape(Bt, T, groups, state)
    C = xbc[..., E + groups * state:].reshape(Bt, T, groups, state)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = reference.ssd(x, dt, -jnp.exp(p["A_log"]), B, C, p["D"])
    y = reference.norm(y.reshape(Bt, T, E), p["norm_g"], eps) * \
        reference.silu(z)
    return reference.matmul(y.astype(u.dtype), p["out_proj"], bits)


@pytest.mark.parametrize("change", ["embedding", "residual", "attention",
                                    "logits", "gate_after_norm"])
def test_the_comparison_sees_every_scaled_path(family, exact, monkeypatch,
                                               change):
    """The same comparison against a reference with ONE published choice
    changed — x 12 on the embedding to x 1, x 0.22 on the branches to x 1,
    the attention's 1/64 to 1/sqrt(64), / 8 on the logits to / 1, the gate
    after the norm — fails: the loss or a gradient leaf moves by more
    than a hundred times the tolerance."""
    fam, config, params, batch, (loss, grads), _ = exact
    published = list(fam.multipliers(config))
    assert published == [12.0, 0.22, 0.015625, 8.0]
    kwargs = {}
    if change == "gate_after_norm":
        monkeypatch.setattr(reference, "mamba2", gate_after_norm)
    else:
        index = ["embedding", "residual", "attention", "logits"].index(
            change)
        published[index] = 1.0 / np.sqrt(16) if change == "attention" \
            else 1.0
        kwargs["multipliers"] = tuple(published)
    want, want_grads = reference_value_and_grads(fam, config, params, batch,
                                                 **kwargs)
    gap, _ = worst(fam, config, grads, want_grads)
    assert max(abs(float(loss) - float(want)), gap) > 100 * TOLERANCE


# ------------------------------------------------------ through the engine

def lm_batch(rows, vocab=512, seed=0, seq=SEQ):
    doc = np.random.default_rng(seed).integers(
        0, vocab, size=(rows, seq + 1), dtype=np.int32)
    return doc[:, :-1].copy(), doc[:, 1:].copy()


def engine_config(rows, **over):
    return {"train_batch_size": rows, "steps_per_print": 10 ** 9,
            "bf16": {"enabled": True}, "gradient_clipping": 1.0,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}, **over}


def test_trains_through_initialize_and_train_batch_with_its_gauges():
    """bf16, ``full``: the loss falls on a repeated batch; the ``model``
    gauges of the step program — the Mamba-2 layers held and the chunks a
    row walks, the readers' denominators — reach the registry and
    ``scalars.step_gauges()`` with no step scalar declared."""
    model = Mamba2HybridLM.from_size("tiny")
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, mesh=make_mesh(devices=jax.devices()[:1]),
        model_parameters=model.init_params(jax.random.PRNGKey(3)),
        config=engine_config(2, activation_checkpointing={
            "enabled": True, "policy": "full"}))
    batch = lm_batch(2)
    losses = [float(engine.train_batch(batch)) for _ in range(3)]
    assert all(np.isfinite(losses)) and losses[2] < losses[1] < losses[0]
    assert abs(losses[0] - np.log(512)) < 0.5
    want = {"layers_mamba2": 2, "layers_attention": 1,
            "layer_applications": 1 * 3, "kv_group": 2, "ssd_chunk": 32,
            "ssd_chunks_per_row": 4}
    assert scalars.step_gauges() == want
    group = engine._telemetry.registry.collect()["model"]
    assert group == {**{k: v for k, v in want.items()
                        if k != "layer_applications"},
                     "layer_applications_per_step": 3}


def test_the_published_shape_and_what_is_refused():
    """The defaults are the published model: 40 layers in four periods of
    nine Mamba-2 layers and one attention layer, 3.19B parameters with the
    whole tied table ("3B"); the cell's period and eighth of the table are
    772.16M, as the configuration file and the family count them."""
    cfg = Mamba2HybridConfig()
    assert cfg.kinds == (("mamba2",) * 5 + ("attention",)
                         + ("mamba2",) * 4) * 4
    count = lambda c: sum(int(np.prod(x.shape)) for x in
                          jax.tree_util.tree_leaves(jax.eval_shape(
                              Mamba2HybridLM(c).init_params,
                              jax.random.PRNGKey(0))))
    assert count(cfg) == 36 * 76_182_976 + 4 * 60_821_504 + 2048 * 100_353
    cell = cells.load(CELL)
    held = dataclasses.replace(
        cfg, vocab_size=12544, segments=cell.family.segments(cell.config))
    assert count(held) == cell.family.parameters(cell.config) == 772_160_448
    with pytest.raises(ValueError, match="tensor parallelism"):
        cfg.validate(mp_size=2)
    with pytest.raises(ValueError, match="whole groups"):
        dataclasses.replace(cfg, num_kv_heads=5).validate()
    with pytest.raises(ValueError, match="segment"):
        dataclasses.replace(cfg, segments=((("mamba",), 1),)).validate()
    model = Mamba2HybridLM(cfg)
    with pytest.raises(ValueError, match="sequence"):
        model.validate(sp_size=2)
    with pytest.raises(ValueError, match="pipeline stages"):
        model.validate(pp_size=2)
    with pytest.raises(NotImplementedError, match="serving"):
        model.kv_cache_dims()
