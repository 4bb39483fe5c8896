"""``deepspeed_tpu.models.HybridLM`` (a decoder-hybrid-decoder stack: Mamba,
sliding-window, full and cross-decoder attention on shared keys and values,
Gated Memory Units) and the mixers it brought to ``layers.py``.  Tiny sizes,
CPU.  The plain reference is ``benchmark/reference/phi4flash.py``, which
imports nothing of the program.  (Through the engine, and the other
layouts: tests/test_hybrid_engine.py — a file of its own, so that a run by
files spreads the compiles over two workers.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmark import cell as cells
from deepspeed_tpu.models import HybridConfig, HybridLM
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models import layers as L
from deepspeed_tpu.parallel.topology import make_mesh

SEQ = 48
CELL = "phi4-mini-flash.seq8192"


def moved(params, seed=1):
    """Every leaf off its initial value: a swapped or dropped leaf shows."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return treedef.unflatten(
        [x + 0.05 * jax.random.normal(k, x.shape) for x, k in
         zip(leaves, keys)])


def lm_batch(rows, vocab=512, seed=0, seq=SEQ):
    doc = np.random.default_rng(seed).integers(
        0, vocab, size=(rows, seq + 1), dtype=np.int32)
    return doc[:, :-1].copy(), doc[:, 1:].copy()


def on_one_device(fn, *args):
    mesh = make_mesh(devices=jax.devices()[:1])
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P(),) * len(args), out_specs=P(),
        check_vma=False))(*args)


def tiny(**over):
    return HybridLM.from_size("tiny", **over)


@pytest.fixture(scope="module")
def setting():
    model = tiny()
    params = moved(model.init_params(jax.random.PRNGKey(0)))
    return model, params, lm_batch(2)


# -------------------------------------------------- against the reference

@pytest.fixture(scope="module")
def family():
    return cells.load(CELL)


@pytest.mark.parametrize("pattern", ["held", "whole"])
def test_loss_and_every_gradient_agree_with_the_reference(family, pattern):
    """``held``: the cell's six layers (14-19 of 32, here 4-9 of 12);
    ``whole``: all twelve, ``(mamba, swa) x 3``, ``(mamba, full)``, ``(gmu,
    cross) x 2`` — several periods a side, so the scans stack."""
    fam = family.family
    config = fam.tiny(family.config)
    if pattern == "whole":
        config = {**config, "layers_held": list(range(12))}
    config = {**config, "rehearsal_seq": SEQ}
    model = fam.build_model(config, {"seq": SEQ})
    assert model.config.first_layer == config["layers_held"][0]
    params = moved(model.init_params(jax.random.PRNGKey(0)))
    batch = fam.make_batch(np.random.default_rng(0), 2, config, {"seq": SEQ})
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: on_one_device(model.apply, p, *batch))(params)
        ref, ref_grads = jax.jit(jax.value_and_grad(
            lambda p: fam.reference_loss(p, batch, config)))(params)
    assert float(loss) == pytest.approx(float(ref), rel=1e-5)
    worst = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-30)), grads, ref_grads)
    flat = {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_leaves_with_path(worst)}
    # float32 sums in another order: 1e-5 of a leaf's largest gradient; the
    # lambda vectors' gradients are differences of two exponentials' and
    # four orders under the matrices': 5e-5 of theirs
    for name, err in flat.items():
        assert err < (5e-5 if "lam_" in name else 1e-5), (name, err)
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree_util.tree_leaves(grads))


# ------------------------------------------- what the second half reads

def layer_list(model, params):
    """[(kind, depth, parameters)] of every layer, unstacked."""
    cfg, out, depth = model.config, [], model.config.first_layer
    for (kinds, repeats), stacked in zip(cfg.segments, params["blocks"]):
        for r in range(repeats):
            for j, kind in enumerate(kinds):
                out.append((kind, depth, jax.tree_util.tree_map(
                    lambda w: w[r], stacked[f"l{j}"])))
                depth += 1
    return out


def unrolled_loss(model, params, layers, batch, live):
    """The model's equations with a Python loop over the layers — no scan,
    no checkpoint.  ``live`` [layers] of 0 / 1: whether the hand-over a
    layer READS carries a gradient (the values are the same either way)."""
    cfg = model.config
    tokens, labels = batch
    x = L.vocab_parallel_embedding(tokens, params["wte"])
    shared = {}
    for i, (kind, depth, p) in enumerate(layers):
        given = jax.tree_util.tree_map(
            lambda t: live[i] * t + (1 - live[i]) * jax.lax.stop_gradient(t),
            shared)
        x, made = hybrid.layer_apply(kind, cfg, x, p, jnp.int32(depth), given)
        if kind in ("mamba", "full"):
            shared.update(made)
    x = L.layer_norm(x, params["lnf_s"], params["lnf_b"], cfg.ln_eps)
    ce = L.vocab_parallel_cross_entropy(
        L.vocab_parallel_logits(x, params["wte"]), labels)
    return L.masked_mean_loss(ce, labels >= 0)


def test_source_gradient_is_its_own_plus_the_sum_over_the_readers():
    """The full layer's k/v projections (and the last Mamba layer's scan
    inputs) collect their own use's gradient and one from every layer that
    reads the hand-over: the scanned model's gradient is the sum over
    untied paths, one reader's hand-over live at a time."""
    model = tiny(segments=((("mamba", "full"), 1), (("gmu", "cross"), 2)))
    params = moved(model.init_params(jax.random.PRNGKey(0)))
    batch = lm_batch(2)
    layers = layer_list(model, params)
    kinds = [k for k, _, _ in layers]
    assert kinds == ["mamba", "full", "gmu", "cross", "gmu", "cross"]
    readers = [2, 3, 4, 5]

    @jax.jit
    def grads(live):
        def loss(ls):
            return on_one_device(
                lambda ls, live: unrolled_loss(
                    model, params, [(k, d, p) for (k, d, _), p in
                                    zip(layers, ls)], batch, live), ls, live)
        return jax.grad(loss)([p for _, _, p in layers])

    own = grads(jnp.zeros(6))
    per_reader = [grads(jnp.zeros(6).at[i].set(1.0)) for i in readers]
    tied = jax.grad(lambda p: on_one_device(model.apply, p, *batch))(params)
    for index, leaf, name, kind in ((1, "l1", "k_w", "cross"),
                                    (1, "l1", "v_w", "cross"),
                                    (0, "l0", "x_w", "gmu"),
                                    (0, "l0", "A_log", "gmu")):
        mine = own[index][name]
        adds = [g[index][name] - mine for g in per_reader]
        scale = float(jnp.max(jnp.abs(mine)))
        # a reader of its kind adds to the layer it reads; a GMU's memory
        # has no path to the full layer's projections (a cross layer's K
        # and V have one to the Mamba layer before it, through the stream)
        for i, add in zip(readers, adds):
            size = float(jnp.max(jnp.abs(add)))
            if kinds[i] == kind:
                assert size > 1e-3 * scale, (name, i)
            elif kind == "cross":
                assert size <= 1e-6 * scale, (name, i)
        np.testing.assert_allclose(
            tied["blocks"][0][leaf][name][0], mine + sum(adds), rtol=1e-4,
            atol=1e-5 * scale)


def test_memory_is_the_scan_output_before_the_gate(setting):
    """``mamba_mixer``'s second result is ``y``: the mixer's output is ``(y
    * silu(z)) W_out`` of it, and it is what the source segment hands to
    the Gated Memory Units."""
    model, params, _ = setting
    cfg = model.config
    p = jax.tree_util.tree_map(lambda w: w[0], params["blocks"][1]["l0"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, cfg.hidden_size))
    out, y = on_one_device(
        lambda x, p: L.mamba_mixer(x, p, state=cfg.ssm_state,
                                   dt_rank=cfg.dt_rank), x, p)
    assert y.shape == (2, SEQ, cfg.ssm_channels)
    np.testing.assert_allclose(out, (y * L.silu(x @ p["in_z_w"]))
                               @ p["out_w"], rtol=1e-4, atol=1e-5)
    gmu = jax.tree_util.tree_map(lambda w: w[0], params["blocks"][2]["l0"])
    got = on_one_device(L.gated_memory_unit, x, y, gmu)
    np.testing.assert_allclose(got, (y * L.silu(x @ gmu["w1"])) @ gmu["w2"],
                               rtol=1e-4, atol=1e-5)


def test_window_limits_what_a_query_sees(setting):
    """Sliding-window layer: the output at position t does not move with
    the input more than ``window`` - 1 positions back; the full layer's
    does."""
    model, params, _ = setting
    cfg = model.config
    p = jax.tree_util.tree_map(lambda w: w[0], params["blocks"][0]["l1"])
    x = jax.random.normal(jax.random.PRNGKey(6), (1, SEQ, cfg.hidden_size))
    far = x.at[:, 3].add(1.0)

    def run(x, window):
        return on_one_device(lambda x, p: L.differential_attention(
            x, p, head_dim=cfg.head_dim, lam_init=jnp.float32(0.5),
            eps=cfg.ln_eps, window=window)[0], x, p)

    t = 3 + cfg.window
    assert float(jnp.max(jnp.abs(run(far, cfg.window)[:, t:]
                                 - run(x, cfg.window)[:, t:]))) == 0
    assert float(jnp.max(jnp.abs(run(far, cfg.window)[:, t - 1]
                                 - run(x, cfg.window)[:, t - 1]))) > 0
    assert float(jnp.max(jnp.abs(run(far, None)[:, t:]
                                 - run(x, None)[:, t:]))) > 0


def test_the_streaming_plan_repeats_no_key_or_value_head(monkeypatch):
    """On the streaming plan (interpreter; seq 256 is the kernel's least)
    every attention layer is ONE forward kernel call on 8 query heads, 4 key
    heads and 2 value heads of twice the width — found by index map, nothing
    repeated to the query heads — and under ``selective`` (as under
    ``full``) no call is replayed: 3 layers x (forward + fused backward)."""
    import functools
    from deepspeed_tpu.ops import pallas_attention as pattn
    monkeypatch.setattr(L, "attention_plan", lambda *a, **kw: "stream")
    monkeypatch.setattr(pattn, "stream_attention", functools.partial(
        pattn.stream_attention, interpret=True))
    model = tiny(window=64, remat_policy="selective",
                 segments=((("mamba", "swa"), 1), (("mamba", "full"), 1),
                           (("gmu", "cross"), 1)))
    params = model.init_params(jax.random.PRNGKey(0))
    batch = lm_batch(1, seq=256)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: on_one_device(model.apply, p, *batch)))(params)
    calls, pending = [], [jaxpr.jaxpr]
    while pending:
        for eqn in pending.pop().eqns:
            if eqn.primitive.name == "pallas_call":
                calls.append([tuple(v.aval.shape) for v in eqn.invars])
            pending.extend(jax.core.jaxprs_in_params(eqn.params))
    assert len(calls) == 6
    forward = [c for c in calls if len(c) == 4]
    assert len(forward) == 3
    for q, k, v, _mask in forward:
        assert (q, k, v) == ((8, 256, 16), (4, 256, 16), (2, 256, 32))


def test_bf16_loss_and_gradient_stay_near_float32(setting):
    model, params, batch = setting
    as_bf16 = jax.tree_util.tree_map(lambda w: w.astype(jnp.bfloat16), params)
    run = lambda p: jax.value_and_grad(
        lambda p: on_one_device(model.apply, p, *batch))(p)
    loss32, g32 = run(params)
    loss16, g16 = run(as_bf16)
    assert loss16.dtype == jnp.float32
    assert abs(float(loss16) - float(loss32)) < 2e-2
    for seg, layer, name in ((0, "l0", "in_u_w"), (1, "l1", "k_w"),
                             (2, "l0", "w1"), (2, "l1", "q_w")):
        a = g16["blocks"][seg][layer][name].astype(jnp.float32)
        b = g32["blocks"][seg][layer][name]
        assert g16["blocks"][seg][layer][name].dtype == jnp.bfloat16
        rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert rel < 0.1, (name, rel)


def test_long_sequences_take_the_head_in_blocks(monkeypatch):
    """The head's row blocks change no value: 64 positions in blocks of 16
    give the whole head's loss and gradients."""
    batch = lm_batch(2, seq=64)
    model = tiny(segments=((("mamba", "swa"), 1),))
    params = moved(model.init_params(jax.random.PRNGKey(0)))

    def run(rows):
        monkeypatch.setattr(hybrid, "HEAD_BLOCK_ROWS", rows)
        return jax.value_and_grad(
            lambda p: on_one_device(model.apply, p, *batch))(params)

    loss, grads = run(16)
    want, want_grads = run(64)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(grads["wte"], want_grads["wte"], rtol=1e-4,
                               atol=1e-7)


def test_config_refuses_what_is_not_built():
    ok = HybridLM.from_size("tiny")
    ok.validate(2)
    assert ok.config.kinds.count("mamba") == 3
    assert HybridConfig().kinds.count("mamba") == 9
    assert [HybridConfig().kinds.count(k) for k in hybrid.KINDS] == [
        9, 8, 1, 7, 7]
    with pytest.raises(ValueError, match="context parallelism"):
        ok.validate(1, sp_size=2)
    with pytest.raises(ValueError, match="pipeline stages"):
        ok.validate(1, pp_size=2)
    with pytest.raises(NotImplementedError, match="serving"):
        ok.kv_cache_dims()
    with pytest.raises(ValueError, match="groups of four"):
        ok.validate(4)                        # 2 groups of four query heads
    with pytest.raises(ValueError, match="runs once"):
        tiny(segments=((("mamba", "full"), 2), (("gmu", "cross"), 1))
             ).validate()
    with pytest.raises(ValueError, match="runs once"):
        tiny(segments=((("mamba", "swa"), 1), (("gmu", "cross"), 1))
             ).validate()
    with pytest.raises(ValueError, match="runs once"):
        tiny(segments=((("gmu", "cross"), 1),)).validate()
    with pytest.raises(ValueError, match="pairs the heads"):
        tiny(num_kv_heads=8).validate()
    # a stack with no reader needs no source
    tiny(segments=((("mamba", "swa"), 2),)).validate()
