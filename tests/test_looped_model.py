"""``deepspeed_tpu.models.LoopedLM`` (a looped, weight-shared-depth LM with
exits and an exit gate) and the block pieces it brought to ``layers.py`` /
``transformer.py``: RMSNorm, rotary positions, the gated FFN, the sandwich
block.  Tiny sizes, CPU.  The comparison with the plain reference is in
tests/benchmark_harness/test_bench_ouro.py."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.models import LoopedConfig, LoopedLM
from deepspeed_tpu.models import layers as L
from deepspeed_tpu.models import looped
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.observability import scalars, scopes
from deepspeed_tpu.ops import pallas_attention as pattn
from deepspeed_tpu.parallel.topology import make_mesh

SEQ = 32


def moved(params, seed=1):
    """Every leaf off its initial value (norm scales start at one, the gate's
    bias at zero): a swapped or dropped leaf then shows."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return treedef.unflatten(
        [x + 0.05 * jax.random.normal(k, x.shape) for x, k in
         zip(leaves, keys)])


def lm_batch(rows, vocab=512, seed=0):
    doc = np.random.default_rng(seed).integers(
        0, vocab, size=(rows, SEQ + 1), dtype=np.int32)
    return doc[:, :-1].copy(), doc[:, 1:].copy()


def on_one_device(fn, *args):
    """``fn`` on local shards inside shard_map, one device."""
    mesh = make_mesh(devices=jax.devices()[:1])
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P(),) * len(args), out_specs=P(),
        check_vma=False))(*args)


def tiny(**over):
    return LoopedLM.from_size("tiny", **over)


@pytest.fixture(scope="module")
def setting():
    model = tiny()
    params = moved(model.init_params(jax.random.PRNGKey(0)))
    return model, params, lm_batch(2)


def unrolled_loss(cfg, params, copies, tokens, labels):
    """The model's equations written out with a Python loop over passes and
    layers — no scan, no checkpoint — pass ``t`` running the stack
    ``copies[t]``."""
    rope = L.rotary_tables(tokens.shape[1], cfg.head_dim, cfg.rope_theta)
    h = L.vocab_parallel_embedding(tokens, params["wte"])
    ce, gate = [], []
    for blocks in copies:
        for i in range(cfg.num_layers):
            layer = jax.tree_util.tree_map(lambda w: w[i], blocks)
            h = T.sandwich_block_apply(h, layer, cfg, rope)
        h = L.rms_norm(h, params["normf_s"], cfg.norm_eps)
        ce.append(L.vocab_parallel_cross_entropy(
            L.vocab_parallel_logits(h, params["head"]), labels))
        gate.append(h.astype(jnp.float32) @ params["gate_w"].astype(
            jnp.float32) + params["gate_b"].astype(jnp.float32))
    p, log_p = looped.exit_distribution(jnp.stack(gate))
    per_position = jnp.sum(
        p * (jnp.stack(ce) + cfg.exit_entropy_weight * log_p), axis=0)
    return L.masked_mean_loss(per_position, labels >= 0)


def test_shared_gradient_is_the_sum_over_four_untied_copies(setting):
    """Weight sharing: the looped model's gradient of a block leaf equals the
    sum, over four untied copies of the stack, of the unrolled model's
    gradients (and the scan-of-scans with its checkpoints computes what the
    plain Python loops compute)."""
    model, params, batch = setting
    cfg = model.config
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: on_one_device(model.apply, p, *batch))(params)
        copies = [params["blocks"]] * cfg.loop_passes
        plain, (g_rest, g_copies) = jax.value_and_grad(
            lambda p, c: on_one_device(
                lambda p, c, *b: unrolled_loss(cfg, p, c, *b), p, c, *batch),
            argnums=(0, 1))(params, copies)
    assert float(loss) == pytest.approx(float(plain), rel=1e-6)
    assert len(g_copies) == 4
    for name, got in grads["blocks"].items():
        per_pass = [g[name] for g in g_copies]
        # every pass contributes: no copy's gradient is the whole of it
        assert all(float(jnp.max(jnp.abs(g))) > 0 for g in per_pass)
        np.testing.assert_allclose(got, sum(per_pass), rtol=2e-4,
                                   atol=1e-6 * float(jnp.max(jnp.abs(got))))
    for name in ("wte", "head", "normf_s", "gate_w", "gate_b"):
        np.testing.assert_allclose(
            grads[name], g_rest[name], rtol=2e-4,
            atol=1e-6 * float(jnp.max(jnp.abs(grads[name]))))


def test_one_pass_is_a_plain_stacks_cross_entropy(setting):
    """With one pass there is one exit and it takes everything: the gate and
    the entropy weight drop out and the loss is the sandwich stack's mean
    cross-entropy."""
    _, params, batch = setting
    losses = []
    for beta, gate_b in ((0.1, 0.0), (5.0, -40.0)):
        model = tiny(loop_passes=1, exit_entropy_weight=beta)
        p = {**params, "gate_b": params["gate_b"] + gate_b}
        losses.append(float(on_one_device(model.apply, p, *batch)))
    cfg = tiny(loop_passes=1).config

    def plain(p, tokens, labels):
        rope = L.rotary_tables(tokens.shape[1], cfg.head_dim, cfg.rope_theta)
        h = L.vocab_parallel_embedding(tokens, p["wte"])
        for i in range(cfg.num_layers):
            h = T.sandwich_block_apply(
                h, jax.tree_util.tree_map(lambda w: w[i], p["blocks"]), cfg,
                rope)
        h = L.rms_norm(h, p["normf_s"], cfg.norm_eps)
        return jnp.mean(L.vocab_parallel_cross_entropy(
            L.vocab_parallel_logits(h, p["head"]), labels))

    want = float(on_one_device(plain, params, *batch))
    assert losses[0] == pytest.approx(want, rel=1e-6)
    assert losses[1] == pytest.approx(want, rel=1e-6)


def test_exit_distribution_sums_to_one_and_survives_a_shut_gate():
    z = jax.random.normal(jax.random.PRNGKey(0), (4, 3, 5)) * 3.0
    p, log_p = looped.exit_distribution(z)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    lam = jax.nn.sigmoid(z)
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(
        p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), rtol=1e-5,
        atol=1e-7)
    # a gate forced shut (or open) at every exit: no 0 * log 0, and a finite
    # gradient
    for z0 in (-60.0, 60.0):
        shut = jnp.full((4, 2), z0)
        p, log_p = looped.exit_distribution(shut)
        assert float(p.sum(0)[0]) == pytest.approx(1.0)
        g = jax.grad(lambda z: jnp.sum(
            looped.exit_distribution(z)[0]
            * looped.exit_distribution(z)[1]))(shut)
        assert bool(jnp.all(jnp.isfinite(g)))
        assert bool(jnp.all(jnp.isfinite(p * log_p)))


def test_beta_zero_removes_the_entropy_term(setting):
    model, params, batch = setting
    beta = model.config.exit_entropy_weight
    full = float(on_one_device(model.apply, params, *batch))
    none = float(on_one_device(tiny(exit_entropy_weight=0.0).apply, params,
                               *batch))
    reported = on_one_device(tiny(report_exits=True).apply, params, *batch)
    # the loss WITH the exits as step scalars (observability/scalars.py),
    # one value per pass of each, and not a tuple of nine "losses"
    assert isinstance(reported, scalars.WithScalars)
    assert sorted(reported.scalars) == ["loop/exit_ce", "loop/exit_prob"]
    ce = np.asarray(reported.scalars["loop/exit_ce"])
    p = np.asarray(reported.scalars["loop/exit_prob"])
    assert ce.shape == p.shape == (4,)
    assert tiny(report_exits=True).step_scalars() == {
        "loop/exit_ce": 4, "loop/exit_prob": 4}
    assert tiny().step_scalars() == {}
    assert float(reported.loss) == pytest.approx(full, rel=1e-6)
    assert p.sum() == pytest.approx(1.0, abs=1e-5)
    assert (ce > 0).all()
    # the entropy of the exit distribution only lowers the loss, by at most
    # beta * ln 4
    assert 0 < none - full <= beta * np.log(4) + 1e-6


def test_rotary_depends_on_the_distance_only():
    d, T_len = 32, 24
    rope = L.rotary_tables(T_len, d, 1e4)
    q0, k0 = jax.random.normal(jax.random.PRNGKey(0), (2, d))
    rq = L.apply_rotary(jnp.broadcast_to(q0, (1, T_len, 1, d)), rope)[0, :, 0]
    rk = L.apply_rotary(jnp.broadcast_to(k0, (1, T_len, 1, d)), rope)[0, :, 0]
    scores = rq @ rk.T                                  # [i, j]
    for shift in (1, 5, 11):
        np.testing.assert_allclose(scores[shift:, shift:],
                                   scores[:-shift, :-shift], atol=2e-5)
    # position 0 is the identity, a rotation keeps the length, and distinct
    # distances give distinct scores
    np.testing.assert_allclose(rq[0], q0, atol=1e-6)
    np.testing.assert_allclose(jnp.linalg.norm(rq, axis=-1),
                               jnp.linalg.norm(q0), rtol=1e-5)
    assert abs(float(scores[3, 0] - scores[0, 3])) > 1e-3
    # the pairing is (i, i + d/2): the first pair turns by the position
    x = jnp.zeros((1, T_len, 1, d)).at[..., 0].set(1.0)
    y = L.apply_rotary(x, rope)[0, :, 0]
    np.testing.assert_allclose(y[:, 0], np.cos(np.arange(T_len)), atol=1e-5)
    np.testing.assert_allclose(y[:, d // 2], np.sin(np.arange(T_len)),
                               atol=1e-5)


def test_rms_norm_and_gated_mlp_by_hand():
    x = jnp.asarray([[3.0, 4.0], [0.0, 0.0]])
    y = L.rms_norm(x, jnp.asarray([2.0, 0.5]), eps=0.0 + 1e-12)
    np.testing.assert_allclose(y[0], [2 * 3 / np.sqrt(12.5),
                                      0.5 * 4 / np.sqrt(12.5)], rtol=1e-6)
    assert bool(jnp.all(y[1] == 0))
    # bf16 in, bf16 out, statistic in fp32
    assert L.rms_norm(x.astype(jnp.bfloat16), jnp.ones(2)).dtype == \
        jnp.bfloat16
    p = {"gate_w": jnp.asarray([[1.0, -1.0]]), "up_w": jnp.asarray(
        [[2.0, 3.0]]), "down_w": jnp.asarray([[1.0], [1.0]])}
    out = on_one_device(lambda u, p: T._gated_mlp(u, p),
                        jnp.asarray([[1.0]]), p)
    silu = lambda v: v / (1 + np.exp(-v))
    assert float(out[0, 0]) == pytest.approx(silu(1.0) * 2 + silu(-1.0) * 3,
                                             rel=1e-6)


def test_ffn_width_is_the_ratio_unless_given():
    cfg = T.TransformerConfig(hidden_size=64, num_heads=4, mlp_ratio=4)
    assert cfg.ffn_width == 256
    wide = dataclasses.replace(cfg, ffn_size=176)
    assert wide.ffn_width == 176
    blocks = T.init_block_params(
        dataclasses.replace(wide, num_layers=2), jax.random.PRNGKey(0))
    assert blocks["fc_w"].shape == (2, 64, 176)
    assert blocks["fc2_w"].shape == (2, 176, 64)
    with pytest.raises(ValueError, match="FFN width"):
        dataclasses.replace(wide, ffn_size=177).validate(mp_size=2)


def test_config_refuses_what_the_block_cannot_run():
    with pytest.raises(ValueError, match="heads"):
        LoopedConfig(num_heads=3).validate(mp_size=2)
    with pytest.raises(ValueError, match="even head_dim"):
        LoopedConfig(head_dim=63).validate()
    with pytest.raises(ValueError, match="ring"):
        LoopedConfig(sp_impl="ulysses").validate()
    with pytest.raises(ValueError, match="loop_passes"):
        LoopedConfig(loop_passes=0).validate()
    assert tiny().step_counts() == {"loop_passes": 4, "exits": 4,
                                    "layer_applications": 8}


# ----------------------------------------------------- the shared gradient

def shared_weight_accumulators(model, params, batch):
    """dtypes of the loop carries, in the backward pass's pass loop, that
    have a stacked block weight's shape: the running sums of the shared
    weights' gradients over the passes."""
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: on_one_device(model.apply, p, *batch)))(params)
    shapes = {w.shape for w in jax.tree_util.tree_leaves(params["blocks"])
              if w.ndim == 3}
    found = []

    def visit(jp):
        for eqn in jp.eqns:
            if (eqn.primitive.name == "scan"
                    and eqn.params["length"] == model.config.loop_passes):
                n_consts = eqn.params["num_consts"]
                n_carry = eqn.params["num_carry"]
                for var in eqn.invars[n_consts:n_consts + n_carry]:
                    if var.aval.shape in shapes:
                        found.append(var.aval.dtype)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                visit(sub)

    visit(jaxpr.jaxpr)
    return found


def test_shared_gradient_accumulates_in_the_weights_dtype(setting):
    """In which precision the sum over the four passes runs (PERF.md, PR 26):
    jax carries a closed-over constant's cotangent through the backward scan
    as an accumulator of the constant's dtype — bf16 under the engine's bf16
    policy, fp32 under fp32.  A change of that (an fp32 copy of the weights,
    a hand-written backward) must show here."""
    model, params, batch = setting
    as_bf16 = jax.tree_util.tree_map(lambda w: w.astype(jnp.bfloat16), params)
    for tree, dtype in ((as_bf16, jnp.bfloat16), (params, jnp.float32)):
        found = shared_weight_accumulators(model, tree, batch)
        # q, k, v, o, gate, up, down
        assert len(found) == 7 and set(found) == {jnp.dtype(dtype)}


def test_bf16_loss_and_gradient_stay_near_float32(setting):
    model, params, batch = setting
    as_bf16 = jax.tree_util.tree_map(lambda w: w.astype(jnp.bfloat16), params)
    run = lambda p: jax.value_and_grad(
        lambda p: on_one_device(model.apply, p, *batch))(p)
    loss32, g32 = run(params)
    loss16, g16 = run(as_bf16)
    assert loss16.dtype == jnp.float32
    # the cell's loss_tolerance (benchmark/configs/ouro-2.6b.json)
    assert abs(float(loss16) - float(loss32)) < 2e-3
    for name in ("q_w", "down_w"):
        a, b = g16["blocks"][name].astype(jnp.float32), g32["blocks"][name]
        assert g16["blocks"][name].dtype == jnp.bfloat16
        rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert rel < 0.05, (name, rel)


@pytest.mark.parametrize("policy", ["full", "selective"])
def test_no_policy_replays_the_streaming_kernel(monkeypatch, policy):
    """On the streaming plan (interpreter; seq 256 is the kernel's least) a
    layer application costs two ``pallas_call``s, the forward and the fused
    backward: the gradient's jaxpr holds the layer scan's body once forward
    and once backward, and the rematerialised body has no third.  The saved
    output and log-sum-exp are the values a replay would produce, so the
    loss is that of recomputation off, bit for bit, and the gradients its
    gradients to test_selective_remat.py's tolerance."""
    monkeypatch.setattr(L, "attention_plan",
                        lambda *shape, **kw: ("stream", "stream"))
    monkeypatch.setattr(pattn, "stream_attention", functools.partial(
        pattn.stream_attention, interpret=True))
    toks = np.random.default_rng(3).integers(
        0, 512, size=(1, 257), dtype=np.int32)
    batch = (toks[:, :-1].copy(), toks[:, 1:].copy())
    run = lambda model: jax.value_and_grad(
        lambda p: on_one_device(model.apply, p, *batch))
    model = tiny(remat_policy=policy)
    params = moved(model.init_params(jax.random.PRNGKey(0)))
    text = str(jax.make_jaxpr(run(model))(params))
    assert len(re.findall(r"\bpallas_call\b", text)) == 2
    loss, got = run(model)(params)
    want_loss, want = run(tiny(remat=False))(params)
    assert float(loss) == float(want_loss)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------- the engine

def engine_config(rows, **over):
    return {"train_batch_size": rows, "steps_per_print": 10 ** 9,
            "bf16": {"enabled": True}, "gradient_clipping": 1.0,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}, **over}


@pytest.fixture(scope="module")
def trained():
    """A tiny engine, three steps on one batch under ``full`` recomputation;
    (engine, losses, the step's scope map)."""
    model = tiny()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, mesh=make_mesh(devices=jax.devices()[:1]),
        config=engine_config(2, activation_checkpointing={
            "enabled": True, "policy": "full"}))
    batch = lm_batch(2)
    losses = [float(engine.train_batch(batch)) for _ in range(3)]
    return engine, losses, scopes.step_scope_map()


def test_trains_through_initialize_and_train_batch(trained):
    engine, losses, _ = trained
    assert engine.module.config.remat_policy == "full"
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    # near the loss of a uniform guess less the exit distribution's entropy
    assert abs(losses[0] - np.log(512)) < 0.5


def test_the_step_holds_the_loop_scopes_in_every_phase(trained):
    _, _, names = trained
    phases = {(s, p) for s, p in names.values() if s}
    for scope in ("dstpu/rope", "dstpu/attn", "dstpu/ffn", "dstpu/norm"):
        assert {(scope, "forward"), (scope, "replay"),
                (scope, "backward")} <= phases, scope
    # the pass loop's own instructions (the carried sums of the shared
    # weights' gradients) run in the backward pass; the gate and the exit
    # distribution forward and backward; each exit's head is replayed
    assert {("dstpu/loop", "forward"), ("dstpu/loop", "backward")} <= phases
    assert {("dstpu/exit", "forward"), ("dstpu/exit", "backward")} <= phases
    assert {("dstpu/head", "forward"), ("dstpu/head", "replay"),
            ("dstpu/head", "backward")} <= phases
    assert ("dstpu/block", "backward") in phases
    assert ("dstpu/embed", "forward") in phases
    # a hybrid stack's scopes (tests/test_step_scopes.py) are not this one's
    assert not {s for s, _ in phases} & {
        "dstpu/ssm", "dstpu/scan", "dstpu/conv", "dstpu/swa", "dstpu/xattn",
        "dstpu/gmu"}


def test_model_telemetry_group_reports_the_loop(trained):
    engine, _, _ = trained
    counters = engine._telemetry.registry.collect()["model"]
    assert counters == {"loop_passes": 4, "exits": 4,
                        "layer_applications_per_step": 8}


def test_other_models_have_no_model_group():
    from deepspeed_tpu.models import GPT2
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2.from_size("tiny"), config=engine_config(8),
        mesh=make_mesh(devices=jax.devices()[:1]))
    assert "model" not in engine._telemetry.registry.collect()


def test_report_exits_rides_the_fused_step():
    """The per-exit cross-entropies and the mean exit distribution leave
    the one fused program as step scalars (``read_step_scalars()``), not as
    more losses: ``train_batch`` returns the loss alone, bit for bit the
    plain model's, and no gradient changes."""
    batch = lm_batch(2)
    runs = {}
    for report in (False, True):
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=tiny(report_exits=report), config=engine_config(2),
            mesh=make_mesh(devices=jax.devices()[:1]))
        out = [engine.train_batch(batch) for _ in range(2)]
        runs[report] = (out, jax.tree_util.tree_map(np.asarray,
                                                    engine.params), engine)
    plain, reported = runs[False][0], runs[True][0]
    for a, b in zip(plain, reported):
        assert np.shape(b) == ()                  # one scalar: the loss
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(runs[False][1]),
                    jax.tree_util.tree_leaves(runs[True][1])):
        np.testing.assert_array_equal(a, b)
    assert runs[False][2].read_step_scalars() is None
    read = runs[True][2].read_step_scalars()
    assert (read["steps"], read["micro_steps"]) == (2, 2)
    ce, p = (np.asarray(read["values"][name]) / read["micro_steps"]
             for name in ("loop/exit_ce", "loop/exit_prob"))
    # sums over the two micro-steps: the means are a division at read time
    assert p.shape == (4,) and p.sum() == pytest.approx(1.0, abs=1e-3)
    assert ce.shape == (4,) and (ce > 0).all()
    # the registry's ``model`` group flattens a vector entry
    group = runs[True][2].telemetry.registry.collect()["model"]
    assert group["loop/exit_prob.3"] == read["values"]["loop/exit_prob"][3]
    assert group["loop_passes"] == 4 and group["scalar_steps"] == 2


def test_report_exits_leaves_the_spools_loss_column_to_the_loss():
    """With the metric spool on, the window event's ``loss`` is the loss
    (before PR 35 the exits' eight values were summed into it) and the
    exits ride ``scalars``, a list of one value per pass each."""
    events = []
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=tiny(report_exits=True), mesh=make_mesh(
            devices=jax.devices()[:1]),
        config=engine_config(2, observability={"report_window": 2}))
    engine.telemetry.registry.add_sink(type("Sink", (), {
        "emit": lambda self, event, sample_count=None: events.append(event),
        "close": lambda self: None})())
    losses = [float(engine.train_batch(lm_batch(2))) for _ in range(2)]
    engine.flush_telemetry()
    window, = [e for e in events if "window_steps" in e]
    assert window["loss"] == pytest.approx(losses[-1], rel=1e-6)
    assert window["loss_mean"] == pytest.approx(np.mean(losses), rel=1e-6)
    prob = np.asarray(window["scalars"]["loop/exit_prob"]) / 2
    assert prob.shape == (4,) and prob.sum() == pytest.approx(1.0, abs=1e-3)
    assert len(window["scalars"]["loop/exit_ce"]) == 4


@pytest.mark.parametrize("layout", ["tp2", "sp2", "dp2-zero1", "dp2-zero3"])
def test_other_layouts_agree_with_one_device(layout):
    """Tensor parallelism (the Megatron split of the separate projections
    and the gated FFN, vocabulary-parallel embedding and head), context
    parallelism (rotary positions offset per sequence shard, the rotated k
    and v round the ring), ZeRO-1 and ZeRO-3 (the stack gathered once per
    pass) give the one-device loss."""
    batch = lm_batch(4)
    losses = {}
    for name in ("one", layout):
        over, mesh = {}, make_mesh(devices=jax.devices()[:1])
        if name == "tp2":
            mesh = make_mesh(model_parallel_size=2,
                             devices=jax.devices()[:2])
        elif name == "sp2":
            mesh = make_mesh(context_parallel_size=2,
                             devices=jax.devices()[:2])
        elif name.startswith("dp2"):
            mesh = make_mesh(devices=jax.devices()[:2])
            over["zero_optimization"] = {"stage": int(name[-1])}
        config = engine_config(4, **over)
        model = tiny()
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=config, mesh=mesh,
            model_parameters=model.init_params(jax.random.PRNGKey(3)))
        losses[name] = [float(engine.train_batch(batch)) for _ in range(2)]
    # bf16 compute (ZeRO wants a half-precision policy): the layouts differ
    # in the order of their sums only
    np.testing.assert_allclose(losses[layout], losses["one"], rtol=2e-3)
