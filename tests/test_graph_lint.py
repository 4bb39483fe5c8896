"""Graph-lint analyzer tests (docs/analysis.md).

Three seeded-defect fixtures — a rank-divergent collective order, an
fp32-upcast matmul on the low-precision path, and a hidden host sync —
each must be (a) detected in ``error`` mode with a location-bearing
message and (b) clean after applying the documented fix.  Plus the
engine wiring (``graph_lint`` config key) and the first-class
shard-spec error path that replaced the raw shard_map crash.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import deepspeed_tpu
from deepspeed_tpu import analysis
from deepspeed_tpu.analysis import report as lint_report

pytestmark = pytest.mark.analysis

H = 32


def _mlp_model():
    class MLP:
        def init_params(self, rng):
            k1, k2 = jax.random.split(rng)
            return {"w1": jax.random.normal(k1, (H, H)) / np.sqrt(H),
                    "b1": jnp.zeros((H,)),
                    "w2": jax.random.normal(k2, (H, 1)) / np.sqrt(H)}

        def apply(self, params, x, y):
            x = x.astype(params["w1"].dtype)
            h = jax.nn.relu(x @ params["w1"] + params["b1"])
            pred = (h @ params["w2"])[:, 0].astype(jnp.float32)
            return jnp.mean((pred - y) ** 2)
    return MLP()


def _engine(model, **cfg_extra):
    cfg = {"train_batch_size": 16, "gradient_accumulation_steps": 1,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "fp16": {"enabled": True, "initial_scale_power": 8}}
    cfg.update(cfg_extra)
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=cfg,
        model_parameters=model.init_params(jax.random.PRNGKey(0)))
    return eng


def _batch(b=16):
    rng = np.random.default_rng(0)
    return (rng.normal(size=(b, H)).astype(np.float32),
            rng.normal(size=(b,)).astype(np.float32))


# ======================================================================
# seeded defect 1: rank-divergent collective order (deadlock)
# ======================================================================

def _divergent_fn(x):
    i = lax.axis_index("data")

    def order_a(v):
        v = lax.psum(v, "data")
        return lax.ppermute(v, "data", [(0, 1), (1, 0)])

    def order_b(v):
        v = lax.ppermute(v, "data", [(0, 1), (1, 0)])
        return lax.psum(v, "data")

    return lax.cond(i > 0, order_b, order_a, x)


def _uniform_fn(x):
    i = lax.axis_index("data")

    def order_a(v):
        v = lax.psum(v, "data")
        return lax.ppermute(v, "data", [(0, 1), (1, 0)])

    def scaled(v):
        return order_a(v * 2.0)

    return lax.cond(i > 0, scaled, order_a, x)


def test_seeded_divergent_collective_detected():
    jx = jax.make_jaxpr(_divergent_fn, axis_env=[("data", 2)])(
        jnp.ones((4, 4)))
    rep = analysis.analyze_jaxpr(jx, mesh_axes=["data"])
    errs = [f for f in rep.errors
            if f.code == "collective.divergent-order"]
    assert errs, rep.format()
    # the message must name the divergence and carry a source location
    assert "psum" in errs[0].message and "ppermute" in errs[0].message
    assert "test_graph_lint.py" in errs[0].source
    with pytest.raises(analysis.GraphLintError):
        rep.raise_on_error()


def test_seeded_divergent_collective_fixed_clean():
    jx = jax.make_jaxpr(_uniform_fn, axis_env=[("data", 2)])(
        jnp.ones((4, 4)))
    rep = analysis.analyze_jaxpr(jx, mesh_axes=["data"])
    assert not rep.errors, rep.format()


def test_malformed_ppermute_detected():
    def bad(x):  # rank 1 receives from both 0 and itself
        return lax.ppermute(x, "data", [(0, 1), (1, 1)])
    jx = jax.make_jaxpr(bad, axis_env=[("data", 2)])(jnp.ones((4,)))
    rep = analysis.analyze_jaxpr(jx, mesh_axes=["data"])
    assert any(f.code == "collective.ppermute-malformed"
               for f in rep.errors), rep.format()


def test_divergent_scan_trip_count_detected():
    """Branches scanning the SAME collective body a different number of
    times deadlock at runtime — the trip count is part of the collective
    signature."""
    def bad(x):
        i = lax.axis_index("data")

        def body(c, _):
            return lax.psum(c, "data"), ()

        def twice(v):
            return lax.scan(body, v, None, length=2)[0]

        def thrice(v):
            return lax.scan(body, v, None, length=3)[0]

        return lax.cond(i > 0, thrice, twice, x)

    jx = jax.make_jaxpr(bad, axis_env=[("data", 2)])(jnp.ones((4,)))
    rep = analysis.analyze_jaxpr(jx, mesh_axes=["data"])
    errs = [f for f in rep.errors
            if f.code == "collective.divergent-order"]
    assert errs, rep.format()
    assert "scan[length=" in errs[0].message


def test_upcast_taint_escapes_subjaxpr():
    """An upcast inside a cond whose result feeds an outer fp32 dot must
    still be flagged — taint propagates out of sub-jaxprs."""
    def seeded(x, w, p):
        h = lax.cond(p, lambda v: v.astype(jnp.float32) * 2.0,
                     lambda v: v.astype(jnp.float32), x)
        return jnp.sum(h @ w)

    x = jnp.ones((128, 128), jnp.bfloat16)
    w = jnp.ones((128, 128), jnp.float32)
    rep = analysis.analyze_jaxpr(
        jax.make_jaxpr(seeded)(x, w, jnp.asarray(True)))
    assert any(f.code == "precision.upcast-dot" for f in rep.errors), \
        rep.format()


def test_global_vote_predicate_is_not_rank_dependent():
    """A predicate built from a full-axis psum is replicated on every
    rank — branch-divergent collectives under it are the uniform-predicate
    INFO case, not a deadlock ERROR (the global-vote pattern: a psum'd
    overflow flag selecting a collective-bearing recovery branch)."""
    def vote(x):
        tot = lax.psum(lax.axis_index("data").astype(jnp.float32), "data")

        def with_coll(v):
            return lax.psum(v, "data")

        def without(v):
            return v * 2.0

        return lax.cond(tot > 0, with_coll, without, x)

    jx = jax.make_jaxpr(vote, axis_env=[("data", 2)])(jnp.ones((4,)))
    rep = analysis.analyze_jaxpr(jx, mesh_axes=["data"])
    assert not [f for f in rep.errors
                if f.code == "collective.divergent-order"], rep.format()
    assert any(f.code == "collective.branch-mismatch" for f in rep.infos)


def test_branch_laundered_upcast_not_flagged():
    """Every branch down-casts before returning, so the later bf16 dot
    with fp32 accumulation (the recommended pattern) must stay clean."""
    def fixed(x, w, p):
        xf = x.astype(jnp.float32)
        y = lax.cond(p, lambda a: (a * 2.0).astype(jnp.bfloat16),
                     lambda a: a.astype(jnp.bfloat16), xf)
        return jnp.sum(lax.dot_general(
            y, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))

    x = jnp.ones((128, 128), jnp.bfloat16)
    w = jnp.ones((128, 128), jnp.bfloat16)
    rep = analysis.analyze_jaxpr(
        jax.make_jaxpr(fixed)(x, w, jnp.asarray(True)))
    assert not [f for f in rep.errors
                if f.code == "precision.upcast-dot"], rep.format()


def test_unknown_axis_detected():
    def bad(x):
        return lax.psum(x, "bogus")
    jx = jax.make_jaxpr(bad, axis_env=[("bogus", 2)])(jnp.ones((4,)))
    rep = analysis.analyze_jaxpr(jx, mesh_axes=["data", "model"])
    assert any(f.code == "collective.axis-unknown" for f in rep.errors)


# ======================================================================
# seeded defect 2: fp32 upcast on the low-precision matmul path
# ======================================================================

def test_seeded_upcast_dot_detected():
    def seeded(x, w):
        h = x.astype(jnp.float32)      # the defect: upcast before the dot
        return jnp.sum(h @ w)
    x = jnp.ones((128, 128), jnp.bfloat16)
    w = jnp.ones((128, 128), jnp.float32)
    rep = analysis.analyze_jaxpr(jax.make_jaxpr(seeded)(x, w))
    errs = [f for f in rep.errors if f.code == "precision.upcast-dot"]
    assert errs, rep.format()
    assert "test_graph_lint.py" in errs[0].source


def test_seeded_upcast_dot_fixed_clean():
    def fixed(x, w):
        # the documented fix: keep operands low-precision, accumulate fp32
        return jnp.sum(lax.dot_general(
            x, w.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
    x = jnp.ones((128, 128), jnp.bfloat16)
    w = jnp.ones((128, 128), jnp.float32)
    rep = analysis.analyze_jaxpr(jax.make_jaxpr(fixed)(x, w))
    assert not rep.errors, rep.format()


def test_xla_attention_backward_stays_lowp():
    """Regression for the finding the analyzer surfaced in-tree: the
    score-einsum transpose used to run the dq/dk dots in fp32 on
    bf16/fp16 inputs (now a custom VJP rounding the cotangent first)."""
    from deepspeed_tpu.ops import pallas_attention as pattn
    q = jnp.ones((2, 64, 2, 16), jnp.float16)
    mask = jnp.ones((2, 64), jnp.float32)

    def loss(q, k, v):
        out, _ = pattn.xla_attention(q, k, v, mask, causal=True)
        return jnp.sum(out.astype(jnp.float32))

    jx = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    rep = analysis.analyze_jaxpr(jx)
    assert not [f for f in rep.errors
                if f.code == "precision.upcast-dot"], rep.format()


def test_xla_attention_fp32_grads_unchanged():
    """The custom VJP must be an identity in fp32."""
    from deepspeed_tpu.ops import pallas_attention as pattn
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 16, 2, 8)), jnp.float32)
               for _ in range(3))
    mask = jnp.ones((2, 16), jnp.float32)

    def loss_custom(q, k, v):
        return jnp.sum(pattn.xla_attention(q, k, v, mask, True)[0])

    def loss_plain(q, k, v):
        scores = jnp.einsum("btnd,bsnd->bnts", q, k,
                            preferred_element_type=jnp.float32)
        scores = scores / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
        cmask = jnp.tril(jnp.ones((16, 16), jnp.bool_))
        scores = jnp.where(cmask[None, None], scores, -1e9)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.sum(jnp.einsum("bnts,bsnd->btnd", probs, v))

    ga = jax.grad(loss_custom, argnums=(0, 1, 2))(q, k, v)
    gb = jax.grad(loss_plain, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


# ======================================================================
# seeded defect 3: hidden host sync
# ======================================================================

def _sync_model(fixed=False):
    """pure_callback has no autodiff rule, so the seeded host sync lives
    in the loss *reporting* path — exactly where they hide in real code
    (a per-step metric normalisation bounced through numpy)."""
    class M:
        def init_params(self, rng):
            return {"w": jax.random.normal(rng, (H, 1)) / np.sqrt(H)}

        def apply(self, params, x, y):
            x = x.astype(params["w"].dtype)
            pred = (x @ params["w"])[:, 0].astype(jnp.float32)
            loss = jnp.mean((pred - y) ** 2)
            if not fixed:
                # the defect: per-step host round trip inside the program
                loss = jax.pure_callback(
                    lambda a: np.asarray(a),
                    jax.ShapeDtypeStruct((), jnp.float32), loss)
            return loss
    return M()


def test_seeded_host_sync_detected():
    eng = _engine(_sync_model())
    rep = eng.run_graph_lint(_batch(), train=False)
    errs = [f for f in rep.errors if f.code == "transfer.host-callback"]
    assert errs, rep.format()
    assert "test_graph_lint.py" in errs[0].source


def test_seeded_host_sync_fixed_clean():
    eng = _engine(_sync_model(fixed=True))
    rep = eng.run_graph_lint(_batch(), train=False)
    assert not rep.errors, rep.format()


def test_spool_drain_callback_allowlisted():
    """The telemetry MetricSpool's batched drain io_callback is the ONE
    sanctioned ordered host transfer: linted as ``transfer.spool-drain``
    (info), NOT as a host-sync error (docs/observability.md)."""
    from deepspeed_tpu.observability.spool import MetricSpool

    sp = MetricSpool(4, on_window=lambda rows, pos: None)
    closed = jax.make_jaxpr(sp.drain_program())(sp.state)
    rep = analysis.analyze_jaxpr(closed, subject="spool_drain")
    assert not rep.errors, rep.format()
    assert any(f.code == "transfer.spool-drain" for f in rep.infos), \
        rep.format()


def test_unspooled_io_callback_still_errors():
    """The allowlist keys on the drain marker, not the primitive: any
    OTHER per-step io_callback in a step program stays an error."""
    from jax.experimental import io_callback

    def step(x):
        io_callback(lambda v: None, None, x.sum(), ordered=True)
        return x * 2

    rep = analysis.analyze_jaxpr(jax.make_jaxpr(step)(jnp.ones(8)),
                                 subject="bad_step")
    errs = [f for f in rep.errors if f.code == "transfer.host-callback"]
    assert errs, rep.format()
    assert not any(f.code == "transfer.spool-drain" for f in rep.infos)


# ======================================================================
# engine wiring: the graph_lint config key
# ======================================================================

def test_engine_error_mode_raises_at_build():
    eng = _engine(_sync_model(), graph_lint="error").eval()
    with pytest.raises(analysis.GraphLintError) as ei:
        eng.forward(*_batch())
    assert "transfer.host-callback" in str(ei.value)


def test_engine_error_mode_is_sticky_on_retry():
    """A retried forward of the same batch format must lint (and fail)
    again — not silently proceed because the format was already seen."""
    eng = _engine(_sync_model(), graph_lint="error").eval()
    for _ in range(2):
        with pytest.raises(analysis.GraphLintError):
            eng.forward(*_batch())


def test_engine_warn_mode_logs_and_runs(caplog):
    import logging
    eng = _engine(_sync_model(), graph_lint="warn").eval()
    with caplog.at_level(logging.WARNING, logger="deepspeed_tpu.engine"):
        loss = eng.forward(*_batch())
    assert np.isfinite(float(loss))
    assert any("graph lint" in r.message and "host-callback" in r.message
               for r in caplog.records)


def test_engine_suppression():
    eng = _engine(_sync_model(), graph_lint={
        "mode": "error", "suppress": ["transfer.host-callback"]}).eval()
    loss = eng.forward(*_batch())     # suppressed: must not raise
    assert np.isfinite(float(loss))


def test_engine_off_mode_is_silent(caplog):
    import logging
    eng = _engine(_sync_model()).eval()   # default mode: off
    with caplog.at_level(logging.WARNING, logger="deepspeed_tpu.engine"):
        eng.forward(*_batch())
    assert not any("graph lint" in r.message for r in caplog.records)


def test_clean_engine_error_mode_trains():
    eng = _engine(_mlp_model(), graph_lint="error")
    loss = eng.forward(*_batch())
    eng.backward(loss)
    eng.step()
    assert np.isfinite(float(loss))


def test_config_rejects_bad_mode():
    from deepspeed_tpu.config import DeepSpeedConfigError
    with pytest.raises(DeepSpeedConfigError):
        _engine(_mlp_model(), graph_lint="loud")


# ======================================================================
# first-class shard-spec error path (the PR-1 crash class)
# ======================================================================

def test_indivisible_batch_raises_readable_error():
    eng = _engine(_mlp_model())
    dp = eng.dp_world_size
    bad = _batch(b=dp + 1)            # leading dim not divisible by dp
    with pytest.raises(analysis.ShardSpecError) as ei:
        eng.forward(*bad)
    msg = str(ei.value)
    assert "'data'" in msg or "data" in msg       # names the axis
    assert "batch" in msg                         # names the leaf family
    assert str(dp + 1) in msg                     # names the actual size


def test_bad_model_batch_spec_raises_readable_error():
    from jax.sharding import PartitionSpec as P

    class BadSpecs:
        def init_params(self, rng):
            return {"w": jax.random.normal(rng, (H, 1)) / np.sqrt(H)}

        def batch_specs(self, batch):
            # 'ctx' is not a mesh axis (the typo'd-spec variant of the
            # PR-1 crash class)
            return (P("ctx"), P("data"))

        def apply(self, params, x, y):
            x = x.astype(params["w"].dtype)
            pred = (x @ params["w"])[:, 0].astype(jnp.float32)
            return jnp.mean((pred - y) ** 2)

    eng = _engine(BadSpecs())
    with pytest.raises(analysis.ShardSpecError) as ei:
        eng.forward(*_batch(b=eng.dp_world_size))
    msg = str(ei.value)
    assert "ctx" in msg and "mesh" in msg


def test_eval_path_also_validates():
    eng = _engine(_mlp_model()).eval()
    with pytest.raises(analysis.ShardSpecError):
        eng.forward(*_batch(b=eng.dp_world_size + 1))


def test_train_batch_path_also_validates():
    eng = _engine(_mlp_model())
    gas = eng.gradient_accumulation_steps()
    bad = _batch(b=gas * (eng.dp_world_size + 1))
    with pytest.raises(analysis.ShardSpecError):
        eng.train_batch(bad)


# ======================================================================
# report mechanics
# ======================================================================

def test_suppression_prefix_matching():
    rep = lint_report.Report()
    rep.add("precision.upcast-dot", lint_report.ERROR, "a")
    rep.add("precision.upcast", lint_report.INFO, "b")
    rep.add("transfer.host-callback", lint_report.ERROR, "c")
    assert len(rep.filtered(["precision"])) == 1
    # exact/dotted-prefix only: silencing the INFO rule must NOT also
    # disable the distinct ERROR rule "precision.upcast-dot"
    assert len(rep.filtered(["precision.upcast"])) == 2
    assert len(rep.filtered(["precision.upcast-dot"])) == 2
    assert rep.filtered(["precision"]).suppressed_count == 2


def test_report_format_collapses_noise():
    rep = lint_report.Report()
    for _ in range(12):
        rep.add("precision.upcast", lint_report.INFO, "x")
    text = rep.format()
    assert "+7 more" in text


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_pipeline_schedules_lint_clean(schedule):
    """The GPipe and 1F1B schedules in parallel/pipeline.py are built from
    rank-dependent masking (``jnp.where`` on axis_index) around a
    collective-uniform program — the analyzer must find no divergent
    collective order across stages (and must keep finding none as the
    schedules evolve: a stage-dependent collective there IS a deadlock)."""
    from deepspeed_tpu.models.pipeline_gpt2 import GPT2Pipelined
    from deepspeed_tpu.parallel.topology import make_mesh
    model = GPT2Pipelined.from_size("tiny", num_micro_batches=2,
                                    schedule=schedule)
    cfg = {"train_batch_size": 8, "gradient_accumulation_steps": 1,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
           "fp16": {"enabled": True, "initial_scale_power": 8}}
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=cfg, mesh=make_mesh(pipeline_parallel_size=2),
        model_parameters=model.init_params(jax.random.PRNGKey(0)))
    b = eng.train_micro_batch_size_per_gpu() * eng.dp_world_size
    rng = np.random.default_rng(0)
    toks = rng.integers(0, model.config.vocab_size, (b, 64)).astype(np.int32)
    rep = eng.run_graph_lint((toks, toks.copy()))
    assert not rep.errors, rep.format()
    assert not [f for f in rep
                if f.code == "collective.divergent-order"], rep.format()


def test_cli_clean_on_shipped_example():
    """The CI gate in miniature: the CLI in --mode error must exit 0 on a
    shipped example config."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = os.path.join(repo, "examples", "simple", "ds_config.json")
    r = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.analysis", "--mode", "error",
         cfg],
        capture_output=True, text=True, cwd=repo, timeout=540)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "graph lint" in r.stdout


def test_prefix_tree_spec_still_validated():
    """A spec pytree may be a PREFIX of the value pytree (one spec for a
    whole subtree — valid shard_map in_specs): the gate must apply it to
    every leaf underneath, not silently skip validation."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()).reshape(-1, 1), ("data", "model"))
    dp = mesh.shape["data"]
    rep = analysis.check_shard_specs(
        mesh, P("data"), (np.zeros((dp - 1, 8)), np.zeros((dp - 1,))))
    assert len([f for f in rep.errors
                if f.code == "shardspec.indivisible"]) == 2, rep.format()


def test_all_to_all_layout_divergence_detected():
    """all_to_all calls differing only in split/concat dims exchange
    mismatched buffers — the layout params are part of the signature."""
    def bad(x):
        i = lax.axis_index("data")

        def a(v):
            return lax.all_to_all(v, "data", split_axis=0, concat_axis=1)

        def b(v):
            return lax.all_to_all(v, "data", split_axis=1, concat_axis=0)

        return lax.cond(i > 0, b, a, x)

    jx = jax.make_jaxpr(bad, axis_env=[("data", 2)])(jnp.ones((2, 2, 2)))
    rep = analysis.analyze_jaxpr(jx, mesh_axes=["data"])
    assert any(f.code == "collective.divergent-order"
               for f in rep.errors), rep.format()


def test_upcast_through_scan_carry_detected():
    """An upcast created in iteration N reaching a dot in iteration N+1
    through the scan carry (the dot precedes the upcast in body order)."""
    def seeded(xs, c0):
        def body(c, x):
            z = lax.dot_general(c, c, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            h = x.astype(jnp.float32)          # upcast inside the body
            return c + h, jnp.sum(z)
        c, zs = lax.scan(body, c0, xs)
        return jnp.sum(zs)

    xs = jnp.ones((2, 64, 64), jnp.bfloat16)
    c0 = jnp.zeros((64, 64), jnp.float32)
    rep = analysis.analyze_jaxpr(jax.make_jaxpr(seeded)(xs, c0))
    assert any(f.code == "precision.upcast-dot" for f in rep.errors), \
        rep.format()


def test_shard_spec_pass_rank_overflow():
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()).reshape(-1, 1), ("data", "model"))
    rep = analysis.check_shard_specs(
        mesh, {"x": P("data", "model")}, {"x": np.ones((8,))})
    assert any(f.code == "shardspec.rank" for f in rep.errors)


# ------------------------------------------- bucketed wire-format checks

def test_divergent_bucket_shapes_detected():
    """Collective signatures include the operand shape (the wire format):
    two rank-divergent branches issuing the SAME primitive over the same
    axis but with DIFFERENT bucket tilings are a real deadlock — ranks in
    either branch would block exchanging mismatched buffers: the failure
    class of a schedule that chunks a reduction per branch."""
    def bad(x):
        r = lax.axis_index("data")

        def bucketed(v):
            return jnp.sum(lax.psum(v.reshape(2, 8), "data"))

        def monolithic(v):
            return jnp.sum(lax.psum(v, "data"))

        return lax.cond(r > 0, bucketed, monolithic, x)

    jx = jax.make_jaxpr(bad, axis_env=[("data", 2)])(jnp.ones((16,)))
    rep = analysis.analyze_jaxpr(jx, mesh_axes=["data"])
    errs = [f for f in rep.errors
            if f.code == "collective.divergent-order"]
    assert errs, rep.format()
    assert "operand" in errs[0].message, errs[0].message


def test_same_bucket_shapes_clean():
    """Identical bucketed sequences in both branches stay quiet."""
    def ok(x):
        r = lax.axis_index("data")

        def bucketed(v):
            halves = [lax.psum(v[:8], "data"), lax.psum(v[8:], "data")]
            return jnp.sum(jnp.concatenate(halves))

        return lax.cond(r > 0, bucketed,
                        lambda v: bucketed(v * 2.0), x)

    jx = jax.make_jaxpr(ok, axis_env=[("data", 2)])(jnp.ones((16,)))
    rep = analysis.analyze_jaxpr(jx, mesh_axes=["data"])
    assert not [f for f in rep.errors
                if f.code == "collective.divergent-order"], rep.format()


def test_donation_lint_sees_jit_equations():
    """jax 0.9 names the jit primitive ``jit`` (it was ``pjit``): the
    donation lint must find the jit level, flag a large undonated input
    whose shape matches an output, and stay quiet once it is donated."""
    x = jnp.ones((1024, 1024), jnp.float32)          # 4 MiB

    def findings(fn):
        rep = analysis.analyze_step(lambda a: fn(a), (x,))
        return [f for f in rep.infos if f.code == "transfer.donation"]

    assert "jit" in str(jax.make_jaxpr(jax.jit(lambda a: a + 1.0))(x))
    assert len(findings(jax.jit(lambda a: a + 1.0))) == 1
    assert findings(jax.jit(lambda a: a + 1.0, donate_argnums=0)) == []
