"""Capacity-planner tests (docs/analysis.md "Capacity planner").

The headline contract: the planner's statically predicted per-device
peak HBM must track XLA's own ``compiled.memory_analysis()`` across the
configuration matrix that changes the memory story — ZeRO stages 0-3,
remat on/off, MP/PP splits, gas>1 — on tiny mlp/gpt2/bert models, within
+-10% relative (with a small absolute floor for toy-scale
buffer-assignment noise: at these sizes XLA's buffer packing decisions
move peaks by ~1 MiB, which would be <0.1% at production scale).

Parity cells run in fp16 with the CPU backend profile: XLA-CPU has no
native half GEMM and materializes fp32 copies of every fp16/bf16 dot
operand — a lowering quirk ``profiles.PROFILES["cpu-8"]`` declares and
the memory model reproduces (and must NOT apply on TPU).  bf16 on CPU
additionally widens elementwise compute unpredictably, so the parity
matrix pins fp16; the planner's TPU predictions use the same walk minus
the quirk.

Plus: wire-cost formulas, the memory.* suppression contract, and the
engine/config/CLI wiring.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu import analysis
from deepspeed_tpu.analysis import commplan, memplan, profiles
from deepspeed_tpu.analysis import report as lint_report
from deepspeed_tpu.parallel.topology import make_mesh

pytestmark = pytest.mark.analysis

H = 32
SEQ = 64
GAS = 2          # gas>1: the accumulation scan is part of the matrix
CPU = profiles.PROFILES["cpu-8"]

#: parity tolerance: 10% relative, with an absolute floor covering XLA
#: buffer-assignment noise at toy scale (see module docstring)
REL_TOL = 0.10
ABS_FLOOR = int(1.5 * 2**20)


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


def _mlp_batch(b):
    rng = np.random.default_rng(0)
    return (rng.normal(size=(b, H)).astype(np.float32),
            rng.normal(size=(b,)).astype(np.float32))


def _gpt2_batch(model, b):
    rng = np.random.default_rng(0)
    toks = rng.integers(0, model.config.vocab_size,
                        (b, SEQ)).astype(np.int32)
    return (toks, toks.copy())


def _bert_batch(model, b):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, model.config.vocab_size,
                       (b, SEQ)).astype(np.int32)
    mask = np.ones((b, SEQ), np.int32)
    tt = np.zeros((b, SEQ), np.int32)
    labels = np.where(rng.random((b, SEQ)) < 0.15, ids, -1)
    return (ids, mask, tt, labels.astype(np.int32))


def _engine(model, mesh=None, **cfg_extra):
    cfg = {"train_batch_size": 16, "gradient_accumulation_steps": GAS,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "fp16": {"enabled": True, "initial_scale_power": 8}}
    cfg.update(cfg_extra)
    kw = {"mesh": mesh} if mesh is not None else {}
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=cfg,
        model_parameters=model.init_params(jax.random.PRNGKey(0)), **kw)
    return eng


def _full_batch_size(eng):
    return (eng.train_micro_batch_size_per_gpu() * eng.dp_world_size
            * eng.gradient_accumulation_steps())


def _xla_peak(eng, batch):
    """XLA's own per-device peak of the fused train_batch program:
    arguments + outputs + temp - aliased (donated outputs reuse argument
    buffers)."""
    key = eng._batch_cache_key(batch)
    fn = eng._cached_batch_fn(eng._train_batch_fns, key,
                              lambda: eng._build_train_batch(batch))
    args = analysis.train_batch_args(eng, batch)
    ma = fn.lower(*args).compile().memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def _assert_parity(eng, batch, label):
    plan = eng.plan_capacity(batch, profile=CPU)
    pred = plan.peak_bytes
    xla = _xla_peak(eng, batch)
    err = abs(pred - xla)
    assert err <= max(REL_TOL * xla, ABS_FLOOR), (
        f"{label}: predicted {pred} vs XLA {xla} "
        f"(ratio {pred / xla:.3f}, |err| {err / 2**20:.2f} MiB)")
    return plan


# ======================================================================
# predicted-vs-XLA peak HBM parity: the verification hook that makes
# this static analysis rather than vibes
# ======================================================================

def test_parity_mlp_stage0():
    eng = _engine(MLP())
    _assert_parity(eng, _mlp_batch(_full_batch_size(eng)), "mlp stage0")


@pytest.mark.parametrize("stage,remat", [
    (0, False), (0, True), (1, False), (1, True),
    (2, False), (2, True), (3, False), (3, True)])
def test_parity_gpt2_zero_stage_x_remat(stage, remat):
    from deepspeed_tpu.models.gpt2 import GPT2
    model = GPT2.from_size("tiny", num_layers=4)
    cfg = {"activation_checkpointing": remat}
    if stage:
        cfg["zero_optimization"] = {"stage": stage}
    eng = _engine(model, **cfg)
    _assert_parity(eng, _gpt2_batch(model, _full_batch_size(eng)),
                   f"gpt2 zero{stage} remat={remat}")


def test_parity_gpt2_mp2():
    from deepspeed_tpu.models.gpt2 import GPT2
    model = GPT2.from_size("tiny", num_layers=4)
    eng = _engine(model, mesh=make_mesh(model_parallel_size=2),
                  model_parallel_size=2)
    _assert_parity(eng, _gpt2_batch(model, _full_batch_size(eng)),
                   "gpt2 mp2")


def test_parity_gpt2_pp2():
    from deepspeed_tpu.models.pipeline_gpt2 import GPT2Pipelined
    model = GPT2Pipelined.from_size("tiny", num_layers=4,
                                    num_micro_batches=2)
    eng = _engine(model, mesh=make_mesh(pipeline_parallel_size=2),
                  pipeline_parallel_size=2)
    _assert_parity(eng, _gpt2_batch(model, _full_batch_size(eng)),
                   "gpt2 pp2")


def test_parity_bert():
    from deepspeed_tpu.models.bert import BertForPreTraining
    model = BertForPreTraining.from_size("tiny")
    eng = _engine(model)
    _assert_parity(eng, _bert_batch(model, _full_batch_size(eng)), "bert")


# ======================================================================
# wire-cost formulas (commplan)
# ======================================================================

def _comm_of(fn, args, mesh_axes, mesh_shape):
    jx = jax.make_jaxpr(fn, axis_env=list(mesh_shape.items()))(*args)
    return commplan.analyze_comm(jx, mesh_shape, profile=CPU)


def test_commplan_psum_ring_bytes():
    x = jnp.ones((1024,), jnp.float32)            # 4096 bytes
    plan = _comm_of(lambda v: jax.lax.psum(v, "data"), (x,), ["data"],
                    {"data": 8})
    [c] = plan.costs
    assert c.primitive == "psum" and c.group_size == 8
    assert c.bytes_per_execution == int(2 * 4096 * 7 / 8)
    assert plan.per_axis_bytes() == {"data": c.bytes_total}


def test_commplan_all_gather_bytes():
    x = jnp.ones((128,), jnp.float32)             # 512 bytes per shard
    plan = _comm_of(
        lambda v: jax.lax.all_gather(v, "data", tiled=True), (x,),
        ["data"], {"data": 8})
    [c] = plan.costs
    assert c.primitive == "all_gather"
    assert c.bytes_per_execution == 512 * 7       # receives 7 other shards


def test_commplan_scan_trip_multiplier():
    x = jnp.ones((64,), jnp.float32)

    def fn(v):
        def body(c, _):
            return jax.lax.psum(c, "data"), ()
        return jax.lax.scan(body, v, None, length=5)[0]

    plan = _comm_of(fn, (x,), ["data"], {"data": 8})
    [c] = plan.costs
    assert c.executions == 5
    assert c.bytes_total == 5 * c.bytes_per_execution


def test_commplan_axis_index_groups_size():
    x = jnp.ones((64,), jnp.float32)
    plan = _comm_of(
        lambda v: jax.lax.psum(v, "data",
                               axis_index_groups=[[0, 1, 2, 3],
                                                  [4, 5, 6, 7]]),
        (x,), ["data"], {"data": 8})
    [c] = plan.costs
    assert c.group_size == 4                      # sub-group, not the axis


def test_commplan_predicted_time_positive():
    x = jnp.ones((1 << 16,), jnp.float32)
    plan = _comm_of(lambda v: jax.lax.psum(v, "data"), (x,), ["data"],
                    {"data": 8})
    t = plan.predicted_time_ms()
    assert t is not None and t > 0
    # DCN-rate data axis is slower than ICI when the mesh spans hosts
    assert plan.predicted_time_ms(multi_host=True) >= t


# ======================================================================
# memory.* findings ride the report machinery (the satellite fix)
# ======================================================================

def test_suppressing_memory_budget_cannot_disable_budget_exceeded():
    """Regression: 'memory.budget' is exact/dotted-prefix only — it must
    NOT silence the distinct error rule 'memory.budget-exceeded' (a
    dash is not a hierarchy separator)."""
    rep = lint_report.Report()
    rep.add("memory.budget", lint_report.WARNING, "near budget")
    rep.add("memory.budget-exceeded", lint_report.ERROR, "over budget")
    kept = rep.filtered(["memory.budget"])
    assert [f.code for f in kept] == ["memory.budget-exceeded"]
    assert kept.suppressed_count == 1
    # the whole family is still suppressible by the pass prefix
    assert len(rep.filtered(["memory"])) == 0


def test_plan_report_severities():
    eng = _engine(MLP())
    batch = _mlp_batch(_full_batch_size(eng))
    plan = eng.plan_capacity(batch, profile=CPU)

    def memory_codes(rep):
        return [f.code for f in rep if f.code.startswith("memory")]

    # comfortable budget -> info; near budget -> warning; over -> error
    import dataclasses as dc
    peak = plan.peak_bytes
    fit = dc.replace(plan, budget_bytes=10 * peak).to_report()
    assert memory_codes(fit) == ["memory.fit"]
    # the wire roll-up rides the report too, as the comm.* family's info
    # rule — suppressible like any other code
    assert [f.code for f in fit if f.code.startswith("comm")] \
        == ["comm.wire"]
    assert len(fit.filtered(["comm.wire"])) == len(fit) - 1
    assert memory_codes(dc.replace(
        plan, budget_bytes=int(peak * 1.05)).to_report()) \
        == ["memory.budget"]
    over = dc.replace(plan, budget_bytes=peak - 1).to_report()
    assert memory_codes(over) == ["memory.budget-exceeded"]
    assert over.errors
    # no budget at all -> report-only info
    assert memory_codes(dc.replace(
        plan, budget_bytes=None).to_report()) == ["memory.no-budget"]


def test_no_budget_no_profile_is_report_only():
    """Regression: with neither analysis.memory_budget_gb nor a profile
    chosen (config or caller), the plan is REPORT-ONLY — plan_engine's
    internal quirk-profile default (cpu-8 on this rig) must not turn
    into a surprise 4 GiB budget gating real configs on dev boxes."""
    eng = _engine(MLP())                 # no analysis section at all
    batch = _mlp_batch(_full_batch_size(eng))
    plan = eng.plan_capacity(batch)      # no explicit profile either
    assert plan.budget_bytes is None
    assert plan.fits() is None
    codes = [f.code for f in plan.to_report()]
    assert "memory.no-budget" in codes
    assert not plan.to_report().errors


def test_budget_exceeded_names_contributors_with_leaf_paths():
    eng = _engine(MLP())
    batch = _mlp_batch(_full_batch_size(eng))
    plan = eng.plan_capacity(batch, profile=CPU, budget_gb=1e-6)
    rep = plan.to_report()
    [f] = rep.errors
    assert f.code == "memory.budget-exceeded"
    assert "MiB" in f.message
    # argument contributors carry engine leaf paths
    assert "master" in f.message or "params" in f.message, f.message


# ======================================================================
# engine wiring: the analysis config key
# ======================================================================

def test_engine_error_mode_raises_memory_plan_error():
    eng = _engine(MLP(), analysis={"mode": "error",
                                   "memory_budget_gb": 1e-6})
    batch = _mlp_batch(_full_batch_size(eng))
    with pytest.raises(analysis.MemoryPlanError) as ei:
        eng.train_batch(batch)
    msg = str(ei.value)
    assert "memory.budget-exceeded" in msg
    assert "contributors" in msg
    # MemoryPlanError must remain catchable as GraphLintError (the
    # machinery contract)
    assert isinstance(ei.value, analysis.GraphLintError)
    # sticky: a retry must plan (and fail) again
    with pytest.raises(analysis.MemoryPlanError):
        eng.train_batch(batch)


def test_engine_suppression_disables_the_gate():
    eng = _engine(MLP(), analysis={
        "mode": "error", "memory_budget_gb": 1e-6,
        "suppress": ["memory.budget-exceeded"]})
    batch = _mlp_batch(_full_batch_size(eng))
    loss = eng.train_batch(batch)       # suppressed: must not raise
    assert np.isfinite(float(loss))


def test_engine_warn_mode_logs_and_trains(caplog):
    import logging
    eng = _engine(MLP(), analysis={"mode": "warn",
                                   "memory_budget_gb": 1e-6})
    batch = _mlp_batch(_full_batch_size(eng))
    with caplog.at_level(logging.WARNING, logger="deepspeed_tpu.engine"):
        loss = eng.train_batch(batch)
    assert np.isfinite(float(loss))
    assert any("capacity plan" in r.message
               and "budget-exceeded" in r.message for r in caplog.records)


def test_engine_split_api_also_gated():
    eng = _engine(MLP(), analysis={"mode": "error",
                                   "memory_budget_gb": 1e-6})
    micro = _mlp_batch(eng.train_micro_batch_size_per_gpu()
                       * eng.dp_world_size)
    with pytest.raises(analysis.MemoryPlanError):
        eng.forward(*micro)


def test_config_rejects_bad_analysis_section():
    from deepspeed_tpu.config import DeepSpeedConfigError
    with pytest.raises(DeepSpeedConfigError):
        _engine(MLP(), analysis={"mode": "loud"})
    with pytest.raises(DeepSpeedConfigError):
        _engine(MLP(), analysis={"memory_budget_gb": -1})
    with pytest.raises(DeepSpeedConfigError):
        _engine(MLP(), analysis={"budget": 1})          # typo'd key
    with pytest.raises(DeepSpeedConfigError):
        _engine(MLP(), analysis={"profile": "v99"})


# ======================================================================
# profiles
# ======================================================================

def test_profile_resolve():
    assert profiles.resolve("v4").name == "v4-8"
    assert profiles.resolve("v4-8").name == "v4-8"
    with pytest.raises(KeyError):
        profiles.resolve("v99")
    assert profiles.PROFILES["cpu-8"].lowp_dot_f32_copies
    assert not profiles.PROFILES["v4-8"].lowp_dot_f32_copies


def test_default_profile_on_cpu_has_dot_copy_quirk():
    prof = profiles.default_profile()
    assert prof is not None and prof.lowp_dot_f32_copies


def test_device_kind_resolves_through_one_table(monkeypatch):
    """The string a chip reports picks its profile — a v5e says "TPU v5
    lite" — and a TPU kind with no row raises instead of borrowing another
    chip's HBM, peak or thresholds."""
    assert profiles.for_device_kind("TPU v5 lite").name == "v5e-8"
    assert profiles.for_device_kind("TPU v5e").name == "v5e-8"
    assert profiles.for_device_kind("TPU v4").name == "v4-8"
    with pytest.raises(KeyError, match="TPU v9 mega"):
        profiles.for_device_kind("TPU v9 mega")

    class FakeChip:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeChip()])
    prof = profiles.default_profile()
    assert prof.name == "v5e-8" and prof.peak_bf16_tflops == 197.0
    assert not prof.persistent_cache_donation_unsafe
    # the attention thresholds come from the same row
    from deepspeed_tpu.models import layers
    for name in ("DSTPU_STREAM_ATTN_MIN", "DSTPU_STREAM_ATTN_MIN_FWD",
                 "DSTPU_STREAM_ATTN_MIN_BWD", "DSTPU_FUSED_ATTN"):
        monkeypatch.delenv(name, raising=False)
    assert layers.attention_plan(512, 16, 64, False) == ("stream", "stream")
    assert layers.attention_plan(128, 16, 64, False) == ("xla", "xla")

    FakeChip.device_kind = "TPU v9 mega"
    with pytest.raises(KeyError, match="no backend profile"):
        profiles.default_profile()
    with pytest.raises(KeyError, match="no backend profile"):
        layers.attention_plan(512, 16, 64, False)


def test_memplan_walks_jit_equations_as_calls():
    """jax 0.9 names the jit primitive ``jit``: memplan must treat it as a
    call (scratch and outputs never coexist), not as an opaque op."""
    inner = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    closed = jax.make_jaxpr(lambda x: inner(x) + 1.0)(
        jnp.ones((256, 256), jnp.float32))
    names = [e.primitive.name for e in closed.jaxpr.eqns]
    assert "jit" in names and "pjit" not in names
    assert "jit" in memplan.CALL_PRIMS
    plan = memplan.analyze_program(lambda x: inner(x) + 1.0,
                                   (jnp.ones((256, 256), jnp.float32),),
                                   profile=CPU)
    buf = 256 * 256 * 4
    # arg + the call's output + one inner temporary; walking the call as
    # an opaque op would stack inner scratch on top of its outputs
    assert 3 * buf <= plan.peak_bytes <= 4 * buf


# ======================================================================
# CLI: --plan / --json (the CI artifact format)
# ======================================================================

def test_cli_plan_json_on_shipped_example():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = os.path.join(repo, "examples", "simple", "ds_config.json")
    r = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.analysis", "--plan",
         "--profile", "v4-8", "--json", "--mode", "error", cfg],
        capture_output=True, text=True, cwd=repo, timeout=540)
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    assert doc["config"] == cfg
    assert doc["plan"]["profile"] == "v4-8"
    assert doc["plan"]["fits"] is True
    assert doc["plan"]["peak_bytes"] > 0
    [prog] = doc["plan"]["programs"]
    assert prog["subject"] == "train_batch"
    assert prog["top_contributors"]
    assert doc["plan"]["comm"]["total_bytes"] >= 0
    assert isinstance(doc["findings"], list)
