"""``deepspeed_tpu.models.DeltaMoELM`` through ``deepspeed_tpu.initialize``
→ ``engine.train_batch``: it trains (the loss falls on a repeated batch),
the expert layers' step scalars reach the spool's window event and the
``model`` gauges describe the delta rule's chunks, tensor / expert
parallelism over the ``model`` axis gives the one-device loss, and
``validate`` refuses what the model is not built for with a sentence each.
Tiny sizes, CPU.  (The model against its reference:
tests/test_delta_moe_model.py.)"""

import json

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import DeltaMoELM
from deepspeed_tpu.observability import schema
from deepspeed_tpu.parallel.topology import make_mesh

SEQ = 64


def lm_batch(rows, vocab=512, seed=0, seq=SEQ):
    doc = np.random.default_rng(seed).integers(
        0, vocab, size=(rows, seq + 1), dtype=np.int32)
    return doc[:, :-1].copy(), doc[:, 1:].copy()


def tiny(**over):
    return DeltaMoELM.from_size("tiny", **{"experts_held": (4, 4), **over})


def engine_config(rows, **over):
    return {"train_batch_size": rows, "steps_per_print": 10 ** 9,
            "bf16": {"enabled": True}, "gradient_clipping": 1.0,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}, **over}


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    log = tmp_path_factory.mktemp("events") / "events.jsonl"
    model = tiny()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, mesh=make_mesh(devices=jax.devices()[:1]),
        model_parameters=model.init_params(jax.random.PRNGKey(3)),
        config=engine_config(
            2, activation_checkpointing={"enabled": True, "policy": "full"},
            observability={"report_window": 2, "jsonl_path": str(log)}))
    batch = lm_batch(2)
    losses = [float(engine.train_batch(batch)) for _ in range(3)]
    engine.flush_telemetry()
    return engine, losses, log


def test_trains_through_initialize_and_train_batch(trained):
    engine, losses, _ = trained
    assert engine.module.config.remat_policy == "full"
    assert all(np.isfinite(losses))
    assert losses[2] < losses[1] < losses[0]
    # ln(512) + four layers' balance loss of ~c k each
    assert abs(losses[0] - np.log(512)) < 0.5


def test_the_step_scalars_reach_the_spool(trained):
    """Window 2, three steps: one drained window and the flush's partial
    one, each with ITS steps' ``moe/*`` scalars; the ``model`` gauges name
    the delta rule's chunk layout beside the expert layers' rows."""
    engine, _, log = trained
    assert schema.validate_jsonl(str(log)) == []
    events = [json.loads(line) for line in log.read_text().splitlines()]
    windows = [e for e in events if e["schema"] == schema.SCHEMA_ID]
    assert [e["window_steps"] for e in windows] == [2, 1]
    pairs = 2 * SEQ * 3
    for event in windows:
        got = event["scalars"]
        assert set(got) == {"moe/overflow_passes", "moe/held_pairs",
                            "moe/max_expert_rows"}
        assert got["moe/overflow_passes"] == 0
        per_layer = got["moe/held_pairs"] / (4 * event["window_steps"])
        assert 0.1 * pairs < per_layer < 0.4 * pairs
    read = engine.read_step_scalars()
    assert read["steps"] == 3 and read["values"]["moe/held_pairs"] == sum(
        e["scalars"]["moe/held_pairs"] for e in windows)
    gauges = read["gauges"]
    assert (gauges["layers_gdn"], gauges["layers_full"],
            gauges["layers_moe"]) == (3, 1, 4)
    assert (gauges["delta_chunk"], gauges["delta_chunks_per_sequence"],
            gauges["delta_state_bytes_per_layer"]) == (64, 1, 4 * 4 * 8 * 8)
    assert gauges["delta_kernel"] == 0             # no TPU here: XLA's walk
    assert (gauges["routed_rows_prefix"], gauges["routed_rows_all"]) == (
        256, pairs)
    engine.telemetry.close()                   # the JSONL sink's file


def test_the_model_axis_gives_the_one_device_loss(trained):
    """``model`` = 2: a key/value head, a DeltaNet key head with its two
    value heads, half the shared expert's width, half the vocabulary and
    two of the four experts held a shard; a ``psum`` adds the parts.  The
    same weights and batch as the one-device engine above."""
    model = tiny()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=engine_config(2),
        mesh=make_mesh(model_parallel_size=2, devices=jax.devices()[:2]),
        model_parameters=model.init_params(jax.random.PRNGKey(3)))
    batch = lm_batch(2)
    two = [float(engine.train_batch(batch)) for _ in range(2)]
    np.testing.assert_allclose(two, trained[1][:2], rtol=3e-3)


def test_validate_refuses_with_a_sentence_each():
    model = tiny()
    with pytest.raises(ValueError, match="matrix state and the "
                                         "convolution's last steps"):
        model.validate(sp_size=2)
    with pytest.raises(ValueError, match="pipeline stages"):
        model.validate(pp_size=2)
    with pytest.raises(NotImplementedError, match="state kind of page"):
        model.kv_cache_dims()
    with pytest.raises(ValueError, match="context parallelism|sequence"):
        deepspeed_tpu.initialize(
            model=tiny(), config=engine_config(2),
            mesh=make_mesh(context_parallel_size=2,
                           devices=jax.devices()[:2]))
