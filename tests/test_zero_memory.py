"""ZeRO-1 memory envelope: the headline claim, measured.

The reference's pitch is max-model-size — ZeRO-1 fits ~6B params where
replicated data parallelism caps at ~1.3B on the same GPUs
(/root/reference/README.md:88-96), because optimizer state (fp32 master +
Adam moments = 12 bytes/param) shrinks by ~dp x while params/grads don't.
These tests measure LIVE per-device bytes of engine state on the 8-device
mesh and pin that arithmetic; docs/features.md publishes the derived
max-model-size table.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import GPT2
from deepspeed_tpu.parallel.topology import make_mesh

pytestmark = pytest.mark.slow

VOCAB, SEQ = 64, 16


def device_bytes(arrs, device):
    """Bytes the given device holds across the arrays (each device shard
    counted once — replicas on OTHER devices are what ZeRO eliminates)."""
    total = 0
    for a in jax.tree_util.tree_leaves(arrs):
        if a is None or not hasattr(a, "addressable_shards"):
            continue
        for s in a.addressable_shards:
            if s.device == device:
                total += int(np.prod(s.data.shape)) * s.data.dtype.itemsize
    return total


def make_engine(zero, dp_devices=8, **cfg_over):
    cfg = {
        "train_batch_size": dp_devices,
        "steps_per_print": 10 ** 6,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "fp16": {"enabled": True, "initial_scale_power": 8},
    }
    if zero:
        cfg["zero_optimization"] = zero
    cfg.update(cfg_over)
    model = GPT2.from_size("tiny", vocab_size=VOCAB, max_seq_len=SEQ,
                           num_layers=2, hidden_size=32, num_heads=4)
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=cfg, model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(7)),
        mesh=make_mesh(devices=jax.devices()[:dp_devices]))
    return engine


def opt_state_bytes(engine, device):
    """Optimizer-residency bytes: fp32 master + Adam moments."""
    master = engine.master_flat if engine.zero_enabled else engine.master
    return (device_bytes(master, device)
            + device_bytes(engine.opt_state.m, device)
            + device_bytes(engine.opt_state.v, device))


def test_zero1_optimizer_state_partition_ratio():
    """Per-device optimizer-state bytes under ZeRO-1 are ~1/dp of the
    replicated engine's (the (dp-1)/dp reduction the reference's
    max-model-size table rests on) — params stay replicated (same bytes)."""
    dev = jax.devices()[0]
    repl = make_engine(zero=None)
    zero = make_engine(zero={"stage": 1})
    dp = zero.dp_world_size
    assert dp == 8

    repl_opt = opt_state_bytes(repl, dev)
    zero_opt = opt_state_bytes(zero, dev)
    n = int(sum(np.prod(l.shape) for l in
                jax.tree_util.tree_leaves(repl.master)))
    # replicated: every device holds full fp32 master + m + v = 12 bytes/p
    assert repl_opt == 12 * n, (repl_opt, n)
    # ZeRO-1: each device holds its 1/dp partition of all three buffers;
    # the flat layout pads to a multiple of dp*1024 elements (zero.FLAT_ALIGN)
    padded = zero.flat_meta.padded
    assert zero_opt == 12 * padded // dp, (zero_opt, padded)
    assert zero_opt <= repl_opt / dp + 12 * 1024  # ratio holds past padding

    # compute params are replicated in BOTH engines (ZeRO-1 partitions
    # optimizer state only — stage-1 semantics, zero.py docstring)
    assert (device_bytes(repl.params, dev)
            == device_bytes(zero.params, dev))


def test_pps_subgroups_trade_memory_for_gather_locality():
    """parameter_parallel_size=4 under dp=8 doubles per-device optimizer
    bytes vs full-DP partitioning (each sub-group of 4 holds the full
    state) — the documented memory/locality trade."""
    dev = jax.devices()[0]
    full = make_engine(zero={"stage": 1})
    sub = make_engine(zero={"stage": 1, "parameter_parallel_size": 4})
    b_full = opt_state_bytes(full, dev)
    b_sub = opt_state_bytes(sub, dev)
    # partition size scales with 1/pps; padding differs (dp*1024 vs pps*1024)
    assert b_sub == 12 * sub.flat_meta.padded // 4
    assert abs(b_sub - 2 * b_full) <= 12 * 512


def test_memory_estimate_matches_live_bytes():
    """engine.memory_estimate() is EXACT against the measured per-device
    buffers for replicated, ZeRO-1, and ZeRO-2 engines."""
    dev = jax.devices()[0]
    for zero in (None, {"stage": 1}, {"stage": 2}):
        engine = make_engine(zero=zero)
        est = engine.memory_estimate()
        assert est["optimizer_state_bytes"] == opt_state_bytes(engine, dev)
        assert est["params_bytes"] == device_bytes(engine.params, dev)
        if zero:
            assert est["zero_stage"] == zero["stage"]
        # the ZeRO-2 accumulator estimate matches what backward() holds
        if zero == {"stage": 2}:
            toks = np.random.default_rng(0).integers(
                0, VOCAB, size=(8, SEQ)).astype(np.int32)
            labels = np.roll(toks, -1, axis=1)
            loss = engine(toks, labels)
            engine.backward(loss)
            assert est["grad_accumulator_bytes"] == device_bytes(
                engine._acc, dev)
            engine.step()


def test_zero_memory_envelope_after_training_step():
    """The partition ratio survives real steps (no hidden replicated copies
    appear in the step program's outputs)."""
    dev = jax.devices()[0]
    zero = make_engine(zero={"stage": 1})
    toks = np.random.default_rng(0).integers(
        0, VOCAB, size=(8, SEQ)).astype(np.int32)
    labels = np.roll(toks, -1, axis=1)
    zero.train_batch((toks, labels))
    assert opt_state_bytes(zero, dev) == 12 * zero.flat_meta.padded // 8


def test_memory_estimate_moment_counts():
    """The estimator counts the moments the optimizer actually keeps:
    SGD(momentum=0) has none, RMSprop one, Adam two."""
    dev = jax.devices()[0]
    for opt, want_moments in (({"type": "SGD", "params": {"lr": 0.1}}, 0),
                              ({"type": "RMSprop",
                                "params": {"lr": 0.01}}, 1),
                              ({"type": "Adam", "params": {"lr": 1e-3}}, 2)):
        engine = make_engine(zero=None, optimizer=opt)
        est = engine.memory_estimate()
        n = est["n_params"]
        assert est["optimizer_state_bytes"] == 4 * (1 + want_moments) * n, (
            opt, est)
        assert est["optimizer_state_bytes"] == opt_state_bytes(engine, dev)
