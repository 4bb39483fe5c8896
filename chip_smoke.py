"""chip_smoke.py — the quickest proof that the trainer still starts on a TPU.

Drives the main path once through the entry points a user calls
(``deepspeed_tpu.initialize`` → ``engine.train_batch`` and the split
``engine(batch)`` / ``backward`` / ``step``) at BERT-large's published
widths, in ONE process (a chip belongs to one process), with random weights
and data made from a seed.  Nothing is read from outside the checkout.

Legs (any one that raises, yields a non-finite loss or breaks an assertion
ends the process non-zero — no leg sits inside a catch):

1. BERT-large seq 128 — ``bench.py:run_config``'s shapes (micro-batch 24,
   20 masked positions, bf16 + LAMB, selective remat) at gas 4 on one chip:
   three optimizer steps through the split API, then the fused
   ``train_batch`` compile and three steps.
2. The same model at seq 512 (micro-batch 6, 80 masked positions): the
   streaming Pallas kernel against ``xla_attention`` (forward, fused and
   split backward) at the BERT and a GPT-2 shape, the backward as
   ``stream_bwd_plan`` sizes it against the split at the two T 8,192
   shapes (where the fused call asks Mosaic for more scoped VMEM than its
   default: a libtpu that refuses the limit fails here, not in a cell),
   the attention plan, the Pallas custom call in the lowered step, then
   steps.
3. With four or more devices: the leg-1 model under ZeRO-1 on the default
   ``make_mesh()`` — one process, every chip — checked against a one-chip
   run of the same global batch and seed.  Both sides use Adam: the engine
   admits only Adam-family optimizers under ZeRO-1 (engine.py, "ZeRO
   guard").

A TPU is required: on any other platform the script exits non-zero before
it prints a result.  ``--rehearse-cpu`` runs the same code at a tiny size on
four virtual CPU devices to debug the script itself; it says
``platform: cpu`` and is never the default.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

import argparse
import collections
import contextlib
import gc
import importlib.metadata
import json
import math
import os
import sys
import time
from unittest import mock

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0

# Sizes.  FULL is the published BERT-large configuration at bench.py's
# shapes; TINY exists only for --rehearse-cpu.
FULL = dict(size="large", micro128=24, micro512=6, gas=4, steps=3,
            parity=[((6, 512, 16, 64), False),     # BERT-large phase 2
                    ((2, 1024, 12, 64), True)],    # GPT-2 small widths
            # (B, T, query / key / value heads, d, dv, window), causal:
            # the latent core (2 x 8192, 16 heads of 192 / 128) and the
            # hybrid stack's full and windowed calls (40 / 20 / 10 heads of
            # 64 / 128) — too long for an XLA reference, so fused against
            # split
            backward=[(2, 8192, (16, 16, 16), 192, 128, None),
                      (1, 8192, (40, 20, 10), 64, 128, None),
                      (1, 8192, (40, 20, 10), 64, 128, 512)])
TINY = dict(size="tiny", micro128=4, micro512=2, gas=4, steps=3,
            parity=[((1, 512, 2, 64), False), ((1, 512, 2, 64), True)],
            backward=[(1, 1024, (2, 2, 2), 192, 128, None),
                      (1, 1024, (4, 2, 1), 64, 128, 512)])

# Kernel-vs-XLA tolerance, as max|kernel - xla| / max|xla| per tensor.
# Inputs and outputs are bf16 (8 significand bits: one ulp is 2^-8 = 0.4% of
# a value).  Both paths accumulate in fp32 but round at different points —
# XLA normalises the probabilities before rounding them to bf16 for the P·V
# matmul, the streaming kernel rounds the unnormalised tile and divides at
# the end, and the tiles add up in another order — so they differ by a few
# ulps of the largest element: 2e-2 is five.  The backward rounds dS to bf16
# as well and sums over 512-1024 positions, so it gets 4e-2.  Measured on a
# v5e: 0.005 forward, at most 0.0077 backward.  A wrong mask, scale or tile
# offset gives errors of order 1.
PARITY_TOL_FWD = 2e-2
PARITY_TOL_BWD = 4e-2

# dp=4 vs one chip on the same 96-row batch, per step, absolute on a loss
# of ~10.  The two runs see identical data and initial weights; they differ
# in how bf16 gradients are summed (four per-chip means reduced over ICI vs
# four accumulated micro-batches) and so drift apart by rounding that Adam
# amplifies: at most 5e-3 by the third step in two runs on four v5e chips,
# while the loss itself moved by 0.55 and 4.2.  5e-2 is ten times the drift
# and a tenth of the smallest step.  A run whose gradients never crossed
# chips trains each quarter of the optimizer state on one quarter of the
# data and is off by a sizeable part of that step.
DP_LOSS_TOL = 5e-2
#: Adam for leg 3 (ZeRO-1 refuses LAMB); large enough that each step moves
#: the loss by many times DP_LOSS_TOL
DP_ADAM_LR = 1e-3


def log(msg=""):
    print(msg, flush=True)


def libtpu_version():
    try:
        return importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def mlm_batch(vocab, rows, seq, n_pred):
    """The masked-positions pretraining batch of bench.py:run_config."""
    rng = np.random.default_rng(SEED)
    ids = rng.integers(0, vocab, size=(rows, seq)).astype(np.int32)
    positions = np.stack([rng.choice(seq, size=n_pred, replace=False)
                          for _ in range(rows)]).astype(np.int32)
    return (ids, np.ones((rows, seq), np.int32),
            np.zeros((rows, seq), np.int32), positions,
            np.take_along_axis(ids, positions, axis=1),
            np.ones((rows, n_pred), np.float32))


LAMB = {"type": "Lamb",
        "params": {"lr": 4e-3, "max_coeff": 0.5, "min_coeff": 0.08}}
ADAM = {"type": "Adam", "params": {"lr": DP_ADAM_LR}}


def build_engine(size, seq, micro, gas, devices, optimizer=LAMB,
                 zero_stage=0):
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import BertForPreTraining
    from deepspeed_tpu.parallel.topology import make_mesh
    from deepspeed_tpu.utils import compile_cache

    model = BertForPreTraining.from_size(size, max_seq_len=max(seq, 128))
    mesh = make_mesh(devices=devices)       # devices=None: every chip
    config = {
        "train_batch_size": micro * mesh.shape["data"] * gas,
        "gradient_accumulation_steps": gas,
        "optimizer": optimizer,
        "bf16": {"enabled": True},
        "activation_checkpointing": {"enabled": True,
                                     "policy": "selective"},
        # the fixed in-checkout cache; JAX_COMPILATION_CACHE_DIR, where
        # the machine sets it, outranks this inside the engine
        "compile_cache": {"dir": compile_cache.checkout_dir(ROOT)},
        "steps_per_print": 10 ** 9,
    }
    if zero_stage:
        config["zero_optimization"] = {"stage": zero_stage}
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=config, model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(SEED)),
        mesh=mesh)
    return model, engine


def cache_counts(engine):
    c = engine.resilience_counters()
    return c["compile_cache_hits"], c["compile_cache_misses"]


def check_finite(name, losses):
    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"{name}: non-finite loss in {losses}")


def timed_train_batch(engine, batch, steps):
    """First call (trace + compile or cache read + one step) and ``steps``
    more, each ended by the loss read.  Returns a report dict."""
    h0, m0 = cache_counts(engine)
    t0 = time.perf_counter()
    losses = [float(engine.train_batch(batch))]
    first_call_s = time.perf_counter() - t0
    h1, m1 = cache_counts(engine)
    step_s = []
    for _ in range(steps - 1):
        t0 = time.perf_counter()
        losses.append(float(engine.train_batch(batch)))
        step_s.append(time.perf_counter() - t0)
    return {"first_call_s": round(first_call_s, 3),
            "step_s": [round(s, 4) for s in step_s],
            "losses": losses,
            "step_program_cache_hits": h1 - h0,
            "step_program_cache_misses": m1 - m0}


def log_train_batch(label, fused):
    log(f"  {label}: first call (with compile) {fused['first_call_s']} s, "
        f"steps {fused['step_s']} s; cache hits/misses "
        f"{fused['step_program_cache_hits']}/"
        f"{fused['step_program_cache_misses']}")


def split_api_steps(engine, batch, micro, gas, steps):
    """``steps`` optimizer steps through ``engine(batch)`` / ``backward`` /
    ``step``, ``gas`` micro-batches each.  Returns the per-step mean of
    the micro-batch losses (the loss of the whole batch at that step's
    weights) and the per-step seconds."""
    losses, seconds = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        micro_losses = []
        for g in range(gas):
            rows = slice(g * micro, (g + 1) * micro)
            loss = engine(*(x[rows] for x in batch))
            engine.backward(loss)
            engine.step()
            micro_losses.append(float(loss))
        seconds.append(time.perf_counter() - t0)
        losses.append(sum(micro_losses) / gas)
    if engine.global_steps != steps:
        raise RuntimeError(f"split API took {engine.global_steps} optimizer "
                           f"steps, expected {steps}")
    return losses, seconds


def peak_bytes(device):
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def leg1_seq128(sz, device):
    """One chip, seq 128: the split API, then the fused train_batch."""
    gas, micro = sz["gas"], sz["micro128"]
    model, engine = build_engine(sz["size"], 128, micro, gas, [device])
    batch = mlm_batch(model.config.vocab_size, micro * gas, 128, 20)
    log(f"  compile cache: {engine.compile_cache_dir}")
    log(f"  params: {engine.memory_estimate()['n_params']:,}")

    h0, m0 = cache_counts(engine)
    split_losses, split_s = split_api_steps(engine, batch, micro, gas,
                                            sz["steps"])
    h1, m1 = cache_counts(engine)
    check_finite("leg 1 split API", split_losses)
    log(f"  split API: first step (with compile) {split_s[0]:.1f} s, then "
        f"{[round(s, 3) for s in split_s[1:]]} s; cache hits/misses "
        f"{h1 - h0}/{m1 - m0}")
    log(f"  split API mean micro-batch losses: {split_losses}")

    fused = timed_train_batch(engine, batch, sz["steps"])
    check_finite("leg 1 train_batch", fused["losses"])
    log_train_batch("train_batch", fused)
    log(f"  train_batch losses (last micro-batch): {fused['losses']}")
    # the batch repeats, so the model is memorising it
    if not (split_losses[-1] < split_losses[0]
            and fused["losses"][-1] < fused["losses"][0]):
        raise RuntimeError("leg 1: loss did not fall on the repeated batch")
    log(f"  peak_bytes_in_use (process so far): {peak_bytes(device)}")
    return {"split_losses": split_losses, "split_step_s": split_s,
            "split_cache_hits": h1 - h0, "split_cache_misses": m1 - m0,
            "train_batch": fused, "peak_bytes_in_use": peak_bytes(device),
            "compile_cache_dir": engine.compile_cache_dir}


@contextlib.contextmanager
def stream_bwd_mode(mode):
    """The streaming backward ``mode`` ("auto" as planned, "fused" under
    the VMEM limit it needs, "split") for the length of one traced call."""
    from deepspeed_tpu.ops import pallas_attention as pattn

    plan = pattn.stream_bwd_plan
    forced = {"auto": plan,
              "fused": lambda gb, T, d, itemsize, cap: plan(
                  gb, T, d, itemsize, math.inf),
              "split": lambda *shape: ("split", None)}[mode]
    with mock.patch.object(pattn, "stream_bwd_plan", forced):
        yield


def kernel_parity(shape, causal, interpret):
    """Streaming kernel vs ``xla_attention``: forward, and the backward
    fused and split.  Returns the relative errors."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import pallas_attention as pattn

    B, T, _, _ = shape
    rng = np.random.default_rng(SEED)
    q, k, v, w = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                  for _ in range(4))
    mask = np.ones((B, T), np.float32)
    if not causal:
        mask[::2, T - 37:] = 0.0        # padded tails, as BERT batches have
    mask = jnp.asarray(mask)

    def run(attn):
        def fwd_bwd(q, k, v):
            out, pull = jax.vjp(attn, q, k, v)
            return (out,) + pull(w)
        return [np.asarray(x, np.float32) for x in jax.jit(fwd_bwd)(q, k, v)]

    want = run(lambda q, k, v: pattn.xla_attention(q, k, v, mask, causal))
    errs = {}
    for mode in ("fused", "split"):
        with stream_bwd_mode(mode):
            got = run(lambda q, k, v: pattn.stream_attention(
                q, k, v, mask, causal, interpret))
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            if not np.all(np.isfinite(a)):
                raise RuntimeError(f"stream kernel {name} ({mode} backward) "
                                   f"is not finite at {shape}")
            err = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
            tol = PARITY_TOL_FWD if name == "out" else PARITY_TOL_BWD
            errs[f"{mode}.{name}"] = round(err, 5)
            if err > tol:
                raise RuntimeError(
                    f"stream kernel {name} ({mode} backward) differs from "
                    f"xla_attention by {err:.4f} > {tol} at shape {shape} "
                    f"causal={causal}")
    return errs


def backward_agreement(B, T, heads, d, dv, window, interpret):
    """The streaming backward as ``stream_bwd_plan`` sizes it (past
    Mosaic's default: one fused call under the ``vmem_limit_bytes`` it
    asks for) against the two-kernel split, causal, bf16.  Both accumulate
    dQ over kv tiles and dK/dV over query tiles in the same order in fp32:
    equal to the bit on a v5e (libtpu 0.0.34).  Returns the plan and the
    relative differences."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import pallas_attention as pattn

    n, n_k, n_v = heads
    rng = np.random.default_rng(SEED)
    q, k, v, w = (jnp.asarray(rng.normal(size=(B, T, h, width)),
                              jnp.bfloat16)
                  for h, width in ((n, d), (n_k, d), (n_v, dv), (n, dv)))
    mask = jnp.ones((B, T), jnp.float32)

    def grads(q, k, v):
        _, pull = jax.vjp(lambda q, k, v: pattn.stream_attention(
            q, k, v, mask, True, interpret, window), q, k, v)
        return pull(w)

    got = {}
    for mode in ("auto", "split"):
        with stream_bwd_mode(mode):
            got[mode] = [np.asarray(x, np.float32)
                         for x in jax.jit(grads)(q, k, v)]
    kind, limit = pattn.stream_bwd_plan(
        pattn._stream_gb(B * n), T, d, 2, pattn._kernel_vmem_cap())
    errs = {"plan": kind, "vmem_limit_mib": limit and limit >> 20}
    for name, a, b in zip(("dq", "dk", "dv"), got["auto"], got["split"]):
        if not np.all(np.isfinite(a)):
            raise RuntimeError(f"stream kernel {name} ({kind} backward) is "
                               f"not finite at T {T}, heads {heads}")
        err = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
        errs[name] = round(err, 5)
        if err > PARITY_TOL_BWD:
            raise RuntimeError(
                f"stream kernel {name}: the {kind} backward differs from "
                f"the split by {err:.4f} > {PARITY_TOL_BWD} at T {T}, "
                f"heads {heads}, window {window}")
    return errs


def leg2_seq512(sz, device, on_tpu):
    from deepspeed_tpu import analysis
    from deepspeed_tpu.models import layers

    parity = {}
    for shape, causal in sz["parity"]:
        errs = kernel_parity(shape, causal, interpret=not on_tpu)
        parity[f"{shape} causal={causal}"] = errs
        log(f"  stream kernel vs xla_attention {shape} causal={causal}: "
            f"{errs}")
    for case in sz["backward"]:
        errs = backward_agreement(*case, interpret=not on_tpu)
        parity[f"backward {case}"] = errs
        log(f"  stream backward as planned vs split {case}: {errs}")

    plan = layers.attention_plan(512, 16, 64, False)
    log(f"  attention_plan(512, 16, 64, causal=False) = {plan}")
    if on_tpu and plan != "stream":
        raise RuntimeError(f"seq-512 attention plan is {plan!r}, expected "
                           f"'stream'")

    gas, micro = 2, sz["micro512"]
    model, engine = build_engine(sz["size"], 512, micro, gas, [device])
    batch = mlm_batch(model.config.vocab_size, micro * gas, 512, 80)
    fused = timed_train_batch(engine, batch, sz["steps"])
    check_finite("leg 2 train_batch", fused["losses"])
    calls = analysis.lower_train_batch(engine, batch).as_text().count(
        "tpu_custom_call")
    log(f"  Pallas tpu_custom_call sites in the lowered step: {calls}")
    if on_tpu and calls == 0:
        raise RuntimeError("the lowered seq-512 step holds no Pallas "
                           "tpu_custom_call: the kernel is not on the path")
    log_train_batch("train_batch", fused)
    log(f"  train_batch losses (last micro-batch): {fused['losses']}")
    log(f"  peak_bytes_in_use (process so far): {peak_bytes(device)}")
    return {"kernel_parity": parity, "attention_plan": plan,
            "pallas_custom_calls": calls, "train_batch": fused,
            "peak_bytes_in_use": peak_bytes(device)}


def free_engines():
    """Collect the engines no leg holds any more.  The step program that
    ``observability.scopes`` remembers for a trace reader holds its engine:
    let go of it first."""
    from deepspeed_tpu.observability import scopes
    scopes.forget_step()
    gc.collect()


def leg3_data_parallel(sz):
    """ZeRO-1 over every chip from this one process, against a one-chip
    split-API run of the same rows from the same seed."""
    import jax

    micro, gas = sz["micro128"], sz["gas"]
    model, engine = build_engine(sz["size"], 128, micro, gas,
                                 jax.devices()[:1], optimizer=ADAM)
    batch = mlm_batch(model.config.vocab_size, micro * gas, 128, 20)
    reference_losses, _ = split_api_steps(engine, batch, micro, gas,
                                          sz["steps"])
    check_finite("leg 3 one-chip reference", reference_losses)
    del engine
    free_engines()

    model, engine = build_engine(sz["size"], 128, micro, 1, None,
                                 optimizer=ADAM, zero_stage=1)
    mesh_devices = list(engine.mesh.devices.flat)
    dp = engine.mesh.shape["data"]
    if len(set(mesh_devices)) != jax.device_count() or dp != len(
            mesh_devices):
        raise RuntimeError(f"default mesh holds {mesh_devices}, expected "
                           f"{jax.device_count()} distinct devices on the "
                           f"data axis")
    if dp != gas:
        raise RuntimeError(f"dp={dp} but the one-chip run accumulated "
                           f"{gas} micro-batches: the global batches differ")
    batch = mlm_batch(model.config.vocab_size, micro * dp, 128, 20)
    fused = timed_train_batch(engine, batch, sz["steps"])
    check_finite("leg 3 train_batch", fused["losses"])
    log_train_batch(f"dp={dp} ZeRO-1 train_batch", fused)
    log(f"  losses (global mean): {fused['losses']}")
    log(f"  one-chip losses:      {reference_losses}")
    diffs = [abs(a - b) for a, b in zip(fused["losses"], reference_losses)]
    if max(diffs) > DP_LOSS_TOL:
        raise RuntimeError(f"dp={dp} losses differ from the one-chip run "
                           f"by {diffs} > {DP_LOSS_TOL}")

    # fp32 master + Adam moments, by the device that holds each shard
    master = engine.master_flat if engine.zero_flat else engine.master
    held = collections.Counter()
    for leaf in jax.tree_util.tree_leaves(
            (master, engine.opt_state.m, engine.opt_state.v)):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    total = sum(held.values())
    n_params = engine.memory_estimate()["n_params"]
    shares = {str(d): round(held[d] / total, 4) for d in mesh_devices}
    log(f"  fp32 master + Adam state: {total:,} B over {dp} devices "
        f"(12 B x {n_params:,} params = {12 * n_params:,}); shares "
        f"{shares}")
    if not 0.99 < total / (12 * n_params) < 1.02:
        raise RuntimeError("optimizer state is not 12 B/param in total: "
                           "it is replicated or missing")
    if any(abs(s - 1.0 / dp) > 0.02 for s in shares.values()):
        raise RuntimeError(f"optimizer state is not split evenly: {shares}")
    in_use = {str(d): (d.memory_stats() or {}).get("bytes_in_use")
              for d in mesh_devices}
    log(f"  bytes_in_use per device: {in_use}")
    if mesh_devices[0].platform == "tpu" and not all(
            b and b > 0 for b in in_use.values()):
        raise RuntimeError(f"a device holds nothing: {in_use}")
    return {"dp": dp, "train_batch": fused, "loss_abs_diff": diffs,
            "state_bytes_total": total, "state_shares": shares,
            "bytes_in_use": in_use,
            "peak_bytes_in_use": {str(d): peak_bytes(d)
                                  for d in mesh_devices}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="debug the script at a tiny size on four virtual "
                         "CPU devices (never a chip result)")
    ap.add_argument("--report", default=os.path.join(
        ROOT, "chiprun_out", "chip_smoke.json"),
        help="where the per-leg numbers are written as JSON")
    args = ap.parse_args(argv)

    if args.rehearse_cpu:
        # must precede the first jax import
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()

    import jax
    import jaxlib

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    log(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
        f"libtpu {libtpu_version()}  python {sys.version.split()[0]}")
    log(f"platform: {dev.platform}  device_kind: {dev.device_kind}  "
        f"device_count: {jax.device_count()}")
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse_cpu:
        raise SystemExit(
            f"chip_smoke.py needs a TPU; jax found platform="
            f"{dev.platform!r}.  (--rehearse-cpu debugs the script itself "
            f"on the CPU at a tiny size.)")
    sz = FULL if on_tpu else TINY
    if on_tpu:
        from deepspeed_tpu.analysis import profiles
        log(f"backend profile: {profiles.default_profile().name}")

    t_start = time.perf_counter()
    report = {"device": device, "rehearsal": not on_tpu,
              "versions": {"jax": jax.__version__,
                           "jaxlib": jaxlib.__version__,
                           "libtpu": libtpu_version()}}

    log(f"leg 1: BERT-{sz['size']} seq 128, micro-batch {sz['micro128']} x "
        f"gas {sz['gas']}, one device")
    report["leg1"] = leg1_seq128(sz, dev)
    free_engines()      # the leg's engine: free its HBM before the next

    log(f"leg 2: BERT-{sz['size']} seq 512, micro-batch {sz['micro512']} x "
        f"gas 2, one device")
    report["leg2"] = leg2_seq512(sz, dev, on_tpu)
    free_engines()      # the leg's engine: free its HBM before the next

    if jax.device_count() >= 4:
        log(f"leg 3: BERT-{sz['size']} seq 128, Adam, ZeRO-1 over "
            f"{jax.device_count()} devices, one process")
        report["leg3"] = leg3_data_parallel(sz)
    else:
        log(f"leg 3: skipped, device_count {jax.device_count()} < 4")
        report["leg3"] = None

    report["total_s"] = round(time.perf_counter() - t_start, 1)
    log(f"total: {report['total_s']} s")
    os.makedirs(os.path.dirname(os.path.abspath(args.report)),
                exist_ok=True)
    with open(args.report, "w") as f:
        json.dump(report, f, indent=1)
    log(f"report: {args.report}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
