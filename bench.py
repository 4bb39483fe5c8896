"""Headline benchmark: BERT-large pretrain throughput, samples/sec/chip.

Reference number: 200 samples/s on one V100 at seq-len 128
(/root/reference/docs/_tutorials/bert-pretraining.md:308-320); the driver's
BASELINE.json tracks samples/sec/chip, so ``vs_baseline = value / 200``.

Runs the real engine (bf16 + LAMB, the reference's BERT recipe) through the
fused ``train_batch`` path — one XLA program per optimizer step (lax.scan
over gas micro-batches), buffers donated, "selective" remat (save qkv +
pre-GELU ffn; backward replays no matmuls).  The MLM head uses the standard
masked-positions format (max_predictions_per_seq=20), like the reference's
BingBert pipeline.  gas=16 with micro-batch 96 mirrors the large-batch LAMB
recipe (bert-pretraining.md: 16K global batch) and amortises the optimizer
update.  Steps are queued asynchronously and timed against one final device
sync, so no host round-trip sits inside the measured region.

Prints ONE json line: {"metric","value","unit","vs_baseline","mfu",...}.
Env knobs: BENCH_SIZE/BENCH_SEQ/BENCH_BATCH/BENCH_STEPS/BENCH_REMAT/
BENCH_GAS/BENCH_MAXPRED/BENCH_PALLAS, BENCH_PEAK_TFLOPS (MFU denominator,
auto-detected from the device kind when unset), BENCH_SWEEP=1 for a
batch x remat sweep (rows on stderr, best on stdout), BENCH_OUT=<path> to
also write the JSON line to a file (committed sweep artifacts),
BENCH_PP_SWEEP=1 with BENCH_PP_SCHEDULES=gpipe,1f1b for the pipeline
schedule sweep, BENCH_ATTN_SWEEP=1 for the attention-kernel sweep,
BENCH_HEAD=1 for the MLM-head sparse-vs-dense microbench (CPU-safe),
BENCH_SERVE=1 for the serving bench (continuous vs static batching,
tokens/s/chip + p50/p99 TTFT/ITL -> bench_serve.json),
BENCH_RESUME=1 for the time-to-first-step-after-relaunch bench (serial vs
parallel streaming restore + cold vs warm persistent compile cache;
CPU-safe; see bench_resume.json).  Every row names the ``platform``,
``device_kind`` and ``device_count`` it ran on; the headline path needs a
TPU and exits non-zero without one.

Calibration note (v5e, measured): the published 197 bf16 TFLOP/s peak is
reachable only at large contraction dims (K >= 4096).  BERT-large's body
matmuls contract over hidden=1024, where a chained same-shape matmul
microbenchmark tops out at ~93 TFLOP/s ([12288,1024]x[1024,4096]); the full
train step achieves ~99 TFLOP/s — i.e. ~0.50 MFU against nameplate is
~1.0 of the shape-adjusted ceiling, and the remaining headroom at this
model shape is measurement noise, not schedule waste.
"""

import json
import os
import sys
import time

import numpy as np


def _flatten_leaves(obj, prefix=""):
    """``(numeric, other)`` dotted-key maps over every leaf of a bench
    row (lists included, by index): numbers are threshold-compared,
    everything else — booleans (the acceptance gates like
    ``observability_overhead_ok``), strings, nulls — is
    identity-compared, so a flipped gate always warns."""
    nums, other = {}, {}
    if isinstance(obj, dict):
        items = obj.items()
    elif isinstance(obj, list):
        items = enumerate(obj)
    else:
        key = prefix[:-1]
        if isinstance(obj, (int, float)) and not isinstance(obj, bool):
            nums[key] = float(obj)
        else:
            other[key] = obj
        return nums, other
    for k, v in items:
        n, o = _flatten_leaves(v, f"{prefix}{k}.")
        nums.update(n)
        other.update(o)
    return nums, other


def _load_bench_rows(path):
    """Bench artifacts are one JSON object per line (most files hold
    exactly one); rows key by their ``metric`` tag."""
    rows = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            row = json.loads(line)
            rows[row.get("metric", f"row{len(rows)}")] = row
    if not rows:
        raise SystemExit(f"bench --diff: {path!r} contains no rows")
    return rows


def run_bench_diff(old_path, new_path, threshold=0.10, strict=False):
    """``bench.py --diff old.json new.json`` — compare two committed
    platform-tagged bench artifacts column by column and WARN on any
    numeric column moving more than ``threshold`` (relative).  The
    regression guard for PRs that touch a measured path: commit the
    refreshed artifact, diff it against HEAD's, read the warnings.
    Exit 0 unless ``--strict`` and something moved."""
    old_rows, new_rows = _load_bench_rows(old_path), _load_bench_rows(new_path)
    warnings = 0
    for metric in sorted(set(old_rows) & set(new_rows)):
        old, new = old_rows[metric], new_rows[metric]
        if old.get("platform") != new.get("platform") \
                or old.get("device_kind") != new.get("device_kind"):
            print(f"WARNING [{metric}] platform: "
                  f"{old.get('platform')!r}/{old.get('device_kind')!r} "
                  f"-> {new.get('platform')!r}/"
                  f"{new.get('device_kind')!r} — cross-rig numbers do "
                  f"not compare")
            warnings += 1
        o, other_o = _flatten_leaves(old)
        n, other_n = _flatten_leaves(new)
        # non-numeric columns (acceptance-gate booleans, notes, nulls):
        # any change warns — a flipped observability_overhead_ok or
        # continuous_beats_static must never slide through the diff
        for key in sorted(set(other_o) & set(other_n)):
            if other_o[key] != other_n[key] \
                    and key not in ("platform", "device_kind"):
                print(f"WARNING [{metric}] {key}: {other_o[key]!r} -> "
                      f"{other_n[key]!r}")
                warnings += 1
        for key in sorted(set(o) & set(n)):
            if o[key] == n[key]:
                continue
            if o[key] == 0:
                rel = float("inf")
            else:
                rel = n[key] / o[key] - 1.0
            marker = "WARNING" if abs(rel) > threshold else "ok"
            line = (f"{marker} [{metric}] {key}: {o[key]:g} -> "
                    f"{n[key]:g} ({rel:+.1%})")
            if marker == "WARNING":
                warnings += 1
                print(line)
            elif os.environ.get("BENCH_DIFF_VERBOSE") == "1":
                print(line)
        gone = sorted((set(o) | set(other_o)) - set(n) - set(other_n))
        added = sorted((set(n) | set(other_n)) - set(o) - set(other_o))
        if gone:
            print(f"note [{metric}] columns dropped: {gone}")
        if added:
            print(f"note [{metric}] columns added: {added}")
    only_old = sorted(set(old_rows) - set(new_rows))
    only_new = sorted(set(new_rows) - set(old_rows))
    if only_old:
        print(f"note: rows only in {old_path}: {only_old}")
    if only_new:
        print(f"note: rows only in {new_path}: {only_new}")
    print(f"bench --diff: {warnings} column(s) moved past "
          f"{threshold:.0%} ({old_path} -> {new_path})")
    return 1 if (strict and warnings) else 0


def _emit(obj):
    """Print the one-line JSON, stamped with the device it ran on; also
    write it to $BENCH_OUT when set (the committed-artifact path, e.g.
    bench_attn_sweep.json)."""
    import jax
    dev = jax.devices()[0]
    obj = {**obj, "platform": dev.platform, "device_kind": dev.device_kind,
           "device_count": jax.device_count()}
    line = json.dumps(obj)
    print(line)
    out = os.environ.get("BENCH_OUT")
    if out:
        with open(out, "w") as f:
            f.write(line + "\n")


def _count_params(tree):
    import jax
    return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree))


def _train_flops_per_sample(n_params, cfg, seq, n_pred, remat):
    """Approximate matmul FLOPs per sample for one fwd+bwd pass.

    Standard accounting: 6*N_body per token for parameter matmuls (2N fwd +
    4N bwd) + 12*L*S*H per token for attention score/value matmuls.  The
    tied vocab projection (V*H) runs only over the n_pred gathered MLM
    positions.  Full remat replays the forward (+2N_body + 4*L*S*H per
    token); "selective" replays only the attention einsums (+4*L*S*H).
    """
    V, H, Lyr = cfg.vocab_size, cfg.hidden_size, cfg.num_layers
    n_body = n_params - V * H
    attn_tok = 12.0 * Lyr * seq * H
    per_sample = seq * (6.0 * n_body + attn_tok) + n_pred * 6.0 * V * H
    if remat is True or remat == "full":
        per_sample += seq * (2.0 * n_body + 4.0 * Lyr * seq * H) \
            + n_pred * 2.0 * V * H
    elif remat == "selective":
        per_sample += seq * 4.0 * Lyr * seq * H
    return per_sample


def _env_pallas():
    v = os.environ.get("BENCH_PALLAS", "")
    return None if v == "" else v == "1"


def _peak_tflops():
    """MFU denominator: the attached chip's published bf16 peak from the
    one device table (analysis/profiles.py); a chip with no row raises."""
    import jax

    from deepspeed_tpu.analysis import profiles
    env = os.environ.get("BENCH_PEAK_TFLOPS")
    if env:
        return float(env)
    return profiles.for_device_kind(
        jax.devices()[0].device_kind).peak_bf16_tflops


def _plan_predictions(engine, batch, micro_n):
    """Static capacity-planner columns for a bench row: predicted
    per-device peak HBM of the fused train_batch program and the
    predicted ZeRO-boundary wire time (docs/analysis.md "Capacity
    planner") — prediction sits next to measurement in the committed
    artifact so the next chip session can fit a goodput factor.
    $BENCH_PROFILE picks the profile (default: the attached chip's);
    best-effort: the planner must never take down a bench run."""
    try:
        from deepspeed_tpu.analysis import profiles
        env = os.environ.get("BENCH_PROFILE")
        prof = profiles.resolve(env) if env else profiles.default_profile()
        fused = engine.plan_capacity(batch, train=True, fused=True,
                                     profile=prof)
        micro = tuple(a[:micro_n] for a in batch)
        split = engine.plan_capacity(micro, train=True, fused=False,
                                     profile=prof)
        boundary_ms = (split.boundary_comm.predicted_time_ms()
                       if split.boundary_comm is not None else None)
        return {
            "predicted_peak_hbm_gb": round(fused.peak_bytes / 2**30, 4),
            "predicted_boundary_ms": (round(boundary_ms, 4)
                                      if boundary_ms is not None else None),
            "predicted_profile": prof.name,
        }
    except Exception as e:  # pragma: no cover - defensive
        print(f"capacity-plan columns skipped: {e}", file=sys.stderr)
        return {}


def _measure_boundary(engine, batch, micro_n, repeats=None):
    """MEASURED boundary time: the split-API step program (the same
    collectives+update the planner's ``predicted_boundary_ms`` prices)
    executed fenced ``repeats`` times on real gradients.  The fenced
    timing is deliberate — this is a microbench of one program, not the
    pipelined training path.  Best-effort (None on failure): a
    measurement column must never take down a bench run."""
    import time as _time

    import jax

    try:
        micro = tuple(a[:micro_n] for a in batch)
        fwdbwd = engine._ensure_fwdbwd(micro)
        _, grads = fwdbwd(engine.params,
                          engine.loss_scale_state.cur_scale, micro)
        if engine._step_fn is None:
            engine._step_fn = engine._build_step()
        repeats = repeats or int(os.environ.get("BENCH_OBS_REPEATS", "5"))
        # the step program DONATES master/opt-state/grads/loss-scale; an
        # outer non-donating jit keeps the engine's live buffers intact
        # (donation only binds at the top-level executable).  Call tuple
        # via the protocol owner — hand-rolled copies drift silently.
        from deepspeed_tpu import analysis
        step_fn = jax.jit(lambda *a: engine._step_fn(*a))

        def once():
            outs = step_fn(*analysis.step_args(engine, grads))
            jax.block_until_ready(outs)
            return outs

        once()                                  # compile + warmup
        t0 = _time.perf_counter()
        for _ in range(repeats):
            once()
        return (_time.perf_counter() - t0) / repeats * 1000.0
    except Exception as e:  # pragma: no cover - defensive
        print(f"measured_boundary_ms skipped: {e}", file=sys.stderr)
        return None


def run_config(size, seq, batch_per_chip, steps, remat, gas=1,
               warmup=2, obs_window=0, jsonl_path=None,
               measure_boundary=None, obs_fleet=False):
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import BertForPreTraining
    from deepspeed_tpu.parallel.topology import make_mesh

    n_chips = jax.device_count()
    over = {}
    if os.environ.get("BENCH_LAYER_OVERRIDE"):
        # ablation hook (run_mfu_breakdown): same geometry, fewer layers
        over["num_layers"] = int(os.environ["BENCH_LAYER_OVERRIDE"])
    model = BertForPreTraining.from_size(size, max_seq_len=max(seq, 128),
                                         **over)
    vocab = model.config.vocab_size

    cfg = {
        "train_batch_size": batch_per_chip * n_chips * gas,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "Lamb",
                      "params": {"lr": 4e-3, "max_coeff": 0.5,
                                 "min_coeff": 0.08,
                                 "use_pallas": _env_pallas()}},
        "bf16": {"enabled": True},
        "activation_checkpointing": (
            {"enabled": True, "policy": remat} if isinstance(remat, str)
            else bool(remat)),
        "steps_per_print": 10 ** 9,
    }
    if obs_window:
        # BENCH_OBS leg: metrics spool through the device ring buffer and
        # drain per window (docs/observability.md) — the run must be no
        # slower than the PR 1 window-timer baseline
        obs = {"report_window": int(obs_window)}
        if jsonl_path:
            obs["jsonl_path"] = jsonl_path
        if obs_fleet:
            # fleet aggregation rides the same leg (fleet-of-1 here; the
            # aggregation/detector path is identical to multi-host) —
            # the fences_per_run == 1 contract must hold with it ON
            obs["fleet"] = True
        cfg["observability"] = obs
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=cfg,
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        mesh=make_mesh(model_parallel_size=1))

    n_params = _count_params(engine.params)

    # masked-positions MLM batch: the standard BERT pretraining format
    # (max_predictions_per_seq=20 at seq 128, the reference recipe's shape —
    # bert-pretraining.md data pipeline)
    n_pred = int(os.environ.get("BENCH_MAXPRED",
                                "80" if seq >= 512 else "20"))
    rng = np.random.default_rng(0)
    B = batch_per_chip * n_chips * gas
    ids = rng.integers(0, vocab, size=(B, seq)).astype(np.int32)
    mask = np.ones((B, seq), np.int32)
    tt = np.zeros((B, seq), np.int32)
    positions = np.stack([rng.choice(seq, size=n_pred, replace=False)
                          for _ in range(B)]).astype(np.int32)
    mlm_ids = np.take_along_axis(ids, positions, axis=1)
    weights = np.ones((B, n_pred), np.float32)
    batch = (ids, mask, tt, positions, mlm_ids, weights)

    # compile + warmup (forced to completion by the loss read)
    for _ in range(warmup):
        loss = engine.train_batch(batch)
    first_loss = float(loss)

    measured_boundary = None
    if measure_boundary is None:
        # BENCH_OBS_COLUMNS=1 adds the columns to any leg (e.g. the
        # headline recipe) without re-dispatching main; callers that know
        # (run_obs_bench) pass the flag explicitly
        measure_boundary = os.environ.get("BENCH_OBS_COLUMNS", "0") == "1"
    if measure_boundary:
        # measured boundary next to PR 6's prediction — BEFORE the timed
        # loop (the fenced microbench drains the device, so the timing
        # region below starts clean) and BEFORE any window drains still
        # to come, so with the spool on every subsequent JSONL event
        # carries measured_boundary_ms + boundary_drift
        measured_boundary = _measure_boundary(engine, batch,
                                              batch_per_chip * n_chips)
        if measured_boundary is not None and engine.telemetry is not None:
            engine.telemetry.measured_boundary_ms = measured_boundary

    # timed: queue all steps, sync once at the end (the final loss read
    # forces the whole dispatch chain; per-step host reads would serialize)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch)
    last_loss = float(loss)
    dt = time.perf_counter() - t0

    if not (np.isfinite(first_loss) and np.isfinite(last_loss)):
        raise RuntimeError(
            f"bench loss not finite: first={first_loss} last={last_loss}")

    if obs_window:
        engine.flush_telemetry()    # the final partial window is evidence

    samples_per_sec = B * steps / dt
    per_chip = samples_per_sec / n_chips
    flops = _train_flops_per_sample(n_params, model.config, seq, n_pred,
                                    remat)
    peak = _peak_tflops() * 1e12
    mfu = per_chip * flops / peak
    res = {
        "per_chip": per_chip,
        "mfu": mfu,
        "achieved_tflops": per_chip * flops / 1e12,
        "loss": last_loss,
        "n_params": n_params,
        "measured_boundary_ms": (round(measured_boundary, 4)
                                 if measured_boundary is not None else None),
        "predicted_drift": None,
        **_plan_predictions(engine, batch, batch_per_chip * n_chips),
    }
    pred = res.get("predicted_boundary_ms")
    if measured_boundary is not None and pred:
        # the drift ratio that makes planner rot visible
        res["predicted_drift"] = round(measured_boundary / pred, 4)
    return res


def _pp_body_tok_flops(hidden, seq):
    """Fwd matmul FLOPs per token for one transformer layer body."""
    return 2.0 * 12 * hidden * hidden + 4.0 * seq * hidden


def _pp_head_tok_flops(hidden, vocab):
    """Fwd matmul FLOPs per token for the vocab head."""
    return 2.0 * vocab * hidden


def _pp_analytic_row(pp, schedule, m, layers, hidden, seq, vocab):
    """Exact per-device cost model of one optimizer step of the committed
    schedules (VERDICT r4 weak #1: the virtual-CPU wall-clock sweep was
    noise; these counts are derived from the programs in
    parallel/pipeline.py and are deterministic and hardware-independent).

    Units: one "body unit" = one stage body application (layers/pp layers)
    on one micro-batch; one "head unit" = one head forward on one
    micro-batch (LN -> vocab logits -> CE sum; its VJP pull costs ~2
    more).  SPMD means EVERY stage executes every tick's full program —
    bubble ticks burn the same FLOPs as live ones.

    GPipe (pipeline_apply + scan autodiff): m+pp-1 forward ticks (1 body)
    + m+pp-1 backward ticks (2 body; residuals saved, no recompute); the
    head runs OUTSIDE the schedule on the psum-collected [m] outputs
    through pipe_sharded_loss (each stage takes a 1/pp batch slice) =
    3·m/pp head units per device.  Activation residency: m+pp-1 saved
    stage inputs (scan residuals).

    1F1B (_run_1f1b): m+2(pp-1) ticks, each = 1 body forward + a
    recompute-from-ring VJP (1 forward replay + 2 pull) = 4 body units,
    PLUS the in-schedule head.  Since r5 the head is 1/pp-SHARDED over
    the micro-batch (broadcast yb from the last stage, per-stage slice
    VJP, psum-reassembled dy — mirroring pipe_sharded_loss), so it
    costs 3/pp head units + 2 activation psums per tick instead of the
    3 fully-replicated units the r4 sweep measured.  Activation
    residency: the min(m, 2pp-1) input ring — the memory win the
    schedule exists for.
    """
    body_tok = _pp_body_tok_flops(hidden, seq)
    head_tok = _pp_head_tok_flops(hidden, vocab)
    psums = 0       # full-activation psums (gpipe's output collect is
    # counted once; 1f1b's per-tick head broadcast/gather dominate)
    if pp == 1:
        ticks, body_units, head_units = m, 3.0 * m, 3.0 * m
        ppermutes, ring = 0, m
    elif schedule == "gpipe":
        ticks = m + pp - 1
        body_units = 3.0 * ticks            # 1 fwd + 2 bwd per tick
        head_units = 3.0 * m / pp           # sharded (pipe_sharded_loss)
        ppermutes = 2 * ticks
        psums = 1                           # the [m, mb, ...] collect
        ring = ticks                        # scan residuals
    else:                                   # 1f1b
        ticks = m + 2 * (pp - 1)
        body_units = 4.0 * ticks            # fwd + recompute + 2 pull
        head_units = 3.0 * ticks / pp       # sharded in-schedule head
        ppermutes = 2 * ticks
        psums = 2 * ticks                   # yb broadcast + dy gather,
        ring = min(m, 2 * pp - 1)           # full-activation each
    # per-device fwd-FLOPs per step per (micro-batch token): bubbles and
    # masked head work included — this is what the device EXECUTES
    flops = (body_units * (layers / pp) * body_tok
             + head_units * head_tok)
    return {"pp": pp, "schedule": schedule, "ticks": ticks,
            "body_units": body_units, "head_units": head_units,
            "ppermutes_per_step": ppermutes,
            "activation_psums_per_step": psums,
            "activation_ring_slots": ring,
            "device_flops_per_micro_token": round(flops, 0),
            "theory_bubble_eff": round(m / (m + pp - 1), 3)}


def run_pipeline_sweep(steps=4, warmup=2):
    """pp ∈ {1, 2, 4, ...} GPT-2 schedule sweep at constant global batch.

    Primary output is ANALYTIC (deterministic tick/FLOP/collective counts
    from the committed schedule programs — see _pp_analytic_row), with
    ``analytic_eff_vs_pp1`` = executed-flops(pp=1)/executed-flops(pp) per
    device.  Optional measured wall-clock (BENCH_PP_MEASURE=1) reports
    median ± IQR over BENCH_PP_REPEATS repeats and is flagged
    ``hardware_true`` only on a real TPU mesh — on the virtual CPU mesh
    all 8 devices share one host core, so wall-time there is contention
    noise, not schedule cost (the r4 sweep's negative bubble fractions;
    VERDICT r4 weak #1)."""
    import jax

    n = jax.device_count()
    if n < 2:
        raise RuntimeError(
            "pipeline sweep needs >= 2 devices; set JAX_PLATFORMS=cpu "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "for a virtual mesh")
    seq = int(os.environ.get("BENCH_SEQ", "128"))
    m = int(os.environ.get("BENCH_PP_MICRO", "8"))
    # per-chip batch a multiple of m so the pp=1 baseline's per-shard batch
    # still splits into m micro-batches
    bpc = int(os.environ.get("BENCH_BATCH", str(m)))
    layers = int(os.environ.get("BENCH_PP_LAYERS", "8"))
    hidden = int(os.environ.get("BENCH_PP_HIDDEN", "256"))
    vocab = 50257
    if bpc % m:
        raise RuntimeError(
            f"BENCH_BATCH ({bpc}) must be a multiple of BENCH_PP_MICRO "
            f"({m}) so the pp=1 baseline runs (eff_vs_pp1 is relative to "
            f"it)")
    B = bpc * n  # constant global batch across pp configs

    schedules = [s.strip() for s in
                 os.environ.get("BENCH_PP_SCHEDULES",
                                "gpipe,1f1b").split(",") if s.strip()]
    bad = [s for s in schedules if s not in ("gpipe", "1f1b")]
    if bad or not schedules:
        raise RuntimeError(
            f"BENCH_PP_SCHEDULES entries must be 'gpipe' or '1f1b', "
            f"got {bad or schedules}")

    measure = os.environ.get("BENCH_PP_MEASURE", "0") == "1"
    repeats = int(os.environ.get("BENCH_PP_REPEATS", "5"))
    configs, pp = [], 1
    while pp <= n:
        if (B * pp // n) % m == 0 and layers % pp == 0:
            for schedule in (("gpipe",) if pp == 1 else schedules):
                configs.append((pp, schedule))
        pp *= 2

    rows = [_pp_analytic_row(pp, s, m, layers, hidden, seq, vocab)
            for pp, s in configs]
    # per-chip efficiency at constant global batch: a pp-deep dp-shard
    # processes pp x the per-device batch of pp=1 (mb scales with pp), so
    # wall ∝ device_flops_per_micro_token x pp
    base_flops = rows[0]["device_flops_per_micro_token"]
    for r in rows:
        r["analytic_eff_vs_pp1"] = round(
            base_flops / (r["device_flops_per_micro_token"] * r["pp"]), 3)

    if measure:
        import deepspeed_tpu
        from deepspeed_tpu.models import GPT2Pipelined
        from deepspeed_tpu.parallel.topology import make_mesh

        rng = np.random.default_rng(0)
        toks = rng.integers(0, vocab, size=(B, seq)).astype(np.int32)
        labels = np.roll(toks, -1, axis=1)
        labels[:, -1] = -1
        for row, (pp, schedule) in zip(rows, configs):
            model = GPT2Pipelined.from_size(
                "tiny", num_micro_batches=m, schedule=schedule,
                vocab_size=vocab, max_seq_len=seq,
                num_layers=layers, hidden_size=hidden,
                num_heads=max(4, hidden // 64))
            engine, _, _, _ = deepspeed_tpu.initialize(
                config={"train_batch_size": B, "steps_per_print": 10 ** 9,
                        "optimizer": {"type": "Adam",
                                      "params": {"lr": 1e-4}},
                        "bf16": {"enabled": True}},
                model=model,
                model_parameters=model.init_params(jax.random.PRNGKey(0)),
                mesh=make_mesh(pipeline_parallel_size=pp))
            for _ in range(warmup):
                loss = engine.train_batch((toks, labels))
            float(loss)
            times = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                for _ in range(steps):
                    loss = engine.train_batch((toks, labels))
                float(loss)
                times.append((time.perf_counter() - t0) / steps)
            q1, med, q3 = np.percentile(times, [25, 50, 75])
            row["measured_ms_per_step"] = round(med * 1000, 1)
            row["measured_iqr_ms"] = round((q3 - q1) * 1000, 1)
            row["measured_per_chip"] = round(B / med / n, 2)
            print(f"pp={pp} {schedule}: {med*1000:.0f} ms/step "
                  f"(IQR {1000*(q3-q1):.0f} ms)", file=sys.stderr)

    pp_max = max(pp for pp, _ in configs)
    head_ratio = _pp_head_tok_flops(hidden, vocab) / (
        _pp_body_tok_flops(hidden, seq) * (layers / pp_max))
    gpipe_max = [r for r in rows if r["pp"] == pp_max
                 and r["schedule"] == "gpipe"]
    f1b_max = [r for r in rows if r["pp"] == pp_max
               and r["schedule"] == "1f1b"]
    ratio = (gpipe_max[0]["analytic_eff_vs_pp1"]
             / f1b_max[0]["analytic_eff_vs_pp1"]
             if gpipe_max and f1b_max else float("nan"))
    out = {"metric": "gpt2_pipeline_sweep",
           "unit": "analytic per-device cost model (+ optional timing)",
           "num_micro_batches": m, "layers": layers, "hidden": hidden,
           "hardware_true": bool(measure
                                 and jax.devices()[0].platform == "tpu"),
           "rows": rows,
           "note": ("1F1B trades compute for memory BY DESIGN: 4 body "
                    "units/tick (activation recompute) over m+2(pp-1) "
                    "ticks vs GPipe's 3 over m+pp-1.  Its in-schedule "
                    "head VJP is 1/pp-SHARDED since r5 (broadcast yb, "
                    "per-stage slice, psum dy) — before that it ran "
                    "replicated on every stage every tick, which at this "
                    "toy shape (head %.0fx the per-stage body at pp=%d) "
                    "was the r4 'pp=8 collapse': structural head "
                    "domination, not a scheduler bug.  Post-fix analytic "
                    "gpipe/1f1b ratio at pp=%d: %.1fx (body recompute + "
                    "extra ticks remain — the price of the min(m,2pp-1) "
                    "activation ring vs GPipe's m+pp-1 scan residuals; "
                    "prefer 1F1B when activations, not FLOPs, bound the "
                    "config)."
                    % (head_ratio, pp_max, pp_max, ratio))}
    _emit(out)
    return 0


def run_attention_sweep(steps=10, warmup=3):
    """GPT-2 long-sequence throughput with the streaming Pallas attention
    kernel vs the XLA einsum path (VERDICT r2 #7).  The dispatch env is
    read at trace time, so each mode builds its own engine.  Rows on
    stderr, one JSON summary on stdout."""
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2

    if jax.default_backend() != "tpu":
        raise RuntimeError(
            "BENCH_ATTN_SWEEP needs a TPU backend: the kernel dispatch in "
            "models/layers.py is TPU-gated, so off-TPU both rows would run "
            "the XLA path and the reported speedup would be meaningless")
    T = int(os.environ.get("BENCH_SEQ", "1024"))
    B = int(os.environ.get("BENCH_BATCH", "8"))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 50304, size=(B, T)).astype(np.int32)
    labels = np.roll(toks, -1, axis=1)
    labels[:, -1] = -1

    rows = []
    # "0" = XLA einsum path, "1" = streaming kernel FORCED (the auto
    # dispatch would silently fall back to XLA below STREAM_AUTO_MIN and
    # the "speedup" would compare XLA with itself)
    for mode in ("0", "1"):
        os.environ["DSTPU_FUSED_ATTN"] = mode
        model = GPT2.from_size("tiny", vocab_size=50304, max_seq_len=T,
                               num_layers=12, hidden_size=768, num_heads=12)
        engine, _, _, _ = deepspeed_tpu.initialize(
            config={"train_batch_size": B, "steps_per_print": 10 ** 9,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                    "bf16": {"enabled": True},
                    "activation_checkpointing": {"enabled": True,
                                                 "policy": "selective"}},
            model=model,
            model_parameters=model.init_params(jax.random.PRNGKey(0)))
        for _ in range(warmup):
            loss = engine.train_batch((toks, labels))
        float(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine.train_batch((toks, labels))
        float(loss)
        dt = (time.perf_counter() - t0) / steps
        rows.append({"attn": "xla" if mode == "0" else "stream-pallas",
                     "ms_per_step": round(dt * 1000, 1),
                     "samples_per_sec": round(B / dt, 2)})
        os.environ.pop("DSTPU_FUSED_ATTN", None)
        print(f"attn={rows[-1]['attn']}: {rows[-1]['ms_per_step']} ms/step",
              file=sys.stderr)
    speedup = rows[0]["ms_per_step"] / rows[1]["ms_per_step"]
    _emit({"metric": f"gpt2_seq{T}_attention_kernel_speedup",
           "value": round(speedup, 3), "unit": "x vs XLA path",
           "rows": rows})
    return 0


def run_mfu_breakdown():
    """Account for the headline step's chip time by ENGINE-LEVEL ablation
    (VERDICT r4 weak #2: MFU 0.554 with no committed breakdown).

    Every number here IS a full engine run ended by the final loss read,
    and components come from differencing configs:

      base           L=24 layers, gas=G, maxpred=20   (headline shape)
      half_layers    L=12                             -> per-layer cost
      double_gas     gas=2G                           -> per-micro vs fixed
      maxpred80      maxpred=80                       -> MLM-head cost
      seq256         seq=256, mb halved (same tokens) -> attention growth

    Derived per-optimizer-step seconds:
      body+attn+ln (24 layers) = 2 x (base - half_layers)
      per-step fixed (LAMB update + dispatch) = base - G x per_micro
      mlm head (20 preds) = (maxpred80 - base) / 3
      attention(seq128 portion): seq256 doubles attention score/value
        FLOPs per token but keeps matmul FLOPs constant ->
        attn ~= (seq256 - base) adjusted by the remat replay share
      residual = base - (sum of attributed components) — reported, not
        hidden (VERDICT asks >= 90% accounted).
    One JSON line."""
    import gc

    G = int(os.environ.get("BENCH_GAS", "12"))
    mb = int(os.environ.get("BENCH_BATCH", "24"))
    steps = int(os.environ.get("BENCH_STEPS", "6"))

    def step_s(seq=128, layers=None, gas=None, maxpred=None, batch=None):
        over = {}
        if layers is not None:
            os.environ["BENCH_LAYER_OVERRIDE"] = str(layers)
        if maxpred is not None:
            os.environ["BENCH_MAXPRED"] = str(maxpred)
        try:
            res = run_config("large", seq, batch or mb, steps, "selective",
                             gas=gas or G)
        finally:
            os.environ.pop("BENCH_LAYER_OVERRIDE", None)
            os.environ.pop("BENCH_MAXPRED", None)
        gc.collect()
        B = (batch or mb) * (gas or G)
        return B / res["per_chip"], res

    base_s, base_res = step_s()
    half_layers_s, _ = step_s(layers=12)
    double_gas_s, _ = step_s(gas=2 * G)
    maxpred80_s, _ = step_s(maxpred=80)
    seq256_s, _ = step_s(seq=256, batch=mb // 2)

    per_micro = (double_gas_s - base_s) / G
    fixed = base_s - G * per_micro                 # LAMB + per-step misc
    body_attn_ln = 2.0 * (base_s - half_layers_s)  # all 24 layers, / step
    head20 = (maxpred80_s - base_s) / 3.0
    # seq256 at half mb: same matmul FLOPs/step, attention score/value
    # FLOPs double, remat replays them again in the backward
    attn_total = seq256_s - base_s                 # extra attention = 1x
    embed_and_misc = base_s - body_attn_ln - head20 - fixed

    comps = {
        "body_24_layers_matmul_attn_ln": round(body_attn_ln, 4),
        "attention_portion_of_body": round(attn_total, 4),
        "mlm_head_20_preds": round(head20, 4),
        "per_step_fixed_lamb_dispatch": round(fixed, 4),
        "embedding_residual": round(embed_and_misc, 4),
    }
    attributed = body_attn_ln + head20 + fixed
    accounted_pct = attributed / base_s * 100
    _emit({"metric": "bert_large_seq128_mfu_breakdown",
           "value": round(accounted_pct, 1),
           "unit": "% of measured step attributed by engine ablations "
                   "(residual reported separately)",
           "measured_step_s": round(base_s, 4),
           "gas": G, "batch_per_chip": mb,
           "per_chip": round(base_res["per_chip"], 2),
           "mfu": round(base_res["mfu"], 4),
           # planner prediction next to measurement: diff these against
           # the measured step/boundary next chip session
           "predicted_peak_hbm_gb": base_res.get("predicted_peak_hbm_gb"),
           "predicted_boundary_ms": base_res.get("predicted_boundary_ms"),
           "predicted_profile": base_res.get("predicted_profile"),
           "ablation_step_s": {
               "base": round(base_s, 4),
               "half_layers": round(half_layers_s, 4),
               "double_gas": round(double_gas_s, 4),
               "maxpred80": round(maxpred80_s, 4),
               "seq256_halfbatch": round(seq256_s, 4)},
           "components_s": comps,
           "components_pct": {k: round(v / base_s * 100, 1)
                              for k, v in comps.items()}})
    return 0


def run_data_bench(steps=4, warmup=2):
    """Real-data input-path throughput at the headline config (VERDICT r4
    weak #4): REAL text (the repo's own docs) → wordpiece tokenize →
    masked-LM arrays → FileDataset on disk → memmap + native row-gather →
    producer-thread collation + double-buffered device placement →
    engine.train_batch.  Compared against the synthetic in-memory batch
    the headline uses.  Done-bar: within 3% of synthetic."""
    import gc
    import glob
    import shutil
    import tempfile

    import jax

    import deepspeed_tpu
    from deepspeed_tpu import tokenization as tok
    from deepspeed_tpu.data import DeepSpeedDataLoader, FileDataset
    from deepspeed_tpu.models import BertForPreTraining
    from deepspeed_tpu.parallel.topology import make_mesh

    on_tpu = jax.devices()[0].platform == "tpu"
    mb = int(os.environ.get("BENCH_BATCH", "24" if on_tpu else "4"))
    gas = int(os.environ.get("BENCH_GAS", "48" if on_tpu else "2"))
    seq, n_pred = 128, 20
    size = os.environ.get("BENCH_SIZE", "large" if on_tpu else "tiny")

    # -- synthetic leg (the headline methodology)
    res = run_config(size, seq, mb, steps, "selective", gas=gas,
                     warmup=warmup)
    synth = res["per_chip"]
    gc.collect()

    # -- build the on-disk corpus from real repo text
    texts = []
    for path in sorted(glob.glob(os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "docs", "*.md"))):
        with open(path) as f:
            texts.append(f.read())
    corpus = "\n".join(texts)
    # the docs mention the special tokens literally — dedup against them
    words = sorted(set(w for w in corpus.split() if w)
                   - set(tok.SPECIAL_TOKENS))
    vocab = tok.Vocab(list(tok.SPECIAL_TOKENS) + words)
    tokenizer = tok.BertTokenizer(vocab)
    B = mb * jax.device_count() * gas
    need = (steps + warmup) * B + B
    reps = []
    n_have = 0
    while n_have < need * (seq - 2):        # rough token budget
        reps.append(corpus)
        n_have += len(corpus.split())       # >= 1 token per word
    fields = tok.build_mlm_arrays(reps, tokenizer, seq_len=seq,
                                  max_predictions=n_pred,
                                  n_samples=need)
    d = tempfile.mkdtemp(prefix="dstpu_mlm_")
    FileDataset.save(d, **fields)

    # -- file-backed leg: fresh engine (the synthetic one was freed),
    #    loader streams from disk with producer-side device placement.
    #    The MODEL must match the synthetic leg exactly (standard vocab;
    #    the small test vocab's ids index into it fine)
    model = BertForPreTraining.from_size(size, max_seq_len=seq)
    engine, _, _, _ = deepspeed_tpu.initialize(
        config={"train_batch_size": B,
                "gradient_accumulation_steps": gas,
                "optimizer": {"type": "Lamb",
                              "params": {"lr": 4e-3, "max_coeff": 0.5,
                                         "min_coeff": 0.08}},
                "bf16": {"enabled": True},
                "activation_checkpointing": {"enabled": True,
                                             "policy": "selective"},
                "steps_per_print": 10 ** 9},
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        mesh=make_mesh(model_parallel_size=1))
    loader = DeepSpeedDataLoader(FileDataset(d), batch_size=B,
                                 mesh=engine.mesh, num_workers=1,
                                 prefetch_depth=2, device_prefetch=True)
    it = iter(loader)
    for _ in range(warmup):
        loss = engine.train_batch(next(it))
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(next(it))
    last = float(loss)
    dt = time.perf_counter() - t0
    per_chip = B * steps / dt / jax.device_count()
    shutil.rmtree(d, ignore_errors=True)
    if not np.isfinite(last):
        raise RuntimeError(f"real-data bench loss not finite: {last}")

    _emit({"metric": "bert_%s_seq%d_realdata_vs_synthetic" % (size, seq),
           "value": round(per_chip / synth, 4),
           "unit": "x of synthetic throughput (1.0 = no input bottleneck)",
           "realdata_per_chip": round(per_chip, 2),
           "synthetic_per_chip": round(synth, 2),
           "predicted_peak_hbm_gb": res.get("predicted_peak_hbm_gb"),
           "predicted_boundary_ms": res.get("predicted_boundary_ms"),
           "predicted_profile": res.get("predicted_profile"),
           "n_samples_on_disk": int(fields["input_ids"].shape[0]),
           "vocab": len(vocab)})
    return 0


def run_opt_bench(repeats=30):
    """Optimizer-kernel microbench (VERDICT r4 weak #5 / item 8): the
    Pallas LAMB/Adam kernels vs XLA's fused update, ON CHIP, in the two
    layouts the engine actually runs — the per-leaf BERT-large tree and
    the single ZeRO-style flat fp32 buffer (for Adam the flat buffer is
    one leaf, so the Pallas row IS the batched flat-buffer kernel).  One
    JSON line; the committed artifact decides should_use_pallas."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import BertForPreTraining
    from deepspeed_tpu.ops import optim as optim_mod

    model = BertForPreTraining.from_size("large", max_seq_len=128)
    params = jax.tree_util.tree_map(
        lambda l: l.astype(jnp.float32),
        model.init_params(jax.random.PRNGKey(0)))
    grads = jax.tree_util.tree_map(
        lambda p: jnp.full(p.shape, 1e-4, jnp.float32), params)
    n = _count_params(params)

    def timed(opt, p, g, s):
        """Chained executions ended by one readback."""
        def step(eps, p, g, s):
            p2 = jax.tree_util.tree_map(lambda x: x + eps, p)
            new_p, _ = opt.update(p2, g, s)
            return sum(jnp.sum(l).astype(jnp.float32) * 1e-9
                       for l in jax.tree_util.tree_leaves(new_p))
        upd = jax.jit(step)
        float(upd(jnp.zeros(()), p, g, s))
        acc = jnp.zeros(())
        t0 = time.perf_counter()
        for _ in range(repeats):
            acc = upd(acc * 1e-30, p, g, s)
        float(acc)
        return (time.perf_counter() - t0) / repeats

    import gc

    rows = []
    for layout in ("per_leaf_tree", "flat_buffer"):
        if layout == "per_leaf_tree":
            p, g = params, grads
        else:
            # free the tree layout first — chip HBM holds only one layout
            # (+ its optimizer state) at a time
            params = grads = None
            gc.collect()
            p = zero_flat_like(model.init_params(jax.random.PRNGKey(0)))
            g = jnp.full_like(p, 1e-4)
        for name, mk in (("lamb", lambda up: optim_mod.Lamb(
                              lr=4e-3, use_pallas=up)),
                         ("adam", lambda up: optim_mod.Adam(
                              lr=1e-4, use_pallas=up))):
            if layout == "flat_buffer" and name == "lamb":
                # a flat-buffer LAMB computes ONE global trust ratio —
                # different numerics from the per-leaf reference; the
                # engine never runs it, so don't bench it
                continue
            res = {}
            for mode, up in (("xla", False), ("pallas", True)):
                opt = mk(up)
                state = opt.init(p)
                res[mode] = timed(opt, p, g, state)
                state = None
                gc.collect()
            rows.append({"layout": layout, "opt": name,
                         "xla_ms": round(res["xla"] * 1000, 3),
                         "pallas_ms": round(res["pallas"] * 1000, 3),
                         "pallas_vs_xla": round(
                             res["xla"] / res["pallas"], 3)})
            print(f"{layout} {name}: xla {res['xla']*1e3:.2f} ms, "
                  f"pallas {res['pallas']*1e3:.2f} ms", file=sys.stderr)
        p = g = None
        gc.collect()
    _emit({"metric": "optimizer_kernel_microbench",
           "unit": "ms per update, %d params" % n,
           "n_params": n, "rows": rows})
    return 0


def zero_flat_like(params):
    """One fp32 flat buffer with the tree's total (128-lane padded) size —
    the ZeRO stage-1/2 master layout."""
    import jax.numpy as jnp
    n = _count_params(params)
    padded = ((n + 127) // 128) * 128
    return jnp.zeros((padded,), jnp.float32) + 1e-2


def run_head_bench(repeats=None):
    """MLM-head microbench (the phase-2 seq-512 maxpred-80 suspect,
    bench_mfu_breakdown.json): dense [B,T,H]→vocab head vs the sparse
    masked-position paths, fwd+grad, jitted, chained-execution timing.

    Legs: ``dense`` (full [B, T, vocab] logits + masked CE), ``sparse``
    (dense-labels format with mlm_gather_budget — top_k select + gather),
    ``maskedpos_take`` / ``maskedpos_onehot`` (the standard BingBert
    positions/ids/weights format with the two gather impls —
    DSTPU_MLM_GATHER).  CPU-safe (shapes shrink off-TPU); the committed
    artifact records the platform, so CPU rows are never mistaken for
    chip numbers.  One JSON line."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.models import BertForPreTraining
    from deepspeed_tpu.models import layers as L_mod
    from deepspeed_tpu.parallel.topology import make_mesh

    on_tpu = jax.default_backend() == "tpu"
    T = int(os.environ.get("BENCH_SEQ", "512"))
    n_pred = int(os.environ.get("BENCH_MAXPRED", "80"))
    B = int(os.environ.get("BENCH_BATCH", "24" if on_tpu else "4"))
    H = 1024 if on_tpu else 128
    V = 30528 if on_tpu else 4096
    reps = repeats or int(os.environ.get("BENCH_STEPS",
                                         "20" if on_tpu else "3"))

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(B, T, H)).astype(np.float32),
                    jnp.bfloat16 if on_tpu else jnp.float32)
    dense_labels = np.full((B, T), -1, np.int32)
    positions = np.stack([np.sort(rng.choice(T, size=n_pred, replace=False))
                          for _ in range(B)]).astype(np.int32)
    mlm_ids = rng.integers(0, V, size=(B, n_pred)).astype(np.int32)
    np.put_along_axis(dense_labels, positions, mlm_ids, axis=1)
    weights = np.ones((B, n_pred), np.float32)

    mesh = make_mesh(model_parallel_size=1)
    model = BertForPreTraining.from_size(
        "tiny", vocab_size=V, max_seq_len=T, hidden_size=H,
        num_heads=max(4, H // 64), num_layers=1)
    params = model.init_params(jax.random.PRNGKey(0))
    head_keys = ("mlm_dense_w", "mlm_dense_b", "mlm_ln_s", "mlm_ln_b",
                 "mlm_bias", "wte")
    head_params = {k: params[k] for k in head_keys}

    def head_loss(kind):
        def dense(hp, h):
            logits = model._mlm_head(hp, h)
            tok = L_mod.vocab_parallel_cross_entropy(
                logits, jnp.asarray(dense_labels))
            return L_mod.masked_mean_loss(tok, jnp.asarray(dense_labels) >= 0)

        def sparse(hp, h):
            maskf = (jnp.asarray(dense_labels) >= 0).astype(jnp.float32)
            w, pos = jax.lax.top_k(maskf, n_pred)
            ids = jnp.clip(jnp.take_along_axis(
                jnp.asarray(dense_labels), pos, axis=1), 0, None)
            h_m = L_mod.gather_positions(h, pos)
            tok = L_mod.vocab_parallel_cross_entropy(
                model._mlm_head(hp, h_m), ids)
            return jnp.sum(tok * w) / jnp.maximum(jnp.sum(w), 1.0)

        def maskedpos(hp, h):
            h_m = L_mod.gather_positions(h, jnp.asarray(positions))
            tok = L_mod.vocab_parallel_cross_entropy(
                model._mlm_head(hp, h_m), jnp.asarray(mlm_ids))
            w = jnp.asarray(weights)
            return jnp.sum(tok * w) / jnp.maximum(jnp.sum(w), 1.0)

        body = {"dense": dense, "sparse": sparse,
                "maskedpos": maskedpos}[kind]

        def local(hp, h):
            # grads wrt head params AND the backbone activation (the real
            # training pullback — the scatter-vs-matmul VJP is the point)
            return jax.value_and_grad(
                lambda hp_, h_: jnp.asarray(body(hp_, h_), jnp.float32),
                argnums=(0, 1))(hp, h)

        specs = jax.tree_util.tree_map(lambda _: P(), head_params)
        return jax.jit(jax.shard_map(
            local, mesh=mesh, in_specs=(specs, P()),
            out_specs=(P(), (specs, P())), check_vma=False))

    def timed(fn):
        out = fn(head_params, x)
        jax.tree_util.tree_leaves(out)[0].block_until_ready()
        acc = jnp.zeros((), jnp.float32)
        t0 = time.perf_counter()
        for _ in range(reps):
            loss, _ = fn(head_params, x)
            acc = acc + loss
        float(acc)
        return (time.perf_counter() - t0) / reps

    rows = []
    for leg, gather in (("dense", None), ("sparse", "auto"),
                        ("maskedpos", "take"), ("maskedpos", "onehot")):
        if gather:
            os.environ["DSTPU_MLM_GATHER"] = gather
        try:
            dt = timed(head_loss(leg.split("_")[0]))
        finally:
            os.environ.pop("DSTPU_MLM_GATHER", None)
        name = leg if gather in (None, "auto") else f"{leg}_{gather}"
        rows.append({"leg": name, "ms_per_step": round(dt * 1000, 2)})
        print(f"head {name}: {dt * 1e3:.2f} ms", file=sys.stderr)

    dense_ms = rows[0]["ms_per_step"]
    sparse_ms = rows[1]["ms_per_step"]
    _emit({"metric": "bert_mlm_head_sparse_vs_dense",
           "value": round(dense_ms / max(sparse_ms, 1e-6), 3),
           "unit": "x dense-head cost vs sparse masked-position gather "
                   "(fwd+grad)",
           "platform": jax.default_backend(),
           "seq": T, "n_pred": n_pred, "batch": B, "hidden": H, "vocab": V,
           "rows": rows,
           "note": ("CPU rows establish the algorithmic ratio only; "
                    "re-measure on chip with BENCH_HEAD=1 python bench.py "
                    "(the gather-VJP scatter the onehot path removes is "
                    "TPU-specific, so the chip ratio is LARGER)")})
    return 0


def run_obs_bench():
    """Observability overhead + predicted-vs-measured leg (BENCH_OBS=1).

    Two identical runs of the headline recipe shape: the PR 1
    window-timer baseline (spool OFF — the fence cadence this PR
    replaces) and the spooled run (device ring buffer + one batched drain
    per window + JSONL event log).  The acceptance contract is
    samples/s(spool) >= samples/s(baseline): telemetry must be free on
    the hot path.  Also measures the boundary program directly and
    reports it against the capacity planner's prediction as
    ``predicted_drift`` — the same columns every spooled run now carries
    per window.  One JSON line -> bench_obs.json."""
    import tempfile

    import jax

    from deepspeed_tpu.observability import fences, schema

    on_tpu = jax.devices()[0].platform == "tpu"
    seq = int(os.environ.get("BENCH_SEQ", "128"))
    size = os.environ.get("BENCH_SIZE", "large" if on_tpu else "tiny")
    bpc = int(os.environ.get("BENCH_BATCH", "24" if on_tpu else "8"))
    steps = int(os.environ.get("BENCH_STEPS", "16" if on_tpu else "6"))
    gas = int(os.environ.get("BENCH_GAS", "48" if on_tpu else "1"))
    window = int(os.environ.get("BENCH_OBS_WINDOW", "4" if on_tpu else "3"))
    remat = "selective"

    # each leg runs BENCH_OBS_REPEAT times and keeps its best samples/s
    # (min-time estimator): on a contended CPU a single short run's ratio
    # is noise; the best-of comparison isolates the dispatch-path cost
    # the leg exists to measure
    repeat = int(os.environ.get("BENCH_OBS_REPEAT", "1" if on_tpu else "2"))

    def best(runs):
        return max(runs, key=lambda r: r["per_chip"])

    # baseline leg: spool off AND no boundary microbench — it must time
    # exactly the PR 1 window-timer path
    base = best([run_config(size, seq, bpc, steps, remat, gas=gas,
                            measure_boundary=False)
                 for _ in range(repeat)])

    tmp = tempfile.mkdtemp(prefix="dstpu_obs_")
    f0 = fences.FENCE_COUNT
    spool_runs = []
    for r in range(repeat):
        path = os.path.join(tmp, f"telemetry_{r}.jsonl")
        # fleet mode ON (BENCH_OBS_FLEET=0 opts out): the aggregation /
        # detector / fleet-event path must be free on the hot path too —
        # the fences_per_run contract below gates it
        spool_runs.append((run_config(
            size, seq, bpc, steps, remat, gas=gas,
            obs_window=window, jsonl_path=path, measure_boundary=True,
            obs_fleet=os.environ.get("BENCH_OBS_FLEET", "1") == "1"),
            path))
    # one deliberate fence per run: the final flush (pinned exactly by
    # tests/test_observability.py; bench divides to stay robust to repeat)
    spool_fences = (fences.FENCE_COUNT - f0) // repeat
    spool, jsonl = max(spool_runs, key=lambda t: t[0]["per_chip"])

    problems = schema.validate_jsonl(jsonl)
    by_schema = schema.count_by_schema(jsonl)
    windows = by_schema.get(schema.SCHEMA_ID, 0)
    fleet_events = by_schema.get(schema.FLEET_SCHEMA_ID, 0)
    startup_events = by_schema.get(schema.STARTUP_SCHEMA_ID, 0)

    ratio = spool["per_chip"] / base["per_chip"] if base["per_chip"] else None
    _emit({
        "metric": "observability_overhead",
        "unit": "samples/s/chip (spooled vs window-timer baseline)",
        "platform": jax.devices()[0].platform,
        "hardware_true": on_tpu,
        "size": size, "seq": seq, "batch_per_chip": bpc, "gas": gas,
        "steps": steps, "report_window": window,
        "samples_per_sec_per_chip_baseline": round(base["per_chip"], 2),
        "samples_per_sec_per_chip_spooled": round(spool["per_chip"], 2),
        "spooled_over_baseline": round(ratio, 4) if ratio else None,
        "runs_per_leg": repeat,
        # deliberate engine fences PER spooled run: ONLY the telemetry
        # flush — zero from the per-step path (the bench's own float(loss)
        # reads are caller-side and uncounted; the counter regression is
        # pinned by tests/test_observability.py)
        "spooled_fences_per_run": spool_fences,
        "fleet_mode": os.environ.get("BENCH_OBS_FLEET", "1") == "1",
        "jsonl_windows": windows,
        "jsonl_fleet_events": fleet_events,
        "jsonl_startup_events": startup_events,
        "jsonl_schema_valid": not problems,
        "measured_boundary_ms": spool.get("measured_boundary_ms"),
        "predicted_boundary_ms": spool.get("predicted_boundary_ms"),
        "predicted_drift": spool.get("predicted_drift"),
        "predicted_peak_hbm_gb": spool.get("predicted_peak_hbm_gb"),
        "predicted_profile": spool.get("predicted_profile"),
        "note": ("CPU rows prove overhead-freedom of the spool dispatch "
                 "path and the drift wiring only; wall-clock deltas and "
                 "true boundary/HBM drift need a chip.  Re-measure: "
                 "BENCH_OBS=1 BENCH_OUT=bench_obs.json python bench.py; "
                 "the headline recipe picks up measured_boundary_ms + "
                 "predicted_drift columns with BENCH_OBS_COLUMNS=1"),
    })
    rc = 0
    if problems:
        for line_no, msg in problems:
            print(f"telemetry jsonl invalid at {line_no}: {msg}",
                  file=sys.stderr)
        rc = 1
    if spool_fences != 1:
        # the deterministic half of the acceptance contract: exactly one
        # deliberate fence per spooled run (the flush).  Anything else
        # means a per-step fence crept back into a counted path — a hard
        # failure, unlike the ratio below which is wall-clock noise on a
        # contended virtual-CPU mesh
        print(f"spooled run took {spool_fences} deliberate fences "
              f"(expected exactly 1: the flush)", file=sys.stderr)
        rc = 1
    if ratio is not None and ratio < 1.0:
        print(f"WARNING: spooled/baseline samples/s = {ratio:.4f} < 1 — "
              f"re-measure on an idle machine / a chip before reading "
              f"this as telemetry overhead", file=sys.stderr)
    return rc


def run_ckpt_bench(tmpdir=None):
    """Checkpoint save-stall measurement (VERDICT r4 weak #3): BERT-large
    (the headline model) through engine.save_checkpoint in sync and async
    modes.  Reports the training stall of each — for async that is the
    device→host snapshot only; the container writes overlap the next
    steps — plus restore time and a resume-parity check.  One JSON line."""
    import shutil
    import tempfile

    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import BertForPreTraining

    size = os.environ.get("BENCH_SIZE",
                          "large" if jax.default_backend() == "tpu"
                          else "tiny")
    model = BertForPreTraining.from_size(size, max_seq_len=128)
    engine, _, _, _ = deepspeed_tpu.initialize(
        config={"train_batch_size": 8, "steps_per_print": 10 ** 9,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                "bf16": {"enabled": True}},
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(0)))
    n_params = _count_params(engine.params)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, model.config.vocab_size, size=(8, 128))
    positions = np.stack([rng.choice(128, size=20, replace=False)
                          for _ in range(8)]).astype(np.int32)
    batch = (ids.astype(np.int32), np.ones((8, 128), np.int32),
             np.zeros((8, 128), np.int32), positions,
             np.take_along_axis(ids, positions, axis=1).astype(np.int32),
             np.ones((8, 20), np.float32))
    float(engine.train_batch(batch))      # compile + settle

    d = tmpdir or tempfile.mkdtemp(prefix="dstpu_ckpt_bench_")
    rows = {}
    t0 = time.perf_counter()
    float(engine.train_batch(batch))
    rows["baseline_step_s"] = round(time.perf_counter() - t0, 3)

    # COLD sync save: the step above replaced every device array, so this
    # pays device→host transfer AND the container write
    t0 = time.perf_counter()
    engine.save_checkpoint(d, tag="sync")
    rows["sync_save_stall_s"] = round(time.perf_counter() - t0, 3)
    # WARM sync save (no step in between → jax host-copy caches hit):
    # isolates the container write + disk cost
    t0 = time.perf_counter()
    engine.save_checkpoint(d, tag="sync")
    rows["container_write_s"] = round(time.perf_counter() - t0, 3)
    rows["device_to_host_s"] = round(
        rows["sync_save_stall_s"] - rows["container_write_s"], 3)

    # COLD async save: a fresh step invalidates the caches, so this stall
    # is the honest steady-state one — the device→host snapshot; the
    # container write drains on the background thread under the next step
    float(engine.train_batch(batch))
    t0 = time.perf_counter()
    engine.save_checkpoint(d, tag="async", async_save=True)
    rows["async_save_stall_s"] = round(time.perf_counter() - t0, 3)
    t0 = time.perf_counter()
    loss_after = float(engine.train_batch(batch))
    rows["overlapped_step_s"] = round(time.perf_counter() - t0, 3)
    t0 = time.perf_counter()
    engine.checkpoint_wait()
    rows["async_drain_s"] = round(time.perf_counter() - t0, 3)

    t0 = time.perf_counter()
    e2, _, _, _ = deepspeed_tpu.initialize(
        config={"train_batch_size": 8, "steps_per_print": 10 ** 9,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                "bf16": {"enabled": True}},
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(1)))
    e2.load_checkpoint(d, tag="async")
    rows["restore_s"] = round(time.perf_counter() - t0, 3)
    parity = abs(float(e2.train_batch(batch)) - loss_after)
    if not tmpdir:
        shutil.rmtree(d, ignore_errors=True)

    state_gb = n_params * (2 + 4 + 4 + 4) / 2 ** 30  # bf16 p + fp32 m,mo
    mbps = state_gb * 1024 / max(rows["device_to_host_s"], 1e-3)
    _emit({"metric": "checkpoint_save_stall",
           "value": rows["async_save_stall_s"], "unit": "s (async stall)",
           "n_params": n_params, "state_gb": round(state_gb, 2),
           "device_to_host_mb_per_s": round(mbps, 1),
           "note": ("async stall = device->host snapshot only (the "
                    "container write drains on the writer thread)"),
           "resume_loss_delta": round(parity, 6), **rows})
    return 0


def run_resume_bench(tmpdir=None):
    """End-to-end time-to-first-step after a relaunch (BENCH_RESUME=1):
    the two halves of fast resume, measured separately and summed.

    Restore: one engine saves a checkpoint, then a fresh engine (different
    init seed — nothing to reuse) restores it twice, first through the
    serial fallback (``restore_threads=1``) and then through the parallel
    streaming pipeline (``restore_threads=0`` auto) — same files, bitwise
    the same state, different wall-clock.  Compile: the persistent
    compilation cache sits at one fixed directory in the checkout
    (emptied first; where JAX_COMPILATION_CACHE_DIR is set that directory
    is the cache and "cold" is whatever it holds), so the FIRST
    train_batch pays real XLA compilation (cold, counted as cache misses)
    and the restored engine's first train_batch — after
    ``jax.clear_caches()`` drops the in-memory executables, exactly like a
    relaunched process — deserializes from the cache instead (warm,
    counted as hits).  One JSON line → bench_resume.json."""
    import shutil
    import tempfile

    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import BertForPreTraining
    from deepspeed_tpu.resilience.counters import COUNTERS

    on_tpu = jax.default_backend() == "tpu"
    size = os.environ.get("BENCH_SIZE", "large" if on_tpu else "base")
    from deepspeed_tpu.utils import compile_cache

    root = tmpdir or tempfile.mkdtemp(prefix="dstpu_resume_bench_")
    cache_dir = os.path.join(compile_cache.checkout_dir(
        os.path.dirname(os.path.abspath(__file__))), "resume_bench")
    shutil.rmtree(cache_dir, ignore_errors=True)
    ckpt_dir = os.path.join(root, "ckpt")

    def build(seed):
        model = BertForPreTraining.from_size(size, max_seq_len=128)
        engine, _, _, _ = deepspeed_tpu.initialize(
            config={"train_batch_size": 8, "steps_per_print": 10 ** 9,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                    "bf16": {"enabled": True},
                    "compile_cache": {"dir": cache_dir},
                    "checkpoint": {"restore_threads": 1}},
            model=model,
            model_parameters=model.init_params(jax.random.PRNGKey(seed)))
        return model, engine

    model, engine = build(0)
    n_params = _count_params(engine.params)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, model.config.vocab_size, size=(8, 128))
    positions = np.stack([rng.choice(128, size=20, replace=False)
                          for _ in range(8)]).astype(np.int32)
    batch = (ids.astype(np.int32), np.ones((8, 128), np.int32),
             np.zeros((8, 128), np.int32), positions,
             np.take_along_axis(ids, positions, axis=1).astype(np.int32),
             np.ones((8, 20), np.float32))

    rows = {}
    h0, m0 = COUNTERS.compile_cache_hits, COUNTERS.compile_cache_misses
    t0 = time.perf_counter()
    float(engine.train_batch(batch))
    rows["compile_cold_s"] = round(time.perf_counter() - t0, 3)
    rows["cold_cache_misses"] = COUNTERS.compile_cache_misses - m0
    engine.save_checkpoint(ckpt_dir, tag="resume")

    # fresh engine, serial restore (the pre-PR-5 read path)
    _, e_serial = build(1)
    t0 = time.perf_counter()
    e_serial.load_checkpoint(ckpt_dir, tag="resume")
    rows["restore_serial_s"] = round(time.perf_counter() - t0, 3)

    # fresh engine, parallel streaming restore (reader pool, auto width)
    _, e_par = build(2)
    e_par.config.checkpoint_restore_threads = 0
    t0 = time.perf_counter()
    e_par.load_checkpoint(ckpt_dir, tag="resume")
    rows["restore_parallel_s"] = round(time.perf_counter() - t0, 3)

    # weights-only fast path (the serving cold start): same reader
    # pipeline, but optimizer/ZeRO partitions are never read —
    # docs/resilience.md "Time to resume" carries this row next to the
    # full restores
    from deepspeed_tpu import checkpoint as _ckpt
    t0 = time.perf_counter()
    _tag, _tree = _ckpt.load_params_only(ckpt_dir, tag="resume",
                                         dtype="bfloat16")
    rows["restore_params_only_s"] = round(time.perf_counter() - t0, 3)
    del _tree

    # a relaunched process has no in-memory executables — drop ours so the
    # restored engine's first step goes to the persistent cache
    jax.clear_caches()
    h1 = COUNTERS.compile_cache_hits
    t0 = time.perf_counter()
    loss = float(e_par.train_batch(batch))
    rows["compile_warm_s"] = round(time.perf_counter() - t0, 3)
    rows["warm_cache_hits"] = COUNTERS.compile_cache_hits - h1
    if rows["warm_cache_hits"] <= 0:
        raise RuntimeError(
            "BENCH_RESUME: the restored engine's first step did not hit "
            "the persistent compilation cache (hits stayed at "
            f"{COUNTERS.compile_cache_hits}) — the relaunch would pay a "
            "full recompile")
    if not np.isfinite(loss):
        # fail LOUDLY: a non-finite loss from a bitwise-restored state
        # means the cache-deserialized executable computed garbage, and a
        # garbage artifact must never be committed silently.  Known
        # trigger: some jax 0.4.x XLA-CPU builds lose donation aliasing
        # when deserializing donated-buffer executables.
        raise RuntimeError(
            f"BENCH_RESUME: resumed loss is {loss} on a bitwise-restored "
            "state — the persistent-cache deserialized executable is "
            "computing garbage (known on jax 0.4.x XLA-CPU with donated "
            "buffers).  Rerun with DSTPU_NO_DONATE=1 to measure on this "
            "rig; the artifact records the switch")
    if os.environ.get("DSTPU_NO_DONATE") == "1":
        rows["donation"] = "off (DSTPU_NO_DONATE=1)"
    else:
        # the engine auto-skips donation when the persistent cache is
        # enabled on a quirk-listed backend (the incident this leg's
        # NaN guard caught — docs/resilience.md); record the EFFECTIVE
        # donation so the measurement conditions stay explicit
        from deepspeed_tpu.analysis import profiles as _prof
        _p = _prof.default_profile()
        rows["donation"] = (
            "off (auto: persistent_cache_donation_unsafe)"
            if (_p is not None and _p.persistent_cache_donation_unsafe
                and os.environ.get("DSTPU_FORCE_DONATE") != "1")
            else "on")

    rows["time_to_first_step_cold_s"] = round(
        rows["restore_serial_s"] + rows["compile_cold_s"], 3)
    rows["time_to_first_step_warm_s"] = round(
        rows["restore_parallel_s"] + rows["compile_warm_s"], 3)
    if not tmpdir:
        shutil.rmtree(root, ignore_errors=True)

    _emit({"metric": "resume_time_to_first_step",
           "value": rows["time_to_first_step_warm_s"],
           "unit": "s (parallel restore + warm compile cache)",
           "n_params": n_params, "platform": jax.default_backend(),
           "loss_after_resume": round(loss, 6),
           "note": ("cold = serial restore + full XLA compile (a relaunch "
                    "before PR 5); warm = parallel streaming restore + "
                    "persistent-cache deserialize.  warm_cache_hits > 0 "
                    "is the proof the restarted step skipped recompilation"),
           **rows})
    return 0


def _bench_serve(jsonl_dir=None):
    """Serving throughput/latency under synthetic heavy traffic
    (BENCH_SERVE=1): continuous batching vs the static baseline on the
    SAME deterministic request trace, greedy sampling, identical outputs
    asserted — so the comparison is pure scheduling, not generation
    luck.  Reports tokens/s/chip and p50/p99 time-to-first-token /
    inter-token latency for both schedulers plus an int8-quantized
    continuous leg; one JSON line → bench_serve.json.

    Env knobs: BENCH_SIZE (gpt2 size, default tiny on CPU / small on
    TPU), BENCH_SERVE_SLOTS (8), BENCH_SERVE_REQUESTS (32),
    BENCH_SERVE_TOKENS (per-slot cache capacity, 128),
    BENCH_SERVE_DTYPE (float32 on CPU / bfloat16 on TPU),
    BENCH_SERVE_LEGS (comma subset of
    int8,fused,obs,prefix,spec,router,disagg — default all; the
    continuous/static base always runs: every other leg compares
    against it)."""
    import shutil
    import tempfile

    import jax

    from deepspeed_tpu.inference import (InferenceEngine, StaticScheduler,
                                         latency_summary, run_serve,
                                         synthetic_requests)
    from deepspeed_tpu.models.gpt2 import GPT2

    on_tpu = jax.default_backend() == "tpu"
    size = os.environ.get("BENCH_SIZE", "small" if on_tpu else "tiny")
    vocab = int(os.environ.get("BENCH_VOCAB", "512"))
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", "8"))
    n_req = int(os.environ.get("BENCH_SERVE_REQUESTS", "32"))
    max_tokens = int(os.environ.get("BENCH_SERVE_TOKENS", "128"))
    dtype = os.environ.get("BENCH_SERVE_DTYPE",
                           "bfloat16" if on_tpu else "float32")
    bucket = min(64, max_tokens)
    root = jsonl_dir or tempfile.mkdtemp(prefix="dstpu_serve_bench_")
    legs = {s.strip() for s in os.environ.get(
        "BENCH_SERVE_LEGS", "all").split(",") if s.strip()}

    def leg_on(name):
        return "all" in legs or name in legs

    def build(quantize=None, decode_iters=1, n_slots=None):
        model = GPT2.from_size(size, vocab_size=vocab,
                               max_seq_len=max_tokens)
        cfg = {"train_micro_batch_size_per_gpu": 1,
               "inference": {"max_slots": n_slots or slots,
                             "max_tokens": max_tokens,
                             "prefill_bucket": bucket, "page_tokens": 32,
                             "dtype": dtype, "quantize": quantize,
                             "decode_iters_per_dispatch": decode_iters}}
        return InferenceEngine(model, config=cfg, seed=0)

    # decode-heavy mixed-length trace: generation-length VARIANCE is what
    # static batching pays for (every batch decodes to its longest member)
    trace = synthetic_requests(
        n_req, vocab=vocab, seed=0, prompt_min=2,
        prompt_max=max(8, bucket // 4), new_min=4,
        new_max=int(os.environ.get("BENCH_SERVE_NEW_MAX", "48")))

    engine = build()
    # per-chip accounting uses the ENGINE's mesh (one replica = mp chips;
    # other devices on the host would serve other replicas)
    n_chips = len(engine.mesh.devices.flat)
    n_params = _count_params(engine.params)
    # warm the executables out of the timed region (both schedulers use
    # the same two programs, so neither side pays compile)
    engine.generate([trace[0].prompt], max_new_tokens=2)
    engine.reset()

    cont = run_serve(engine, trace,
                     jsonl_path=os.path.join(root, "serve.jsonl"),
                     window_iters=16)
    cont_sum, cont_results = cont["summary"], cont["results"]

    engine.reset()
    static = StaticScheduler(engine)
    t0 = time.perf_counter()
    static_results = static.run(trace)
    static_sum = latency_summary(static_results,
                                 time.perf_counter() - t0, n_chips)
    static_sum["decode_iters"] = static.decode_iters

    # same trace, same greedy sampler => identical generations, or the
    # comparison is meaningless
    by_rid = {r.rid: r.tokens for r in cont_results}
    for r in static_results:
        if by_rid[r.rid] != r.tokens:
            raise RuntimeError(
                f"BENCH_SERVE: request {r.rid} generated differently "
                f"under continuous vs static scheduling — the batching "
                f"invariance contract is broken")

    int8 = None
    if leg_on("int8"):
        engq = build(quantize="int8")
        engq.generate([trace[0].prompt], max_new_tokens=2)
        engq.reset()
        int8 = run_serve(engq, trace, window_iters=16)["summary"]

    # fused-decode leg: D=4 iterations per dispatch (the serving analog
    # of the multi-step driver) on the SAME trace — the ITL/p99-TTFT
    # row the D-amortization claim rests on, greedy outputs asserted
    # identical to the per-iteration run
    fused_d = int(os.environ.get("BENCH_SERVE_FUSED_D", "4"))
    fused_sum = None
    if leg_on("fused"):
        engf = build(decode_iters=fused_d)
        engf.generate([trace[0].prompt], max_new_tokens=2)
        engf.reset()
        fused = run_serve(engf, trace, window_iters=16)
        fused_sum, fused_results = fused["summary"], fused["results"]
        fused_sum["decode_iters_per_dispatch"] = fused_d
        by_rid_f = {r.rid: r.tokens for r in fused_results}
        for r in cont_results:
            if by_rid_f[r.rid] != r.tokens:
                raise RuntimeError(
                    f"BENCH_SERVE: request {r.rid} generated differently "
                    f"with D={fused_d} fused decode — the greedy-output "
                    f"identity contract is broken")

    # ---- observability-on leg: the SAME continuous trace with the
    # replica observability stack live — per-request lifecycle events +
    # serve v3 windows on the JSONL, the serve watchdog armed around
    # every dispatch, anomaly detectors at each flush (docs/
    # observability.md "Serving view").  Identical greedy outputs
    # asserted; the row records tokens/s as a RATIO of the baseline
    # continuous leg — the documented overhead bound is <= 3%.
    def build_obs():
        model = GPT2.from_size(size, vocab_size=vocab,
                               max_seq_len=max_tokens)
        cfg = {"train_micro_batch_size_per_gpu": 1,
               "inference": {"max_slots": slots, "max_tokens": max_tokens,
                             "prefill_bucket": bucket, "page_tokens": 32,
                             "dtype": dtype,
                             "observability": {
                                 "window_iters": 16,
                                 "request_events": True,
                                 "watchdog_timeout_s": 60.0}}}
        return InferenceEngine(model, config=cfg, seed=0)

    # adjacent-in-time baseline PAIRS on warm engines: the ratio must
    # compare runs seconds apart, not the cold first leg of the bench
    # against a page-cache-warm later one — and on a virtual-CPU rig
    # one pair is contention noise, so it is best-of-N pairs (the PR 7
    # BENCH_OBS_REPEAT precedent; noise only ever LOWERS a ratio)
    obs_sum = obs_base = obs_ratio = obs_ok = None
    if leg_on("obs"):
        engo = build_obs()
        engo.generate([trace[0].prompt], max_new_tokens=2)
        obs_repeat = max(1, int(os.environ.get("BENCH_SERVE_OBS_REPEAT",
                                               "3")))
        for rep in range(obs_repeat):
            engine.reset()
            base_rep = run_serve(engine, trace,
                                 window_iters=16)["summary"]
            engo.reset()
            obs_rep = run_serve(
                engo, trace,
                jsonl_path=os.path.join(root, f"serve_obs_{rep}.jsonl"),
                window_iters=16)
            if rep == 0:
                by_rid_o = {r.rid: r.tokens for r in obs_rep["results"]}
                for r in cont_results:
                    if by_rid_o[r.rid] != r.tokens:
                        raise RuntimeError(
                            f"BENCH_SERVE: request {r.rid} generated "
                            f"differently with replica observability ON "
                            f"— the trajectory-neutrality contract is "
                            f"broken")
                from deepspeed_tpu.observability import \
                    schema as _obs_schema
                _obs_problems = _obs_schema.validate_jsonl(
                    os.path.join(root, "serve_obs_0.jsonl"))
                if _obs_problems:
                    raise RuntimeError(
                        f"BENCH_SERVE: observability-leg JSONL fails "
                        f"validation: {_obs_problems[:3]}")
            if not (base_rep["tokens_per_sec"]
                    and obs_rep["summary"]["tokens_per_sec"]):
                continue
            ratio = round(obs_rep["summary"]["tokens_per_sec"]
                          / base_rep["tokens_per_sec"], 4)
            if obs_ratio is None or ratio > obs_ratio:
                obs_ratio = ratio
                obs_sum, obs_base = obs_rep["summary"], base_rep
        obs_ok = obs_ratio is not None and obs_ratio >= 0.97
        if not obs_ok:
            print(f"BENCH_SERVE: WARNING — observability-on throughput "
                  f"ratio {obs_ratio} < 0.97 (documented bound is <= 3% "
                  f"overhead; virtual-CPU wall clock is contention noise "
                  f"— rerun or use a chip)", file=sys.stderr)

    # ---- shared-prefix multi-tenant leg: N requests share a system
    # prompt; with prefix reuse ON the engine maps the shared pages and
    # prefills only each request's tail — the no-reuse run re-prefills
    # the whole prompt every admission.  Identical greedy outputs
    # asserted; the delta is pure prefill FLOPs/dispatch width.
    from deepspeed_tpu.inference import Request
    from deepspeed_tpu.models.gpt2 import GPT2 as _GPT2
    sys_len = int(os.environ.get("BENCH_SERVE_PREFIX_TOKENS", "64"))
    pfx_bucket = sys_len + 32
    pfx_tokens = max(max_tokens, sys_len + 64)

    def build_prefix(reuse=True):
        model = _GPT2.from_size(size, vocab_size=vocab,
                                max_seq_len=pfx_tokens)
        cfg = {"train_micro_batch_size_per_gpu": 1,
               "inference": {"max_slots": slots, "max_tokens": pfx_tokens,
                             "prefill_bucket": pfx_bucket,
                             "page_tokens": 32, "dtype": dtype,
                             "prefix_reuse": reuse}}
        return InferenceEngine(model, config=cfg, seed=0)

    rng = np.random.default_rng(7)
    sys_prompt = rng.integers(0, vocab, size=sys_len).astype(int).tolist()
    pfx_trace = []
    for i in range(n_req):
        tail = rng.integers(0, vocab, size=int(
            rng.integers(2, 17))).astype(int).tolist()
        pfx_trace.append(Request(
            rid=i, prompt=sys_prompt + tail,
            max_new_tokens=int(rng.integers(8, 25))))

    def clone(tr):
        return [Request(rid=r.rid, prompt=list(r.prompt),
                        max_new_tokens=r.max_new_tokens) for r in tr]

    pfx_sum = pfx_base = reuse_beats = None
    if leg_on("prefix"):
        engp = build_prefix(reuse=True)
        # warm BOTH admission executables out of the timed region: the
        # first generate publishes the prefix (full-bucket program), the
        # second hits it (tail-bucket program)
        engp.generate([pfx_trace[0].prompt], max_new_tokens=2)
        engp.generate([pfx_trace[1].prompt], max_new_tokens=2)
        engp.reset()
        pfx = run_serve(engp, clone(pfx_trace), window_iters=16)
        pfx_sum, pfx_results = pfx["summary"], pfx["results"]
        engb = build_prefix(reuse=False)
        engb.generate([pfx_trace[0].prompt], max_new_tokens=2)
        engb.reset()
        pfx_base = run_serve(engb, clone(pfx_trace), window_iters=16)
        by_rid_p = {r.rid: r.tokens for r in pfx_base["results"]}
        for r in pfx_results:
            if by_rid_p[r.rid] != r.tokens:
                raise RuntimeError(
                    f"BENCH_SERVE: request {r.rid} generated differently "
                    f"with prefix reuse ON — the byte-identity contract "
                    f"is broken")
        pfx_sum["prefix_tokens"] = sys_len
        if not (pfx_sum["prefix_hit_rate"] or 0) > 0:
            raise RuntimeError(
                "BENCH_SERVE: shared-prefix leg recorded no prefix hits "
                "— the reuse path did not engage")
        reuse_beats = (
            (pfx_sum["tokens_per_sec"] or 0)
            >= (pfx_base["summary"]["tokens_per_sec"] or 0)
            and (pfx_sum["ttft_p50_ms"] or 0)
            <= (pfx_base["summary"]["ttft_p50_ms"] or 0))
        if not reuse_beats:
            print("BENCH_SERVE: WARNING — prefix reuse did not beat the "
                  "no-reuse baseline on this rig (wall-clock contention "
                  "noise; rerun or use a chip)", file=sys.stderr)

    # ---- speculative leg: J draft proposals + target verify fused into
    # ONE dispatch per iteration, vs the target-only continuous row on
    # the SAME trace/config.  The draft is the target's LEADING LAYERS
    # (default half) sharing its embedding/head — a distillation
    # stand-in with honestly MEASURED acceptance (spec_accept_rate in
    # the row); BENCH_SERVE_DRAFT_LAYERS overrides the depth.
    import jax as _jax
    spec_j = int(os.environ.get("BENCH_SERVE_SPEC_J", "6"))
    spec_sum = spec_beats = None
    if leg_on("spec"):
        tgt_model = _GPT2.from_size(size, vocab_size=vocab,
                                    max_seq_len=max_tokens)
        tgt_layers = tgt_model.config.num_layers
        draft_layers = int(os.environ.get("BENCH_SERVE_DRAFT_LAYERS",
                                          str(max(1, tgt_layers // 2))))
        tgt_params = tgt_model.init_params(_jax.random.PRNGKey(0))
        draft_model = _GPT2.from_size(size, vocab_size=vocab,
                                      max_seq_len=max_tokens,
                                      num_layers=draft_layers)
        draft_params = dict(
            tgt_params,
            blocks=_jax.tree_util.tree_map(
                lambda l: np.asarray(l)[:draft_layers],
                tgt_params["blocks"]))
        draft_kind = (f"{size}[first {draft_layers}/{tgt_layers} layers, "
                      f"shared embeddings]")
        spec_cfg = {"train_micro_batch_size_per_gpu": 1,
                    "inference": {"max_slots": slots,
                                  "max_tokens": max_tokens,
                                  "prefill_bucket": bucket,
                                  "page_tokens": 32, "dtype": dtype,
                                  "speculative": {
                                      "draft_tokens": spec_j}}}
        engs = InferenceEngine(tgt_model, config=spec_cfg, seed=0,
                               draft_model=draft_model,
                               draft_params=draft_params)
        engs.generate([trace[0].prompt], max_new_tokens=2)
        engs.reset()
        specr = run_serve(engs, trace, window_iters=16)
        spec_sum, spec_results = specr["summary"], specr["results"]
        spec_sum["draft_tokens"] = spec_j
        spec_sum["draft_kind"] = draft_kind
        by_rid_s = {r.rid: r.tokens for r in spec_results}
        for r in cont_results:
            if by_rid_s[r.rid] != r.tokens:
                raise RuntimeError(
                    f"BENCH_SERVE: request {r.rid} generated differently "
                    f"under speculative decoding — the token-identity "
                    f"contract is broken")
        spec_beats = ((spec_sum["tokens_per_sec"] or 0)
                      >= (cont_sum["tokens_per_sec"] or 0))
        if not spec_beats:
            print("BENCH_SERVE: WARNING — the speculative leg did not "
                  "beat target-only decode on this rig (low accept rate "
                  "or contention noise)", file=sys.stderr)

    # ---- router leg: a 2-replica FLEET behind the least-loaded router
    # (deepspeed_tpu/inference/router.py) vs ONE replica on the SAME
    # trace.  Each replica runs on its own driver thread (XLA releases
    # the GIL during compute, so replicas genuinely overlap — the
    # in-process stand-in for replicas on separate chips); scaling =
    # fleet tokens/s over the single replica's, the near-linear-scaling
    # claim (>= 1.8x for 2 replicas).  Greedy outputs asserted identical
    # to the single-replica run — batching invariance is what makes the
    # router's placement decisions output-invisible.  A second fleet run
    # wedges one replica mid-trace (chaos stall → serve watchdog → 503 →
    # router evicts + resubmits) and re-asserts identity THROUGH the
    # eviction.
    router_sum = router_single = router_scaling = router_ok = None
    evict_sum = None
    if leg_on("router"):
        from deepspeed_tpu.inference import run_fleet
        from deepspeed_tpu.observability import schema as _r_schema
        from deepspeed_tpu.resilience import chaos as _chaos_mod
        n_rep = int(os.environ.get("BENCH_SERVE_REPLICAS", "2"))
        # the leg's replica config (BOTH sides: the single baseline IS
        # one fleet replica): D-fused decode + a wider slot count push
        # the per-iteration HOST share down — on a CPU rig every replica
        # thread shares one interpreter, so GIL-serialized scheduler
        # bookkeeping is the in-process stand-in's scaling ceiling
        # (real chips don't share an interpreter; D=1 measures that
        # ceiling honestly at ~1.6x, documented in the note)
        router_d = int(os.environ.get("BENCH_SERVE_ROUTER_D", "8"))
        router_slots = int(os.environ.get("BENCH_SERVE_ROUTER_SLOTS",
                                          str(2 * slots)))

        def build_router():
            return build(decode_iters=router_d, n_slots=router_slots)

        single_eng = build_router()
        single_eng.generate([trace[0].prompt], max_new_tokens=2)
        fleet_engines = [build_router() for _ in range(n_rep)]
        for e in fleet_engines:
            e.generate([trace[0].prompt], max_new_tokens=2)
        # adjacent-in-time single/fleet PAIRS, best-of-N (the obs-leg
        # precedent: virtual-CPU contention noise only ever LOWERS a
        # scaling ratio); identity + JSONL gates ride the first pair
        router_repeat = max(1, int(os.environ.get(
            "BENCH_SERVE_ROUTER_REPEAT", "3")))
        for rep in range(router_repeat):
            single_eng.reset()
            single_rep = run_serve(single_eng, trace,
                                   window_iters=16)["summary"]
            for e in fleet_engines:
                e.reset()
            fleet = run_fleet(
                fleet_engines, trace, poll_s=0.02,
                jsonl_path=(os.path.join(root, "router.jsonl")
                            if rep == 0 else None))
            if rep == 0:
                by_rid_fl = {r.rid: r.tokens for r in fleet["results"]}
                for r in cont_results:
                    if by_rid_fl[r.rid] != r.tokens:
                        raise RuntimeError(
                            f"BENCH_SERVE: request {r.rid} generated "
                            f"differently through the fleet router — "
                            f"placement must be output-invisible "
                            f"(batching invariance)")
                _r_problems = _r_schema.validate_jsonl(
                    os.path.join(root, "router.jsonl"))
                if _r_problems:
                    raise RuntimeError(
                        f"BENCH_SERVE: router-leg JSONL fails "
                        f"validation: {_r_problems[:3]}")
            if not (single_rep["tokens_per_sec"]
                    and fleet["summary"]["tokens_per_sec"]):
                continue
            scaling = round(fleet["summary"]["tokens_per_sec"]
                            / single_rep["tokens_per_sec"], 4)
            if router_scaling is None or scaling > router_scaling:
                router_scaling = scaling
                router_sum = fleet["summary"]
                router_single = single_rep
        if router_sum is not None:
            router_sum["decode_iters_per_dispatch"] = router_d
            router_sum["slots"] = router_slots
        router_ok = (router_scaling is not None
                     and router_scaling >= 1.8)
        if not router_ok:
            print(f"BENCH_SERVE: WARNING — {n_rep}-replica fleet scaled "
                  f"{router_scaling}x (< 1.8x): replica threads are "
                  f"contending for host cores (virtual-CPU rig); rerun "
                  f"on a multi-chip host", file=sys.stderr)

        # eviction sub-leg: same trace, one replica wedged mid-traffic
        def build_wd():
            model = GPT2.from_size(size, vocab_size=vocab,
                                   max_seq_len=max_tokens)
            cfg = {"train_micro_batch_size_per_gpu": 1,
                   "inference": {"max_slots": slots,
                                 "max_tokens": max_tokens,
                                 "prefill_bucket": bucket,
                                 "page_tokens": 32, "dtype": dtype,
                                 "observability": {
                                     "watchdog_timeout_s": 0.75}}}
            return InferenceEngine(model, config=cfg, seed=0)

        evict_engines = [build_wd() for _ in range(2)]
        for e in evict_engines:
            e.generate([trace[0].prompt], max_new_tokens=2)
            e.reset()
        stall_at = max(e.decode_dispatches for e in evict_engines) + 5
        _chaos_mod.configure(stall_step=stall_at, stall_s=30.0)
        try:
            evict = run_fleet(evict_engines, trace, poll_s=0.02)
        finally:
            _chaos_mod.reset()
        by_rid_e = {r.rid: r.tokens for r in evict["results"]}
        for r in cont_results:
            if by_rid_e[r.rid] != r.tokens:
                raise RuntimeError(
                    f"BENCH_SERVE: request {r.rid} generated differently "
                    f"through an eviction + resubmit — the greedy "
                    f"identity contract must survive replica death")
        if evict["summary"]["evictions"] < 1:
            raise RuntimeError(
                "BENCH_SERVE: the eviction sub-leg wedged no replica — "
                "the chaos stall did not reach the watchdog")
        evict_sum = {k: evict["summary"][k] for k in
                     ("requests", "tokens_per_sec", "evictions",
                      "resubmits", "ttft_p99_ms", "queue_wait_p99_ms")}

    # ---- disaggregation leg: prefill and decode pools with KV handoff
    # vs the same TWO replicas as a mixed pool, under concurrent LONG
    # prefills.  The decode cohort's inter-token tail is the number
    # disaggregation protects: in the mixed pool a long prefill dispatch
    # sits inside a serving replica's token loop (every active slot's
    # next token waits behind it); in the disaggregated fleet the decode
    # replica only ever imports finished pages (a small scatter).
    # Identical greedy outputs asserted across single/mixed/disagg —
    # the KV handoff's byte-identity proof rides every run.
    disagg_sum = mixed_sum = None
    disagg_itl = mixed_itl = disagg_ok = None
    if leg_on("disagg"):
        from deepspeed_tpu.inference import run_fleet
        from deepspeed_tpu.inference.scheduler import percentile
        long_bucket = int(os.environ.get("BENCH_SERVE_DISAGG_BUCKET",
                                         "192"))
        dtokens = max(max_tokens, long_bucket + 64)

        def build_disagg():
            model = GPT2.from_size(size, vocab_size=vocab,
                                   max_seq_len=dtokens)
            cfg = {"train_micro_batch_size_per_gpu": 1,
                   "inference": {"max_slots": slots,
                                 "max_tokens": dtokens,
                                 "prefill_bucket": long_bucket,
                                 "page_tokens": 32, "dtype": dtype,
                                 "fleet": {"disaggregate": True}}}
            return InferenceEngine(model, config=cfg, seed=0)

        rngd = np.random.default_rng(11)
        n_decode = int(os.environ.get("BENCH_SERVE_DISAGG_DECODE", "16"))
        n_long = int(os.environ.get("BENCH_SERVE_DISAGG_LONG", "6"))
        decode_rids = set(range(n_decode))
        dtrace = [Request(
            rid=i,
            prompt=rngd.integers(0, vocab, size=int(
                rngd.integers(2, 9))).astype(int).tolist(),
            max_new_tokens=int(rngd.integers(32, 49)))
            for i in range(n_decode)]
        # long prefills interleave INTO the decode traffic (every 3rd
        # position from the middle), almost pure prefill work
        for i in range(n_long):
            dtrace.insert(n_decode // 2 + 2 * i, Request(
                rid=1000 + i,
                prompt=rngd.integers(0, vocab, size=int(
                    long_bucket - 1 - rngd.integers(0, 8))).astype(
                        int).tolist(),
                max_new_tokens=3))

        def itl_cohort_ms(results, which):
            mean = [r.itl_mean_s * 1e3 for r in results
                    if r.rid in which and r.itl_mean_s is not None]
            gap = [max(r.itl_s) * 1e3 for r in results
                   if r.rid in which and r.itl_s]
            return (percentile(mean, 50), percentile(mean, 99),
                    percentile(gap, 99))

        # single-replica identity reference
        engd0 = build_disagg()
        engd0.generate([dtrace[0].prompt], max_new_tokens=2)
        engd0.reset()
        dref = {r.rid: r.tokens
                for r in run_serve(engd0, dtrace)["results"]}
        del engd0

        mixed_engines = [build_disagg(), build_disagg()]
        disagg_decode = build_disagg()
        disagg_prefill = build_disagg()
        # warm every program (incl. export/import) out of the timed
        # region with a tiny fleet pass, then reset the pools
        warm = [Request(rid=9000 + i, prompt=[1, 2, 3],
                        max_new_tokens=3) for i in range(2)]
        run_fleet(mixed_engines, warm)
        run_fleet([disagg_decode], warm,
                  prefill_engines=[disagg_prefill])
        for e in mixed_engines + [disagg_decode, disagg_prefill]:
            e.reset()

        mixed = run_fleet(mixed_engines, dtrace, poll_s=0.02)
        disagg = run_fleet([disagg_decode], dtrace,
                           prefill_engines=[disagg_prefill],
                           jsonl_path=os.path.join(root,
                                                   "disagg.jsonl"),
                           poll_s=0.02)
        for name, res in (("mixed", mixed), ("disaggregated", disagg)):
            got = {r.rid: r.tokens for r in res["results"]}
            if got != dref:
                bad = [k for k in dref if got.get(k) != dref[k]]
                raise RuntimeError(
                    f"BENCH_SERVE: requests {bad[:4]} generated "
                    f"differently under the {name} fleet — the KV "
                    f"handoff byte-identity contract is broken")
        if disagg["summary"]["handoffs"] < n_decode:
            raise RuntimeError(
                "BENCH_SERVE: disaggregation leg recorded "
                f"{disagg['summary']['handoffs']} handoffs — the "
                f"prefill→decode path did not engage")
        mixed_itl = itl_cohort_ms(mixed["results"], decode_rids)
        disagg_itl = itl_cohort_ms(disagg["results"], decode_rids)
        mixed_sum = dict(mixed["summary"],
                         decode_cohort_itl_mean_p50_ms=mixed_itl[0],
                         decode_cohort_itl_mean_p99_ms=mixed_itl[1],
                         decode_cohort_itl_gap_p99_ms=mixed_itl[2])
        disagg_sum = dict(disagg["summary"],
                          decode_cohort_itl_mean_p50_ms=disagg_itl[0],
                          decode_cohort_itl_mean_p99_ms=disagg_itl[1],
                          decode_cohort_itl_gap_p99_ms=disagg_itl[2],
                          long_prefills=n_long,
                          prefill_bucket=long_bucket)
        disagg_ok = (disagg_itl[1] is not None
                     and mixed_itl[1] is not None
                     and disagg_itl[1] <= mixed_itl[1])
        if not disagg_ok:
            print(f"BENCH_SERVE: WARNING — disaggregated decode-pool "
                  f"p99 ITL {disagg_itl[1]} did not beat the mixed "
                  f"pool's {mixed_itl[1]} under long prefills "
                  f"(virtual-CPU contention noise; rerun or use a "
                  f"chip)", file=sys.stderr)

    beats = (cont_sum["tokens_per_sec"] is not None
             and static_sum["tokens_per_sec"] is not None
             and cont_sum["tokens_per_sec"] >= static_sum["tokens_per_sec"]
             and (cont_sum["ttft_p99_ms"] or 0)
             <= (static_sum["ttft_p99_ms"] or 0))
    if not beats:
        print("BENCH_SERVE: WARNING — continuous batching did not beat "
              "static batching on this rig (wall-clock contention noise "
              "on virtual-CPU hosts; rerun or use a chip)",
              file=sys.stderr)

    if not jsonl_dir:
        shutil.rmtree(root, ignore_errors=True)
    row = {"metric": "serve_tokens_per_sec_per_chip",
           "value": cont_sum["tokens_per_sec_per_chip"],
           "unit": "tokens/s/chip (continuous batching, greedy)",
           "platform": jax.default_backend(),
           "device_kind": jax.devices()[0].device_kind,
           "n_chips": n_chips, "n_params": n_params,
           "model": size, "dtype": dtype, "slots": slots,
           "requests": n_req, "max_tokens": max_tokens,
           "prefill_bucket": bucket,
           "continuous": cont_sum, "static": static_sum,
           "continuous_beats_static": bool(beats)}
    if int8 is not None:
        row["int8"] = int8
    if fused_sum is not None:
        row["fused_decode"] = fused_sum
    if obs_ok is not None:
        row.update({"observability": obs_sum,
                    "observability_baseline": obs_base,
                    "observability_ratio": obs_ratio,
                    "observability_overhead_ok": bool(obs_ok)})
    if pfx_sum is not None:
        row.update({"shared_prefix": pfx_sum,
                    "shared_prefix_baseline": pfx_base["summary"],
                    "prefix_hit_rate": pfx_sum["prefix_hit_rate"],
                    "prefill_tokens_saved":
                        pfx_sum["prefill_tokens_saved"],
                    "prefix_reuse_beats_baseline": bool(reuse_beats)})
    if spec_sum is not None:
        row.update({"speculative": spec_sum,
                    "spec_accept_rate": spec_sum["spec_accept_rate"],
                    "draft_params": spec_sum["draft_params"],
                    "speculative_beats_target_only": bool(spec_beats)})
    if router_sum is not None:
        row.update({"router": router_sum,
                    "router_single_baseline": router_single,
                    "router_scaling": router_scaling,
                    "router_scaling_ok": bool(router_ok),
                    "router_eviction": evict_sum})
    if disagg_sum is not None:
        row.update({"disagg": disagg_sum,
                    "disagg_mixed_baseline": mixed_sum,
                    "disagg_decode_itl_p99_ok": bool(disagg_ok)})
    row["note"] = (
        "identical greedy outputs asserted across schedulers "
        "AND across D=1 vs D-fused decode; static decodes "
        "every batch until its last member finishes, "
        "continuous admits into freed slots each iteration — "
        "the delta is pure scheduling.  fused_decode runs "
        "the continuous scheduler with "
        "decode_iters_per_dispatch=D (one dispatch + one "
        "token read per D iterations) — compare its "
        "itl_MEAN_ms and tokens_per_sec against the "
        "continuous row; the itl p50 honestly collapses "
        "toward 0 at D>1 because tokens arrive in bursts "
        "of D (latency_summary docstring).  shared_prefix "
        "runs a multi-tenant trace (every request shares a "
        "system prompt) with prefix reuse ON vs the "
        "no-reuse baseline — identical outputs asserted, "
        "prefill_tokens_saved prompt tokens served from "
        "shared pages.  speculative fuses J drafts + "
        "verify into one dispatch on the continuous "
        "trace — token-identity vs the continuous row "
        "asserted; the default draft is the target's "
        "LEADING LAYERS with shared embeddings (draft_kind "
        "names the depth) — a distillation stand-in whose "
        "spec_accept_rate is honestly measured, not "
        "assumed; BENCH_SERVE_DRAFT_LAYERS picks the "
        "depth (= target depth reproduces the "
        "identical-twin accept≈1 ceiling).  observability "
        "re-runs the continuous trace with the replica "
        "observability stack live (request events, serve "
        "watchdog, detectors) — identical outputs asserted, "
        "observability_ratio = its tokens/s over the "
        "baseline's (documented bound: >= 0.97).  router runs the "
        "SAME trace through a 2-replica fleet behind the "
        "least-loaded router (one driver thread per replica — the "
        "in-process stand-in for per-chip replicas): "
        "router_scaling = fleet tokens/s over the adjacent "
        "single-replica run of the IDENTICAL replica config "
        "(target >= 1.8x for 2 replicas, best-of-N pairs); both "
        "sides serve D-fused with a widened slot count (recorded "
        "in the router row) because on a CPU rig every replica "
        "thread shares one interpreter and at D=1 GIL-serialized "
        "scheduler bookkeeping caps thread overlap near 1.6x — "
        "real chips don't share an interpreter.  Outputs identical "
        "incl. THROUGH the router_eviction "
        "sub-leg (chaos-wedged replica → watchdog → 503 → evict + "
        "resubmit with original timestamps).  disagg splits the "
        "same two replicas into a prefill pool + a decode pool "
        "with chunk-container KV handoff and drives decode "
        "traffic under concurrent LONG prefills — "
        "decode_cohort_itl_mean_p99_ms vs the mixed-pool "
        "baseline's is the protected number "
        "(disagg_decode_itl_p99_ok), byte-identical outputs "
        "asserted against a single replica on every run")
    _emit(row)
    return 0


def run_dispatch_bench():
    """Dispatch-path microbench (BENCH_DISPATCH=1) — the measurement side
    of the dispatch-cost pass (analysis/dispatchplan.py), modeled on
    SNIPPETS [3]'s launch/fence/transfer microbenchmarks: empty-program
    launch overhead (base + per-argument-leaf), per-step fence cost (the
    host's device round trip), and host→device transfer latency +
    bandwidth.  Emits measured columns NEXT TO the active BackendProfile's
    predicted constants so each rig calibrates the profile — the ruler
    ROADMAP item 4's multi-step driver will be judged against.

    Knobs: BENCH_DISPATCH_REPEATS (median-of, default 5),
    BENCH_DISPATCH_CALLS (launches per leg, default 200)."""
    import statistics

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.analysis import profiles as prof_mod

    repeats = int(os.environ.get("BENCH_DISPATCH_REPEATS", "5"))
    calls = int(os.environ.get("BENCH_DISPATCH_CALLS", "200"))
    prof = prof_mod.default_profile()

    def med(fn):
        return statistics.median(fn() for _ in range(repeats))

    # ---- empty-program launch: dispatch-only time of a trivial jitted
    # program (async queuing returns before execution), then the same
    # with a 64-leaf argument tree to split out per-leaf marshalling
    x = jnp.zeros((8,), jnp.float32)
    f1 = jax.jit(lambda v: v + 1.0)
    f1(x).block_until_ready()

    def leg_dispatch():
        t0 = time.perf_counter()
        y = None
        for _ in range(calls):
            y = f1(x)
        t1 = time.perf_counter()
        y.block_until_ready()
        return (t1 - t0) / calls * 1e6

    dispatch_us = med(leg_dispatch)

    NLEAF = 64
    tree = {f"l{i}": jnp.zeros((8,), jnp.float32) for i in range(NLEAF)}
    ftree = jax.jit(lambda t: jax.tree_util.tree_map(lambda v: v + 1.0, t))
    jax.block_until_ready(ftree(tree))

    def leg_tree():
        t0 = time.perf_counter()
        y = None
        for _ in range(calls):
            y = ftree(tree)
        t1 = time.perf_counter()
        jax.block_until_ready(y)
        return (t1 - t0) / calls * 1e6

    tree_us = med(leg_tree)
    leaf_us = max(0.0, (tree_us - dispatch_us) / NLEAF)

    # ---- per-step fence cost: dispatch + block on the result (one
    # device round trip) minus the dispatch-only time
    def leg_fence():
        t0 = time.perf_counter()
        for _ in range(calls):
            f1(x).block_until_ready()
        t1 = time.perf_counter()
        return (t1 - t0) / calls * 1e6

    fence_us = max(0.0, med(leg_fence) - dispatch_us)

    # ---- host→device transfer: tiny buffer = latency, big buffer =
    # bandwidth (the batch-feeding cost class)
    small = np.zeros((256,), np.float32)
    big = np.zeros((16 << 20,), np.float32)        # 64 MiB
    jax.device_put(big).block_until_ready()

    def leg_small():
        t0 = time.perf_counter()
        for _ in range(calls):
            jax.device_put(small).block_until_ready()
        return (time.perf_counter() - t0) / calls * 1e6

    def leg_big():
        n = max(1, calls // 50)
        t0 = time.perf_counter()
        for _ in range(n):
            jax.device_put(big).block_until_ready()
        return (time.perf_counter() - t0) / n

    h2d_latency_us = med(leg_small)
    big_s = med(leg_big)
    h2d_gibps = big.nbytes / big_s / (1 << 30)

    # calibration drift gate: the dispatch-cost pass prices host time
    # with the profile's predicted constants — a >4× measured/predicted
    # ratio means the profile is pricing a DIFFERENT rig (the state the
    # cpu-8 recalibration fixed: 60 µs predicted vs 3.7 µs measured)
    drift = []
    if prof is not None:
        for name, measured, predicted in (
                ("dispatch_us", dispatch_us, prof.dispatch_us),
                ("dispatch_leaf_us", leaf_us, prof.dispatch_leaf_us),
                ("fence_us", fence_us, prof.fence_us),
                ("h2d_gibps", h2d_gibps, prof.h2d_gibps)):
            if measured > 0 and predicted > 0:
                ratio = max(measured / predicted, predicted / measured)
                if ratio > 4.0:
                    drift.append(f"{name}: measured {measured:.3g} vs "
                                 f"predicted {predicted:.3g} ({ratio:.1f}×)")
        if drift:
            print("BENCH_DISPATCH: WARNING — profile "
                  f"'{prof.name}' dispatch constants drift >4× from this "
                  "rig's measurements; recalibrate analysis/profiles.py: "
                  + "; ".join(drift), file=sys.stderr)

    _emit({
        "metric": "dispatch_microbench",
        "unit": "us (median of repeats; predicted = BackendProfile "
                "constants)",
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "hardware_true": jax.default_backend() == "tpu",
        "calls_per_leg": calls, "repeats": repeats,
        "profile": prof.name if prof else None,
        "dispatch_us_measured": round(dispatch_us, 3),
        "dispatch_us_predicted": prof.dispatch_us if prof else None,
        "dispatch_leaf_us_measured": round(leaf_us, 4),
        "dispatch_leaf_us_predicted": (prof.dispatch_leaf_us if prof
                                       else None),
        "fence_us_measured": round(fence_us, 3),
        "fence_us_predicted": prof.fence_us if prof else None,
        "h2d_latency_us_measured": round(h2d_latency_us, 3),
        "h2d_gibps_measured": round(h2d_gibps, 3),
        "h2d_gibps_predicted": prof.h2d_gibps if prof else None,
        "callback_us_predicted": prof.callback_us if prof else None,
        "drift_over_4x": drift,
        "note": ("the dispatch-cost pass prices the static host timeline "
                 "with the predicted columns; measured columns are this "
                 "rig's truth — the leg warns (drift_over_4x) when a "
                 "constant drifts past 4× so the profile gets "
                 "recalibrated, not quietly wrong. Re-measure: "
                 "BENCH_DISPATCH=1 "
                 "BENCH_OUT=bench_dispatch.json python bench.py")})
    return 0


def run_multistep_bench():
    """Multi-step driver leg (BENCH_MULTISTEP=1) — the on-device K-fused
    dispatch vs the per-step ``train_batch`` loop on the SAME model and
    batches: samples/s and per-step wall time at K ∈ {1, 2, 8}, plus a
    per-step fixed-cost column from the 1/K amortization model
    ``t(K) = t_compute + fixed/K`` fitted over the measured K points
    (fit residual reported — a bad fit means the model, not the data,
    is wrong).  One JSON line → bench_multistep.json.

    Env knobs: BENCH_MULTISTEP_KS ("1,2,8"), BENCH_MULTISTEP_STEPS (48,
    must be divisible by every K), BENCH_MULTISTEP_REPEAT (best-of, 3),
    BENCH_HIDDEN (64).  Chip re-measurement: BENCH_MULTISTEP=1
    BENCH_OUT=bench_multistep.json python bench.py (WALLCLOCK §7)."""
    import jax

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    from simple_model import SimpleModel

    import deepspeed_tpu as dstpu

    hidden = int(os.environ.get("BENCH_HIDDEN", "64"))
    # sorted ascending: the speedup ratio and the 1/K fit both assume
    # ks[0] is the smallest and ks[-1] the largest
    ks = sorted({int(x) for x in os.environ.get(
        "BENCH_MULTISTEP_KS", "1,2,8").split(",")})
    steps = int(os.environ.get("BENCH_MULTISTEP_STEPS", "48"))
    repeat = int(os.environ.get("BENCH_MULTISTEP_REPEAT", "3"))
    for k in ks:
        if steps % k:
            raise SystemExit(
                f"BENCH_MULTISTEP_STEPS={steps} must be divisible by "
                f"every K in {ks}")
    batch_n = 16
    cfg = {"train_batch_size": batch_n,
           "gradient_accumulation_steps": 1,
           "steps_per_print": 10 ** 9,
           "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
           "bf16": {"enabled": True}}

    def make_batch(i):
        rng = np.random.default_rng(7000 + i)
        return (rng.normal(size=(batch_n, hidden)).astype(np.float32),
                rng.integers(0, hidden, size=(batch_n,)).astype(np.int32))

    batches = [make_batch(i) for i in range(steps)]
    rows = {}
    for k in ks:
        engine, _, _, _ = dstpu.initialize(
            model=SimpleModel(hidden_dim=hidden), config=dict(cfg))
        run_one = (
            (lambda s: engine.train_batch(batches[s])) if k == 1 else
            (lambda s: engine.train_many(batches[s:s + k])))
        # warm the executable out of the timed region
        run_one(0)
        best = None
        for _ in range(repeat):
            t0 = time.perf_counter()
            s = 0
            out = None
            while s < steps:
                out = run_one(s)
                s += k
            jax.block_until_ready(out)
            elapsed = time.perf_counter() - t0
            if best is None or elapsed < best:
                best = elapsed
        rows[k] = {
            "step_ms": round(best / steps * 1e3, 4),
            "samples_per_sec": round(steps * batch_n / best, 2),
            "dispatches": steps // k,
        }

    # fixed-cost fit: t(K) = t_compute + fixed/K  (least squares over
    # the measured K points; fixed = the per-step host boundary cost the
    # fusion amortizes).  Report the residual so a poorly-fitting rig is
    # visible, and the raw step_ms rows stay the ground truth.  A
    # single-K run cannot determine the 2-parameter model — the fit
    # columns go null instead of emitting a fabricated perfect fit.
    if len(ks) >= 2:
        xs = np.array([1.0 / k for k in ks])
        ys = np.array([rows[k]["step_ms"] for k in ks])
        A = np.stack([np.ones_like(xs), xs], axis=1)
        (t_compute, fixed), res, _, _ = np.linalg.lstsq(A, ys, rcond=None)
        fixed = max(0.0, float(fixed))
        t_compute = float(t_compute)
        residual = (float(np.sqrt(res[0] / len(ks))) if len(res) else 0.0)
        for k in ks:
            rows[k]["fixed_cost_ms_per_step"] = round(fixed / k, 4)
    else:
        fixed = t_compute = residual = None
    speedup = rows[ks[0]]["step_ms"] / rows[ks[-1]]["step_ms"]
    _emit({
        "metric": "multistep_driver",
        "unit": "ms/step (best-of-%d, %d optimizer steps, Adam bf16 "
                "hidden=%d)" % (repeat, steps, hidden),
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "hardware_true": jax.default_backend() == "tpu",
        "ks": ks,
        "rows": {str(k): rows[k] for k in ks},
        "fixed_cost_ms_k1": (round(fixed, 4) if fixed is not None
                             else None),
        "compute_ms_fitted": (round(t_compute, 4)
                              if t_compute is not None else None),
        "fit_residual_ms": (round(residual, 4) if residual is not None
                            else None),
        "stepms_kmin_over_kmax": round(speedup, 3),
        "note": ("t(K) = compute + fixed/K fitted over the measured Ks; "
                 "rows carry the raw per-step wall time — the "
                 "amortization claim rests on step_ms falling with K, "
                 "the fit only prices it.  K-fused is bitwise with "
                 "serial (tests/test_multistep.py).  Re-measure on "
                 "chip: BENCH_MULTISTEP=1 BENCH_OUT=bench_multistep.json "
                 "python bench.py"),
    })
    return 0


def main():
    # artifact diff mode needs no backend at all — handle it before jax
    # is imported so it runs anywhere (CI gates, laptops, artifact
    # review): bench.py --diff old.json new.json [--threshold 0.1]
    # [--strict]
    if "--diff" in sys.argv:
        argv = sys.argv[1:]
        argv.remove("--diff")
        strict = "--strict" in argv
        if strict:
            argv.remove("--strict")
        threshold = 0.10
        usage = ("usage: bench.py --diff old.json new.json "
                 "[--threshold 0.1] [--strict]")
        if "--threshold" in argv:
            i = argv.index("--threshold")
            try:
                threshold = float(argv[i + 1])
            except (IndexError, ValueError):
                raise SystemExit(usage)
            del argv[i:i + 2]
        if len(argv) != 2:
            raise SystemExit(usage)
        return run_bench_diff(argv[0], argv[1], threshold=threshold,
                              strict=strict)

    import jax

    if os.environ.get("BENCH_PP_SWEEP", "0") == "1":
        return run_pipeline_sweep(
            steps=int(os.environ.get("BENCH_STEPS", "4")))
    if os.environ.get("BENCH_CKPT", "0") == "1":
        return run_ckpt_bench()
    if os.environ.get("BENCH_RESUME", "0") == "1":
        return run_resume_bench()
    if os.environ.get("BENCH_SERVE", "0") == "1":
        return _bench_serve()
    if os.environ.get("BENCH_MFU_BREAKDOWN", "0") == "1":
        return run_mfu_breakdown()
    if os.environ.get("BENCH_OPT", "0") == "1":
        return run_opt_bench()
    if os.environ.get("BENCH_HEAD", "0") == "1":
        return run_head_bench()
    if os.environ.get("BENCH_OBS", "0") == "1":
        return run_obs_bench()
    if os.environ.get("BENCH_DISPATCH", "0") == "1":
        return run_dispatch_bench()
    if os.environ.get("BENCH_MULTISTEP", "0") == "1":
        return run_multistep_bench()
    if os.environ.get("BENCH_DATA", "0") == "1":
        return run_data_bench()
    if os.environ.get("BENCH_ATTN_SWEEP", "0") == "1":
        return run_attention_sweep(
            steps=int(os.environ.get("BENCH_STEPS", "10")))

    # the headline is a device metric: no TPU, no number (a CPU run under
    # this metric's name is how a 4.65 samples/s row once sat beside chip
    # rows)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py: the headline benchmark needs a TPU; jax found "
            f"platform={dev.platform!r} device_kind={dev.device_kind!r}")
    seq = int(os.environ.get("BENCH_SEQ", "128"))
    size = os.environ.get("BENCH_SIZE", "large")
    # r4 sweep (BENCH_SWEEP=1 + manual refinement, bench_headline.json):
    # micro-batch 24 x gas 48 beats the old 96 x 16 by 10% at seq128 —
    # 449.05 vs 409.5 samples/s/chip with selective remat.  The smaller
    # live micro-batch keeps the fused fwd+bwd working set closer to
    # VMEM and the longer accumulation scan amortises the LAMB step;
    # global batch stays in the published LAMB recipe range
    # (bert-pretraining.md 16K-64K: 24 x 48 x 32 chips = 36.9K).
    # remat=False fails to compile at any batch (score tensors exceed
    # HBM without the replay); full remat peaks lower end-to-end.
    # seq512 defaults (r5 sweep): micro-batch 6 x gas 48 with the streaming
    # kernel (auto at >= 512 non-causal now) = 84.8 samples/s/chip; larger
    # micro-batches spill (b=8 collapsed to 43.5).  The recipe-faithful
    # 256-samples/chip/step config (b=8 x gas=32, bert-pretraining.md
    # phase 2) measures within 1% of the optimum — WALLCLOCK.md uses it.
    seq512 = seq >= 512
    batch_per_chip = int(os.environ.get(
        "BENCH_BATCH", "6" if seq512 else "24"))
    steps = int(os.environ.get("BENCH_STEPS", "8"))
    gas = int(os.environ.get("BENCH_GAS", "48"))
    remat_env = os.environ.get("BENCH_REMAT", "selective")
    remat = {"0": False, "1": True, "false": False, "true": True}.get(
        remat_env.lower(), remat_env)   # "selective"/"dots"/"full" pass

    if os.environ.get("BENCH_SWEEP", "0") == "1":
        best = None
        for r in (False, "selective", "full"):
            for b in (batch_per_chip // 2, batch_per_chip, batch_per_chip * 2):
                try:
                    res = run_config(size, seq, b, steps, r, gas=gas)
                except Exception as e:  # OOM etc: report and move on
                    print(f"sweep remat={r} batch={b}: FAILED {e}",
                          file=sys.stderr)
                    continue
                print(f"sweep remat={r} batch={b}: "
                      f"{res['per_chip']:.1f} samples/s/chip "
                      f"mfu={res['mfu']:.3f}", file=sys.stderr)
                if best is None or res["per_chip"] > best[0]["per_chip"]:
                    best = (res, r, b)
        if best is None:
            raise RuntimeError(
                "BENCH_SWEEP: every configuration failed (see stderr)")
        res, remat, batch_per_chip = best
    else:
        res = run_config(size, seq, batch_per_chip, steps, remat, gas=gas)

    _emit({
        "metric": "bert_%s_seq%d_pretrain_samples_per_sec_per_chip"
                  % (size, seq),
        "value": round(res["per_chip"], 2),
        "unit": "samples/s/chip",
        "vs_baseline": round(res["per_chip"] / 200.0, 3),
        "mfu": round(res["mfu"], 4),
        "achieved_tflops": round(res["achieved_tflops"], 1),
        "predicted_peak_hbm_gb": res.get("predicted_peak_hbm_gb"),
        "predicted_boundary_ms": res.get("predicted_boundary_ms"),
        "predicted_profile": res.get("predicted_profile"),
        "measured_boundary_ms": res.get("measured_boundary_ms"),
        "predicted_drift": res.get("predicted_drift"),
        "batch_per_chip": batch_per_chip,
        "gas": gas,
        "remat": remat,
    })
    return 0


if __name__ == "__main__":
    sys.exit(main())
