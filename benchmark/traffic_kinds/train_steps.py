"""Traffic kind ``train_steps``: whole optimizer steps of a fixed shape through
``deepspeed_tpu.initialize`` → ``engine.train_batch``.

The traffic file gives ``seq``, ``micro_batch`` (rows per chip per
micro-step), ``gas`` (micro-steps per optimizer step), ``batch_pool`` (host
batches made from the seed, cycled), ``warmup_steps`` and ``api`` (``fused``
= ``train_batch``; the family may read more, such as ``masked_positions``).

Set-up: weights made on the device from the seed in one jitted call, the
family's plain reference on the rows whose loss the first step reports, one
engine, ``warmup_steps`` fenced steps on one repeated batch.  Window: each
step takes the next ``numpy`` batch of the pool, so staging a real input is
inside; at most two steps are in flight; dispatching stops when the clock
passes ``--seconds`` and the window closes when the last loss is ready.
With ``--trace 1`` a short stretch of the same loop runs under
``jax.profiler`` in place of the window.
"""

import collections
import contextlib
import math
import tempfile
import time
import traceback

import numpy as np

from benchmark import trace_reduce

#: steps in the traced stretch: three, or two where a step lasts over a
#: second (one boundary between steps has to be inside, or a slow dispatch
#: could not show as idle time)
TRACE_STEPS = 3
TRACE_STEPS_LONG = 2
MAX_IN_FLIGHT = 2


def engine_config(cell, cache_dir):
    """The ``deepspeed_tpu`` configuration of the cell's job."""
    job, traffic, layout = cell.config["job"], cell.traffic, cell.layout
    if traffic["api"] != "fused":
        raise ValueError(f"traffic api {traffic['api']!r}: train_steps "
                         f"drives engine.train_batch ('fused') only")
    config = {
        "train_batch_size": (traffic["micro_batch"] * traffic["gas"]
                             * layout["mesh"]["data"]),
        "gradient_accumulation_steps": traffic["gas"],
        "optimizer": job["optimizer"],
        "bf16": {"enabled": job["precision"] == "bf16"},
        "activation_checkpointing": {
            "enabled": True, "policy": job["activation_checkpointing"]},
        "steps_per_print": 10 ** 9,
    }
    if cache_dir:
        # the directory benchmark.run enabled (fixed, in the checkout;
        # JAX_COMPILATION_CACHE_DIR, where the machine sets it, outranks it
        # inside the program): the engine takes the one it is given
        config["compile_cache"] = {"dir": cache_dir}
    if job.get("gradient_clipping"):
        config["gradient_clipping"] = job["gradient_clipping"]
    if layout["zero_stage"]:
        config["zero_optimization"] = {"stage": layout["zero_stage"]}
    return config


def make_mesh(layout, devices):
    from deepspeed_tpu.parallel.topology import make_mesh
    axes = layout["mesh"]
    mesh = make_mesh(model_parallel_size=axes["model"],
                     context_parallel_size=axes["seq"],
                     pipeline_parallel_size=axes["pipe"], devices=devices)
    if dict(mesh.shape) != axes:
        raise ValueError(f"layout {layout['name']!r} wants mesh {axes}, "
                         f"{len(devices)} devices give {dict(mesh.shape)}")
    return mesh


def batch_pool(cell, seed):
    """``batch_pool`` host batches of one optimizer step each; the same seed
    gives the same batches."""
    t = cell.traffic
    rows = t["micro_batch"] * t["gas"] * cell.layout["mesh"]["data"]
    return [cell.family.make_batch(np.random.default_rng([seed, i]), rows,
                                   cell.config, t)
            for i in range(t["batch_pool"])]


def reported_rows(cell):
    """Row slices of a step's batch whose mean loss ``train_batch`` returns:
    the last micro-batch of each data-parallel shard (a shard scans its own
    contiguous rows; the loss is averaged over the shards)."""
    micro, gas = cell.traffic["micro_batch"], cell.traffic["gas"]
    return [slice((shard * gas + gas - 1) * micro, (shard + 1) * gas * micro)
            for shard in range(cell.layout["mesh"]["data"])]


def reference_loss(cell, params, batch, **precision):
    """The plain reference's loss on the rows ``reported_rows`` names, one
    jitted call per shard's micro-batch (all of one shape)."""
    import jax
    fn = jax.jit(lambda p, b: cell.family.reference_loss(
        p, b, cell.config, **precision))
    return float(np.mean([
        float(fn(params, tuple(x[rows] for x in batch)))
        for rows in reported_rows(cell)]))


def state_shares(engine):
    """Each device's share of the fp32 master and Adam moments."""
    import jax
    master = engine.master_flat if engine.zero_flat else engine.master
    held = collections.Counter()
    for leaf in jax.tree_util.tree_leaves(
            (master, engine.opt_state.m, engine.opt_state.v)):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    total = sum(held.values())
    return {str(d): held[d] / total for d in engine.mesh.devices.flat}


def memory_peak_bytes(device):
    """The device's peak memory so far, from its runtime's statistics.  The
    TPU runtime keeps live buffers (``bytes_in_use``: here the engine's
    state) apart from what a running program reserves for its temporaries
    (``bytes_reserved``: the step's activations and gradients), and
    ``peak_bytes_in_use`` alone leaves the second out.  So: the larger of the
    peak of live buffers (set-up's transients count) and the live buffers
    now plus the most a program reserved (the step, called after the
    window).  None where the backend keeps no statistics (the CPU)."""
    stats = device.memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return None
    return max(stats["peak_bytes_in_use"],
               stats["bytes_in_use"] + stats.get("peak_bytes_reserved", 0))


def run_steps(engine, pool, stop, span):
    """Dispatch steps until ``stop(dispatched, seconds)``, at most
    ``MAX_IN_FLIGHT`` in flight; returns (losses, failed, t_first_dispatch,
    t_last_ready).  A step that raises ends the loop and counts as failed."""
    losses, in_flight, failed = [], collections.deque(), 0
    t0 = time.perf_counter()
    while True:
        with span("batch_prep"):
            batch = pool[len(losses) % len(pool)]
        try:
            with span("dispatch"):
                loss = engine.train_batch(batch)
        except Exception:
            # the engine's state was donated to the step that raised:
            # nothing more can run, so report and close the window
            traceback.print_exc()
            failed += 1
            break
        losses.append(loss)
        in_flight.append(loss)
        if stop(len(losses), time.perf_counter() - t0):
            break
        if len(in_flight) >= MAX_IN_FLIGHT:
            with span("loss_wait"):
                in_flight.popleft().block_until_ready()
    with span("loss_wait"):
        for loss in in_flight:
            loss.block_until_ready()
    t1 = time.perf_counter()
    return [float(x) for x in losses], failed, t0, t1


def compile_requests():
    """Programs compiled or read from the persistent cache so far."""
    from deepspeed_tpu.resilience import COUNTERS
    counters = COUNTERS.as_dict()
    return counters["compile_cache_hits"] + counters["compile_cache_misses"]


def set_up(cell, opts, clock, log, devices):
    """Batches, weights, the reference, one engine, the fenced warm-up steps
    and the checks they allow.  Returns (engine, pool, checks, seconds of the
    last warm-up step, number of parameters)."""
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.utils import compile_cache

    family, traffic = cell.family, cell.traffic
    with clock("batches"):
        pool = batch_pool(cell, opts.seed)
    with clock("weights"):
        model = family.build_model(cell.config, traffic)
        # on the LAST chip of the cell: initialize() builds its ZeRO state
        # through full-size fp32 buffers on the first (the moments' zeros and
        # their slices, 12 B/param), and with the seed's weights (4 B/param)
        # on the same chip the 26-layer cell ran out of memory there
        # (PERF.md, finding PR 22)
        key = jax.device_put(jax.random.PRNGKey(opts.seed), devices[-1])
        params = jax.block_until_ready(jax.jit(model.init_params)(key))
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    log(f"parameters: {n_params:,} (fp32, made on {devices[-1]})")

    with clock("reference"):
        ref = reference_loss(cell, params, pool[0])
        if opts.probe_reference:
            import jax.numpy as jnp
            lower = [reference_loss(cell, params, pool[0], **precision)
                     for precision in ({"dtype": jnp.bfloat16},
                                       {"operand_bits": 7},
                                       {"operand_bits": 3})]
            log(f"reference loss, float32: {ref:.6f}; stored in bfloat16: "
                f"{lower[0]:.6f}; float32 with matmul operands at 7 mantissa "
                f"bits: {lower[1]:.6f}, at 3 (fp8 e4m3): {lower[2]:.6f}")

    with clock("initialize"):
        engine, _, _, _ = deepspeed_tpu.initialize(
            config=engine_config(cell, compile_cache.enabled_dir()),
            model=model, model_parameters=params,
            mesh=make_mesh(cell.layout, devices))
        del params
    used = [str(d) for d in engine.mesh.devices.flat]
    if used != [str(d) for d in devices]:
        raise RuntimeError(f"the engine's mesh holds {used}, not {devices}")

    with clock("warmup"):
        warm, warm_s, warm_compiles = [], [], []
        for _ in range(traffic["warmup_steps"]):
            t, before = time.perf_counter(), compile_requests()
            warm.append(float(engine.train_batch(pool[0])))
            warm_s.append(time.perf_counter() - t)
            warm_compiles.append(compile_requests() - before)
    log(f"warm-up steps (fenced): {[round(s, 3) for s in warm_s]} s, "
        f"programs compiled or read from the cache in each "
        f"{warm_compiles}, losses {warm}")

    rules = cell.config["checks"]
    # the LOWEST later loss: Adam's first steps on one batch overshoot, so
    # the last of three is not always the lowest
    drop = (warm[0] - min(warm[1:])) / warm[0]
    checks = {
        "reference": {
            "engine_loss": warm[0], "reference_loss": ref,
            "abs_diff": abs(warm[0] - ref),
            "tolerance": rules["loss_tolerance"],
            "ok": abs(warm[0] - ref) <= rules["loss_tolerance"]},
        "warmup_loss_drop": {
            "share": drop, "least": rules["warmup_loss_drop_share"],
            "ok": drop >= rules["warmup_loss_drop_share"]}}
    if cell.layout["zero_stage"]:
        shares = state_shares(engine)
        checks["state_split"] = {
            "shares": shares, "ok": all(abs(s - 1.0 / len(shares)) <= 0.02
                                        for s in shares.values())}
    return engine, pool, checks, warm_s[-1], n_params


def traced_steps(engine, pool, steps, span, keep_dir, clock):
    """``steps`` steps of the window's loop under ``jax.profiler``; returns
    ``run_steps``'s tuple and the reduced trace."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0         # keep the host at its own speed
    with contextlib.ExitStack() as stack:
        trace_dir = keep_dir or stack.enter_context(
            tempfile.TemporaryDirectory(prefix="bench_trace_"))
        clock.mark_setup_done()
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            ran = run_steps(engine, pool, lambda n, _s: n >= steps, span)
        finally:
            jax.profiler.stop_trace()
        return ran, trace_reduce.load(trace_dir)


def run(cell, opts, clock, log):
    """One run of the cell; see the module docstring.  ``clock`` records the
    set-up phases, ``log`` prints a line for people.  Returns the dict
    ``benchmark.run`` turns into the result line."""
    import jax
    from deepspeed_tpu.resilience import COUNTERS

    family, traffic = cell.family, cell.traffic
    devices = jax.devices()[:cell.chips]
    engine, pool, checks, step_s, n_params = set_up(cell, opts, clock, log,
                                                    devices)
    spans = []                    # the window's host spans, on perf_counter

    @contextlib.contextmanager
    def span(name):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + name):
            yield
        spans.append(trace_reduce.Event(name, t, time.perf_counter()))

    setup_cache = {k: COUNTERS.as_dict()[k] for k in ("compile_cache_hits",
                                                      "compile_cache_misses")}
    log(f"set-up: {setup_cache['compile_cache_hits']} programs read from "
        f"the compile cache, {setup_cache['compile_cache_misses']} compiled")
    requests_before = compile_requests()
    trace = None
    if opts.trace:
        steps = TRACE_STEPS if step_s < 1.0 else TRACE_STEPS_LONG
        (losses, failed, t0, t1), trace = traced_steps(
            engine, pool, steps, span, opts.keep_trace, clock)
    else:
        clock.mark_setup_done()
        losses, failed, t0, t1 = run_steps(
            engine, pool, lambda _n, s: s >= opts.seconds, span)
    compiled_in_window = compile_requests() - requests_before

    ceiling = family.loss_ceiling(cell.config)
    bad = [x for x in losses if not (math.isfinite(x) and x < ceiling)]
    checks["window_losses"] = {
        "first": losses[:1], "last": losses[-1:], "ceiling": ceiling,
        "not_finite_or_over": len(bad), "ok": not bad and bool(losses)}
    checks["no_compile_in_window"] = {
        "compile_requests": compiled_in_window,
        "ok": compiled_in_window == 0}

    peak_bytes = [memory_peak_bytes(d) for d in devices]
    log(f"window: {len(losses)} steps in {t1 - t0:.3f} s, losses "
        f"{losses[:1]} .. {losses[-1:]}")
    log(f"peak bytes per device {peak_bytes}; {devices[0]} memory_stats "
        f"{devices[0].memory_stats()}")
    tokens_per_step = (traffic["micro_batch"] * traffic["gas"]
                       * cell.layout["mesh"]["data"]
                       * family.tokens_per_row(traffic))
    rate = ((len(losses) - len(bad)) * tokens_per_step / (t1 - t0)
            / cell.chips)
    per_token = family.flops_per_token(cell.config, traffic)
    end_to_end = {"tokens_per_s_per_chip": rate}
    if opts.peaks:
        end_to_end["mfu"] = (100.0 * rate * per_token["total"]
                             / opts.peaks["bf16_flops_per_s"])
    return {
        "correct": all(c["ok"] for c in checks.values()) and not failed,
        "checks": checks,
        "attempted": len(losses) + failed,
        "failed": failed + len(bad),
        # rates of the untraced window only: a traced stretch is no window
        "end_to_end": None if opts.trace else end_to_end,
        "record": {
            "steps": len(losses), "trace": trace, "spans": spans,
            "setup_cache_misses": setup_cache["compile_cache_misses"],
            "memory_peak_bytes": max((b for b in peak_bytes if b),
                                     default=None),
            "n_params": n_params, "flops_per_token": per_token,
        },
    }
