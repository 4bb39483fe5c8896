"""Traffic kind ``train_steps_update``: ``train_steps`` — the same loop, the
same window, the same checks — and ONE CHECK MORE in set-up, for a cell
whose first-step loss cannot tell one precision from the next (random
weights of std 0.02 and random labels: whatever the body computes, the loss
is the logarithm of the vocabulary to three digits).

``first_update``: the engine's first ``train_batch`` changes the fp32 master
of every parameter; the family's plain reference, differentiated in float32,
says what that change should be (``family.reference_first_update``: its
gradient ``g`` through the job's first optimizer step).  Per leaf of the
reference's layout (``family.to_reference``)

    reading = |engine's change - reference's change| / |reference's change|

in the norm ``|d|^2 = sum |g| d^2`` — each weight's change weighed by how
much the loss depends on that weight — 0 for the same step, 1 for a state
left unchanged, more for a step the other way.  The check holds the WORST
leaf to ``checks.first_update_limit`` of the configuration.  A leaf the
reference's gradient reaches nowhere (a correction bias) reads 0 if the
engine leaves it too, and infinity if not; in the other leaves a weight that
gradient does not reach (a row of the table no token used) is not weighed.
With ``--probe-reference`` the same reading is taken of the reference itself
at lower precisions, which has to fail the limit, and every leaf's reading
is printed (PERF.md gives both sides).

Why that norm: Adam's first step moves every weight by the rate whatever its
gradient's size, so in the plain norm a leaf reads ``2 sqrt(f)`` for the
share ``f`` of weights that moved the other way — and those are the weights
whose gradient is nearest zero, which any rounding flips and on which
nothing depends (at the rehearsal's sizes on the CPU: 0.32 for the bf16
engine, 0.71 for fp8 operands).  Weighed by ``|g|`` the reading is about
the gradient's relative error (on the v5e, PR 33, in the dense leaves of
its cell: 0.045 for the engine, 0.25 for fp8 operands).

The reference's gradient needs the chip nearly whole (fp32 parameters,
their gradient, one layer's activations: 13 GB), so it is taken before the
engine is built and kept on the host with the change and the initial
parameters (12 B a parameter there); after the first warm-up step they go
back a leaf at a time beside the engine's master.  The step's batch is
handed to the reference whole, so the kind takes one micro-batch on one data
shard (``gas`` 1, ``data`` 1, ZeRO 0).

``train_steps.run`` drives the rest; its ``set_up`` is replaced by the one
here for the length of the call.  A ``benchmark`` PR that may edit
``train_steps.py`` folds the check into it and deletes this file.
"""

import math
import time
from unittest import mock

from benchmark.traffic_kinds import train_steps

#: leaves named in the check's line, worst first
LEAVES_SHOWN = 3


def leaf_readings(change, expected, gradient):
    """``{leaf: reading}`` of ``change`` — a tree, or its leaves in order,
    made as they are asked for — against the trees ``expected`` and
    ``gradient``, on the device a leaf at a time (host arrays are sent
    there: at 4 B a parameter the host's own passes took a minute)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sums(got, want, grad):
        weight, off = jnp.abs(grad), got - want
        return (jnp.sum(weight * off * off), jnp.sum(weight * want * want),
                jnp.any(off != 0))

    out = {}
    named, _ = jax.tree_util.tree_flatten_with_path(expected)
    if isinstance(change, (dict, list, tuple)):
        change = jax.tree_util.tree_leaves(change)
    for (path, want), got, grad in zip(
            named, change, jax.tree_util.tree_leaves(gradient), strict=True):
        off, size, moved = (float(x) for x in sums(got, want, grad))
        out[jax.tree_util.keystr(path)] = (
            math.sqrt(off / size) if size else math.inf if moved else 0.0)
    return out


def worst(readings):
    return sorted(readings.items(), key=lambda kv: -kv[1])[:LEAVES_SHOWN]


def reference_update(cell, params, batch, **precision):
    """``family.reference_first_update`` in one jitted call; the change
    comes back as host arrays."""
    import jax
    value, change, gradient = jax.jit(
        lambda p, b: cell.family.reference_first_update(
            p, b, cell.config, **precision))(params, batch)
    return float(value), jax.device_get(change), jax.device_get(gradient)


def set_up(cell, opts, clock, log, devices):
    """``train_steps.set_up`` with the reference's first update taken beside
    its loss, and the engine's first warm-up step held to it."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.utils import compile_cache

    family, traffic = cell.family, cell.traffic
    if (traffic["gas"] != 1 or cell.layout["mesh"]["data"] != 1
            or cell.layout["zero_stage"]):
        raise ValueError("train_steps_update hands the step's batch to the "
                         "reference whole and reads the engine's master by "
                         "leaf: gas 1, one data shard, ZeRO 0")
    with clock("batches"):
        pool = train_steps.batch_pool(cell, opts.seed)
    with clock("weights"):
        model = family.build_model(cell.config, traffic)
        # on the last chip, as train_steps.set_up says why
        key = jax.device_put(jax.random.PRNGKey(opts.seed), devices[-1])
        params = jax.block_until_ready(jax.jit(model.init_params)(key))
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    log(f"parameters: {n_params:,} (fp32, made on {devices[-1]})")

    with clock("reference"):
        ref, expected, gradient = reference_update(cell, params, pool[0])
        start = jax.device_get(params)
        if opts.probe_reference:
            for name, precision in (
                    ("stored in bfloat16", {"dtype": jnp.bfloat16}),
                    ("matmul operands at 7 mantissa bits",
                     {"operand_bits": 7}),
                    ("matmul operands at 3 (fp8 e4m3)", {"operand_bits": 3})):
                value, change, _ = reference_update(cell, params, pool[0],
                                                    **precision)
                off = leaf_readings(change, expected, gradient)
                limit = cell.config["checks"]["first_update_limit"]
                log(f"reference {name}: loss {value:.6f} (float32 "
                    f"{ref:.6f}), first update off the float32 one by "
                    f"{worst(off)} at the worst leaves, by the limit "
                    f"{limit} {'' if worst(off)[0][1] <= limit else 'NOT '}"
                    f"correct; every leaf: {off}")
                del change

    with clock("initialize"):
        engine, _, _, _ = deepspeed_tpu.initialize(
            config=train_steps.engine_config(cell,
                                             compile_cache.enabled_dir()),
            model=model, model_parameters=params,
            mesh=train_steps.make_mesh(cell.layout, devices))
        del params
    used = [str(d) for d in engine.mesh.devices.flat]
    if used != [str(d) for d in devices]:
        raise RuntimeError(f"the engine's mesh holds {used}, not {devices}")

    with clock("warmup"):
        warm, warm_s, warm_compiles = [], [], []
        for i in range(traffic["warmup_steps"]):
            t, before = time.perf_counter(), train_steps.compile_requests()
            warm.append(float(engine.train_batch(pool[0])))
            warm_s.append(time.perf_counter() - t)
            warm_compiles.append(train_steps.compile_requests() - before)
            if i == 0:
                # inside "warmup" on the clock, after the step's own time
                with clock("first_update"):
                    ends, starts = (jax.tree_util.tree_leaves(
                        family.to_reference(tree, cell.config))
                        for tree in (engine.master, start))
                    readings = leaf_readings(
                        (a - b for a, b in zip(ends, starts, strict=True)),
                        expected, gradient)
                    del ends, starts, start, expected, gradient
    log(f"warm-up steps (fenced): {[round(s, 3) for s in warm_s]} s, "
        f"programs compiled or read from the cache in each "
        f"{warm_compiles}, losses {warm}")
    if opts.probe_reference:
        log(f"the engine's first update, every leaf: {readings}")

    rules = cell.config["checks"]
    drop = (warm[0] - min(warm[1:])) / warm[0]
    top = worst(readings)
    checks = {
        "reference": {
            "engine_loss": warm[0], "reference_loss": ref,
            "abs_diff": abs(warm[0] - ref),
            "tolerance": rules["loss_tolerance"],
            "ok": abs(warm[0] - ref) <= rules["loss_tolerance"]},
        "first_update": {
            "worst_leaves": top, "leaves": len(readings),
            "limit": rules["first_update_limit"],
            "ok": top[0][1] <= rules["first_update_limit"]},
        "warmup_loss_drop": {
            "share": drop, "least": rules["warmup_loss_drop_share"],
            "ok": drop >= rules["warmup_loss_drop_share"]}}
    return engine, pool, checks, warm_s[-1], n_params


def run(cell, opts, clock, log):
    with mock.patch.object(train_steps, "set_up", set_up):
        return train_steps.run(cell, opts, clock, log)
