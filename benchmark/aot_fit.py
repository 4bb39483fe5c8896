"""``python -m benchmark.aot_fit <cell> [--layers N]``: does the cell's step
program fit the chip?  Asked of the TPU's compiler, without a chip.

Compiles the cell's ``train_batch`` program at its real size for a described
``v5e:2x2`` topology (``on-chip-measurement`` guide, section 2, rehearsal 3)
and prints ``memory_analysis()`` per device, the attention plan, and the
Pallas calls and collectives in the compiled module.  This is how the depths
of the ``gpt2-xl`` cells were chosen, and what a later ``model_config`` PR
runs before it asks for chip time.  A compile that passes is not a chip run:
nothing here is a time, and what the process keeps on the device beside the
program (set-up copies) is not counted.

How: the engine is built here on virtual CPU devices through the normal
entry point, with real-size parameters (host memory: ~20 bytes per
parameter).  Its step is traced through the public ``engine.train_batch``
with the engine's state as the traced arguments, exported for the TPU
platform, and the exported module is compiled on the described devices under
the engine's own shardings and donation.  Program code that asks which
backend it runs on is steered to its TPU branch for the length of the trace
(``jax.default_backend`` and ``profiles.default_profile`` are patched here,
in the tool, not through an option of the program).
"""

import argparse
import os
import re
import sys
import time
from unittest import mock

TOPOLOGY = "v5e:2x2"
DEVICE_KIND = "TPU v5 lite"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--layers", type=int,
                    help="compile at this depth instead of the "
                         "configuration's")
    opts = ap.parse_args(argv)

    # must precede the first jax import
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmark import cell as cells
    cell = cells.load(opts.workload)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={cell.chips}").strip()

    import jax
    import numpy as np
    from jax import export
    from jax.experimental import topologies
    from jax.sharding import NamedSharding

    import deepspeed_tpu
    from deepspeed_tpu.analysis import profiles
    from deepspeed_tpu.models import layers

    from benchmark.traffic_kinds import train_steps

    if opts.layers:
        cell.config = cell.family.with_depth(cell.config, opts.layers)
    traffic = cell.traffic
    model = cell.family.build_model(cell.config, traffic)
    t = time.perf_counter()
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=train_steps.engine_config(cell, None), model=model,
        model_parameters=params,
        mesh=train_steps.make_mesh(cell.layout,
                                   jax.devices()[:cell.chips]))
    del params
    print(f"{cell.name}: {n_params:,} parameters, engine built on the CPU "
          f"in {time.perf_counter() - t:.0f} s", flush=True)

    batch = train_steps.batch_pool(cell, 0)[0]
    zero = engine.zero_flat

    def step(state, batch):
        # the engine's public state attributes, traced: train_batch is
        # plain Python around one jitted call
        (engine.params, master, engine.opt_state,
         engine.loss_scale_state) = state
        if zero:
            engine.master_flat = master
        else:
            engine.master = master
        loss = engine.train_batch(batch)
        return (engine.params, engine.master_flat if zero else engine.master,
                engine.opt_state, engine.loss_scale_state), loss

    state = (engine.params, engine.master_flat if zero else engine.master,
             engine.opt_state, engine.loss_scale_state)
    att = cell.family.attention_call(cell.config, traffic)
    v5e = profiles.for_device_kind(DEVICE_KIND)
    t = time.perf_counter()
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.object(profiles, "default_profile", lambda: v5e):
        plan = layers.attention_plan(att["seq"], att["heads"],
                                     att["head_dim"], att["causal"])
        exported = export.export(jax.jit(step), platforms=("tpu",))(
            state, batch)
    print(f"attention_plan({att['seq']}, {att['heads']}, {att['head_dim']}, "
          f"causal={att['causal']}) = {plan}; traced and exported in "
          f"{time.perf_counter() - t:.0f} s", flush=True)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=TOPOLOGY)
    mesh = train_steps.make_mesh(cell.layout, topo.devices[:cell.chips])

    def described(x):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, x.sharding.spec))

    state_shapes = jax.tree_util.tree_map(described, state)
    t = time.perf_counter()
    # the engine donates its whole state to the step in bf16
    compiled = jax.jit(exported.call, donate_argnums=(0,)).trace(
        state_shapes, tuple(np.asarray(x) for x in batch)).lower(
            lowering_platforms=("tpu",)).compile()
    print(f"compiled for {TOPOLOGY} in {time.perf_counter() - t:.0f} s",
          flush=True)

    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes
            + mem.generated_code_size_in_bytes)
    print(f"memory_analysis per device: arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, outputs "
          f"{mem.output_size_in_bytes / 1e9:.3f} GB, aliased "
          f"{mem.alias_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB, code "
          f"{mem.generated_code_size_in_bytes / 1e9:.3f} GB")
    print(f"program peak per device: {live / 1e9:.3f} GB = "
          f"{live / 2 ** 30:.2f} GiB "
          f"({engine.memory_estimate()['total_persistent_bytes'] / 1e9:.3f}"
          f" GB of it the engine's persistent state by its own estimate); "
          f"a program over the compiler's limit (15.75 GiB on a v5e, libtpu "
          f"0.0.34) does not compile at all")
    text = compiled.as_text()
    counts = {name: len(re.findall(rf"= \S+ {name}\(", text))
              for name in ("custom-call", "all-reduce", "reduce-scatter",
                           "all-gather", "all-gather-start",
                           "all-reduce-start", "collective-permute")}
    print(f"in the compiled module: {text.count('tpu_custom_call')} "
          f"mentions of tpu_custom_call; instructions {counts}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
