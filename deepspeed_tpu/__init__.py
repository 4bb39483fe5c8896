"""deepspeed_tpu — a TPU-native training engine with the capabilities of
DeepSpeed v0.1.0 (and beyond: ZeRO stages 1-3 including stage-3/FSDP
parameter partitioning, pipeline GPipe/1F1B, sequence/context parallelism
two ways — ring attention and Ulysses all-to-all — and MoE expert
parallelism), built on JAX / XLA / Pallas / pjit.

Public API mirrors the reference (/root/reference/deepspeed/__init__.py:28-169):
``initialize(...)`` returns an ``(engine, optimizer, dataloader, lr_scheduler)``
4-tuple; ``add_config_arguments(parser)`` injects the standard CLI flags.
Submodules: ``models`` (sharded GPT-2/BERT family incl. ring/Ulysses
attention), ``tokenization`` + ``squad`` (wordpiece pipeline),
``metrics``, ``checkpoint`` (incl. ``load_module_tree``/
``init_from_module_tree`` transfer), ``ops`` (optimizers incl. Lion +
Pallas kernels), ``parallel`` (mesh/collectives/pipeline), ``zero3``
(parameter-partitioning helpers), ``resilience`` (preemption-safe
training, auto-resume, hang watchdog, fault injection —
docs/resilience.md).
"""

__version__ = "0.1.0"
__version_major__, __version_minor__, __version_patch__ = (
    int(x) for x in __version__.split("."))
__git_hash__ = None
__git_branch__ = None


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mesh=None,
               dist_init_required=None,
               collate_fn=None,
               config=None,
               config_params=None,
               param_groups=None,
               seed=0):
    """Construct the engine; returns (engine, optimizer, dataloader, lr_scheduler).

    Reference signature: /root/reference/deepspeed/__init__.py:28-102.  The
    ``mpu`` argument becomes ``mesh`` (a ``jax.sharding.Mesh`` or a
    ``deepspeed_tpu.parallel.MeshConfig``); ``model`` is a model-returning-loss
    callable or a ``deepspeed_tpu.Module``; ``model_parameters`` is the initial
    parameter pytree (or None to let the module init them).
    """
    from deepspeed_tpu.engine import DeepSpeedTpuEngine

    engine = DeepSpeedTpuEngine(args=args,
                                model=model,
                                optimizer=optimizer,
                                model_parameters=model_parameters,
                                training_data=training_data,
                                lr_scheduler=lr_scheduler,
                                mesh=mesh,
                                dist_init_required=dist_init_required,
                                collate_fn=collate_fn,
                                config=config,
                                config_params=config_params,
                                param_groups=param_groups,
                                seed=seed)
    return_items = [engine,
                    engine.optimizer,
                    engine.training_dataloader,
                    engine.lr_scheduler]
    return tuple(return_items)


def init_distributed(coordinator_address=None, num_processes=None,
                     process_id=None, use_mpi=False):
    """Early multi-host rendezvous — MUST run before any other JAX call on
    multi-host launches (jax.distributed requirement).  The engine also
    triggers this from its ctor, but user scripts that touch JAX before
    ``initialize()`` (e.g. to init model params) should call this first.
    Reference analog: dist.init_process_group, deepspeed_light.py:125-130."""
    from deepspeed_tpu.parallel.topology import init_distributed as _init
    _init(coordinator_address=coordinator_address,
          num_processes=num_processes, process_id=process_id,
          use_mpi=use_mpi)


def _add_core_arguments(parser):
    """Core flags (reference /root/reference/deepspeed/__init__.py:105-153)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag for user code, no impact on engine)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to DeepSpeed json configuration file")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help="Deprecated enable DeepSpeed (helper flag for user code)")
    group.add_argument("--deepscale_config", default=None, type=str,
                       help="Deprecated path to DeepSpeed json configuration")
    group.add_argument("--deepspeed_mpi", default=False, action="store_true",
                       help="Run via MPI; rank/size discovered from the MPI environment")
    return parser


def add_config_arguments(parser):
    """Update an argument parser to enable config-file params
    (reference /root/reference/deepspeed/__init__.py:156-169)."""
    parser = _add_core_arguments(parser)
    return parser
