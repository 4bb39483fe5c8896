"""``python -m deepspeed_tpu.analysis`` — graph-lint / capacity-plan a
DeepSpeed config.

For each config file a representative model is built (inferred from the
path: ``*bert*`` → tiny BertForPreTraining, ``*gpt2*`` → tiny GPT2,
anything else → the examples/simple MLP), an engine is constructed on a
virtual CPU mesh, the train step is traced, and the findings report is
printed.  Static analysis only — no optimizer step runs, no TPU is needed.

    python -m deepspeed_tpu.analysis examples/simple/ds_config.json
    python -m deepspeed_tpu.analysis --mode error examples/*/ds_config*.json
    python -m deepspeed_tpu.analysis --plan --profile v4-8 <config>
    python -m deepspeed_tpu.analysis --plan --json <config>   # CI artifact
    python -m deepspeed_tpu.analysis --concurrency --mode error  # host lint

``--plan`` adds the capacity planner: predicted per-device peak HBM of
the fused train_batch program, the persistent-state breakdown, bytes on
wire per step and predicted wire time, gated against ``--profile``'s HBM
(``memory.budget-exceeded`` is error severity).  ``--json`` emits one
machine-readable JSON line per config (findings + plan table) so CI can
artifact-diff lint/plan results across PRs.

Exit status: 0 clean (or ``--mode warn``), 2 when error-severity findings
survive suppression in ``--mode error``, 1 on usage/analysis failure.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_ENV_MARK = "_DSTPU_ANALYSIS_ENV"


def _reexec_with_analysis_env(argv):
    """Re-exec once with a deterministic analysis environment: CPU backend
    (static analysis needs no accelerator) and enough virtual CPU devices
    for the config's mesh.  Runs before jax is imported, so the parent
    never holds a device the child needs."""
    if os.environ.get(_ENV_MARK) == "1":
        return
    env = dict(os.environ)
    env[_ENV_MARK] = "1"
    env.setdefault("JAX_PLATFORMS", "cpu")
    if env["JAX_PLATFORMS"] == "cpu":
        # virtual device count: lcm of 8 (covers the shipped configs)
        # and every config's mp*sp*pp product, so make_mesh divides
        import math
        need = 8
        for a in argv:
            if a.endswith(".json") and os.path.exists(a):
                try:
                    with open(a) as f:
                        cfg = json.load(f)
                    prod = (int(cfg.get("model_parallel_size", 1))
                            * int(cfg.get("context_parallel_size", 1))
                            * int(cfg.get("pipeline_parallel_size", 1)))
                    need = need * prod // math.gcd(need, prod)
                except Exception:
                    pass
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={need}"
            ).strip()
    env.setdefault("JAX_ENABLE_X64", "0")
    os.execve(sys.executable,
              [sys.executable, "-m", "deepspeed_tpu.analysis"] + argv, env)


def _infer_family(path: str, override: str) -> str:
    if override != "auto":
        return override
    base = path.lower()
    import re
    tokens = re.split(r"[^a-z0-9]+", os.path.basename(base))
    if "serve" in tokens or "serving" in tokens:
        # serving config (FILENAME tokens only — a substring test would
        # misroute "server/", "preserve" or "observed"): gate the
        # INFERENCE engine's prefill + decode programs instead of a
        # train step (docs/inference.md)
        return "serve"
    if "bert" in base:
        return "bert"
    if "gpt" in base:
        return "gpt2"
    return "mlp"


def _load_example_mlp(config_path: str):
    """Lint the program the example ACTUALLY runs: when a train_simple.py
    sits next to the config, import its MLP instead of the built-in
    fallback copy — so the CI gate cannot drift from the example."""
    import importlib.util
    cand = os.path.join(os.path.dirname(os.path.abspath(config_path)),
                        "train_simple.py")
    if not os.path.exists(cand):
        return None
    try:
        spec = importlib.util.spec_from_file_location(
            "_dstpu_lint_example", cand)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        cls = getattr(mod, "MLP", None)
        hidden = int(getattr(mod, "HIDDEN", 64))
        if cls is not None:
            return cls(), hidden
    except Exception as e:
        print(f"note: could not import example model from {cand} ({e}); "
              f"using the built-in MLP", file=sys.stderr)
    return None


def _build_model(family: str, seq_len: int, config_path: str = ""):
    """A tiny engine-protocol model per family (the analysis runs over the
    traced graph structure, so tiny shapes exercise the same program as
    production sizes)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if family == "gpt2":
        from deepspeed_tpu.models.gpt2 import GPT2
        model = GPT2.from_size("tiny")

        def make_batch(b):
            rng = np.random.default_rng(0)
            toks = rng.integers(0, model.config.vocab_size,
                                (b, seq_len)).astype(np.int32)
            return (toks, toks.copy())
        return model, make_batch

    if family == "bert":
        from deepspeed_tpu.models.bert import BertForPreTraining
        model = BertForPreTraining.from_size("tiny")

        def make_batch(b):
            rng = np.random.default_rng(0)
            ids = rng.integers(0, model.config.vocab_size,
                               (b, seq_len)).astype(np.int32)
            mask = np.ones((b, seq_len), np.int32)
            tt = np.zeros((b, seq_len), np.int32)
            labels = np.where(rng.random((b, seq_len)) < 0.15, ids, -1)
            return (ids, mask, tt, labels.astype(np.int32))
        return model, make_batch

    loaded = _load_example_mlp(config_path)
    if loaded is not None:
        model, H = loaded
    else:
        H = 64

        class MLP:
            """Fallback copy of the examples/simple model (used only when
            no train_simple.py sits next to the config): inputs cast to
            the parameter dtype so fp16/bf16 configs run low-precision
            matmuls."""

            def init_params(self, rng):
                k1, k2 = jax.random.split(rng)
                s = 1.0 / np.sqrt(H)
                return {"w1": jax.random.normal(k1, (H, H)) * s,
                        "b1": jnp.zeros((H,)),
                        "w2": jax.random.normal(k2, (H, 1)) * s}

            def apply(self, params, x, y):
                x = x.astype(params["w1"].dtype)
                h = jax.nn.relu(x @ params["w1"] + params["b1"])
                pred = (h @ params["w2"])[:, 0].astype(jnp.float32)
                return jnp.mean((pred - y) ** 2)

        model = MLP()

    def make_batch(b):
        rng = np.random.default_rng(0)
        return (rng.normal(size=(b, H)).astype(np.float32),
                rng.normal(size=(b,)).astype(np.float32))
    return model, make_batch


def _analyze_serve_config(path: str, cfg: dict, an_cfg, suppress,
                          plan: bool = False, profile: str = None,
                          dispatch: bool = False):
    """Serve-config analysis: build a tiny GPT-2 InferenceEngine on the
    config (gating sections stripped — the CLI dispatches itself) and
    lint/plan EVERY serving program — prefill (+ the prefix-reuse tail
    bucket), decode/decode_many, and with an ``inference.speculative``
    section the draft prefill + fused draft/verify step (the engine
    builds the draft from ``speculative.draft_size``).  The serving
    analog of the train-step gate — ``--plan`` adds the capacity table
    with the persistent page-pool (and draft) lines, ``--dispatch`` the
    compile-stability pass (the exactly-N-executables invariant across
    prompt lengths and reuse offsets) and the priced per-iteration host
    timeline."""
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models.gpt2 import GPT2

    # auto slot sizing needs the profile; everything else gates via the
    # CLI's own dispatch, so keep only the profile from the section
    if an_cfg and an_cfg.get("profile") and "analysis" not in cfg:
        cfg["analysis"] = {"profile": an_cfg["profile"]}
    model = GPT2.from_size("tiny")
    dplans = None
    try:
        engine = InferenceEngine(model, config=cfg)
        rep = engine.run_graph_lint()
        cap = None
        from deepspeed_tpu.analysis import profiles as prof_mod
        prof = (prof_mod.resolve(profile) if profile
                else prof_mod.default_profile())
        if plan:
            cap = engine.plan_capacity(profile=prof)
            rep.extend(cap.to_report(subject="serve"))
        if dispatch:
            rep.extend(engine.run_stability())
            dplans = engine.plan_dispatch(profile=prof)
            for p in dplans.values():
                rep.extend(p.to_report())
    finally:
        from deepspeed_tpu.utils import compile_cache
        if compile_cache.enabled_dir() is not None:
            compile_cache.disable()
    rep.subject = f"{path} (model=serve)"
    return rep.filtered(suppress), cap, dplans


def _analyze_config(path: str, family: str, seq_len: int, suppress,
                    plan: bool = False, profile: str = None,
                    dispatch: bool = False):
    """(filtered lint Report, CapacityPlan | None, dispatch plans | None)
    for one config."""
    import jax

    import deepspeed_tpu
    from deepspeed_tpu import analysis

    with open(path) as f:
        cfg = json.load(f)
    # the CLI decides lint/plan dispatch itself; the engine must not also
    # raise on its own config keys
    cfg.pop("graph_lint", None)
    an_cfg = cfg.pop("analysis", None)
    family = _infer_family(path, family)
    if family == "serve":
        return _analyze_serve_config(path, cfg, an_cfg, suppress,
                                     plan=plan, profile=profile,
                                     dispatch=dispatch)
    model, make_batch = _build_model(family, seq_len, config_path=path)
    cap = None
    dplans = None
    try:
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=cfg,
            model_parameters=model.init_params(jax.random.PRNGKey(0)))
        batch = make_batch(engine.train_micro_batch_size_per_gpu()
                           * engine.dp_world_size)
        rep = analysis.analyze_engine(engine, batch, train=True)
        from deepspeed_tpu.analysis import profiles as prof_mod
        prof = (prof_mod.resolve(profile) if profile
                else prof_mod.default_profile())
        if plan or dispatch:
            # the fused train_batch program needs the full effective batch
            full = make_batch(engine.train_micro_batch_size_per_gpu()
                              * engine.dp_world_size
                              * engine.gradient_accumulation_steps())
        if plan:
            cap = engine.plan_capacity(full, train=True, fused=True,
                                       profile=prof)
            rep.extend(cap.to_report(subject="train_batch"))
        if dispatch:
            # compile-stability + per-step host-cost passes over the
            # production (fused) program family — stability.* errors
            # (the PR 5/PR 10 classes) gate exactly like lint errors
            rep.extend(engine.run_stability(full, fused=True))
            dplans = {"train_batch": engine.plan_dispatch(
                full, fused=True, profile=prof)}
            rep.extend(dplans["train_batch"].to_report())
    finally:
        # engine build enables any configured persistent compile cache
        # PROCESS-WIDE (and exports the env fallback for relaunches) —
        # turn it back off so one gated config's cache dir cannot leak
        # into the next config's build in this multi-config CLI
        from deepspeed_tpu.utils import compile_cache
        if compile_cache.enabled_dir() is not None:
            compile_cache.disable()
    rep.subject = f"{path} (model={family})"
    return rep.filtered(suppress), cap, dplans


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    _reexec_with_analysis_env(argv)

    ap = argparse.ArgumentParser(
        prog="python -m deepspeed_tpu.analysis",
        description="Statically analyze the train-step graph a DeepSpeed "
                    "config would build (collectives, precision, "
                    "transfers, shard specs).  See docs/analysis.md.")
    ap.add_argument("configs", nargs="*",
                    help="DeepSpeed JSON config file(s) to analyze "
                         "(optional with --concurrency, which runs over "
                         "source files, not configs)")
    ap.add_argument("--mode", choices=("warn", "error"), default="warn",
                    help="'error': exit 2 on error-severity findings "
                         "(the CI gate); 'warn' (default): report only")
    ap.add_argument("--model",
                    choices=("auto", "mlp", "gpt2", "bert", "serve"),
                    default="auto",
                    help="representative model family (default: inferred "
                         "from the config path; 'serve' gates the "
                         "inference engine's prefill/decode programs)")
    ap.add_argument("--seq-len", type=int, default=64,
                    help="sequence length for the synthetic batch")
    ap.add_argument("--suppress", action="append", default=[],
                    help="rule-code prefix to suppress (repeatable), e.g. "
                         "--suppress precision.upcast")
    ap.add_argument("--verbose", "-v", action="store_true",
                    help="include info-severity findings in the report")
    ap.add_argument("--plan", action="store_true",
                    help="run the capacity planner: predicted per-device "
                         "peak HBM + bytes on wire, gated against the "
                         "--profile budget (docs/analysis.md)")
    ap.add_argument("--dispatch", action="store_true",
                    help="run the compile-stability + dispatch-cost "
                         "passes: executable-key hazards (the PR 5/PR 10 "
                         "classes) as stability.* findings and the priced "
                         "per-step host timeline (docs/analysis.md "
                         "\"Dispatch & compile-stability\")")
    ap.add_argument("--profile", default=None,
                    help="backend profile for --plan (v4-8, v5e-8, v5p-8, "
                         "cpu-8; default: the running backend's profile)")
    ap.add_argument("--concurrency", action="store_true",
                    help="run the host-concurrency lint (lock-order, "
                         "blocking-under-lock, thread-role contracts) "
                         "over the serving control-plane SOURCES — no "
                         "config needed (docs/analysis.md \"Host "
                         "concurrency\")")
    ap.add_argument("--concurrency-path", action="append", default=[],
                    dest="concurrency_paths", metavar="FILE",
                    help="analyze these Python files instead of the "
                         "shipped control plane (repeatable; the "
                         "seeded-defect tests use this)")
    ap.add_argument("--json", action="store_true", dest="json_out",
                    help="emit one machine-readable JSON line per config "
                         "(findings + plan) instead of the pretty report — "
                         "the CI artifact format")
    args = ap.parse_args(argv)
    if not args.configs and not args.concurrency:
        ap.error("no configs given (and --concurrency not requested)")

    from deepspeed_tpu import analysis

    total_errors = 0
    failed = []

    if args.concurrency:
        from deepspeed_tpu.analysis import concurrency as conc
        paths = args.concurrency_paths or conc.control_plane_paths()
        try:
            rep = conc.check_paths(paths, suppress=args.suppress)
        except Exception as e:
            print(f"== concurrency: ANALYSIS FAILED ==\n   "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            failed.append("--concurrency")
            rep = None
        if rep is not None:
            if args.json_out:
                print(json.dumps({
                    "config": None,
                    "subject": "concurrency",
                    "mode": args.mode,
                    "paths": [os.path.relpath(p) for p in paths],
                    "findings": [{
                        "code": f.code, "severity": f.severity,
                        "message": f.message, "path": f.path,
                        "source": f.source, "pass": f.pass_name,
                    } for f in rep.sorted()],
                    "suppressed_count": rep.suppressed_count,
                    "errors": len(rep.errors),
                    "warnings": len(rep.warnings),
                }, sort_keys=True))
            else:
                print(f"== concurrency lint: {len(paths)} control-plane "
                      f"module(s) ==")
                text = rep.format(
                    min_severity=analysis.INFO if args.verbose
                    else analysis.WARNING)
                if text == "no findings" and rep.infos:
                    text = (f"no warning/error findings "
                            f"({len(rep.infos)} info — use --verbose)")
                print(text)
                print(rep.summary())
                print()
            total_errors += len(rep.errors)
    for path in args.configs:
        try:
            rep, cap, dplans = _analyze_config(
                path, args.model, args.seq_len, args.suppress,
                plan=args.plan, profile=args.profile,
                dispatch=args.dispatch)
        except Exception as e:
            # keep analyzing the remaining configs so one broken config
            # does not hide whether the others are clean
            print(f"== {path}: ANALYSIS FAILED ==\n   {type(e).__name__}: "
                  f"{e}", file=sys.stderr)
            failed.append(path)
            continue
        if args.json_out:
            doc = {
                "config": path,
                "subject": rep.subject,
                "mode": args.mode,
                "findings": [{
                    "code": f.code, "severity": f.severity,
                    "message": f.message, "path": f.path,
                    "source": f.source, "pass": f.pass_name,
                } for f in rep.sorted()],
                "suppressed_count": rep.suppressed_count,
                "errors": len(rep.errors),
                "warnings": len(rep.warnings),
                "plan": cap.to_json() if cap is not None else None,
                "dispatch": ({k: p.to_json() for k, p in dplans.items()}
                             if dplans is not None else None),
            }
            print(json.dumps(doc, sort_keys=True))
        else:
            print(f"== graph lint: {rep.subject} ==")
            text = rep.format(min_severity=analysis.INFO if args.verbose
                              else analysis.WARNING)
            if text == "no findings" and rep.infos:
                text = (f"no warning/error findings "
                        f"({len(rep.infos)} info — use --verbose)")
            print(text)
            print(rep.summary())
            if cap is not None:
                print("-- capacity plan --")
                print(cap.format_table())
            if dplans is not None:
                for p in dplans.values():
                    print("-- dispatch plan --")
                    print(p.format_table())
            print()
        total_errors += len(rep.errors)

    if failed:
        print(f"graph lint: analysis failed for {len(failed)} config(s): "
              f"{', '.join(failed)}", file=sys.stderr)
        return 1
    if args.mode == "error" and total_errors:
        print(f"graph lint: {total_errors} error-severity finding(s) — "
              f"failing (--mode error)", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
