"""Parallel streaming restore + persistent compile cache (fast resume).

The restore pipeline (checkpoint.py "parallel streaming restore") must be
a pure wall-clock optimization: a reader pool fetching chunk records
concurrently, leaves assembled as chunks land, device placement overlapped
with the remaining reads — and bitwise the same state as the serial path,
with failures surfacing as a NAMED error on the restoring thread instead
of a hang.  The compile-cache half: a process whose in-memory executables
are gone (= a relaunch) must get its step programs back from the
persistent cache instead of recompiling (resilience counters prove it).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import checkpoint as ck
from deepspeed_tpu.config import DeepSpeedConfig, DeepSpeedConfigError
from deepspeed_tpu.parallel.topology import make_mesh
from deepspeed_tpu.resilience import chaos
from deepspeed_tpu.resilience.counters import COUNTERS
from deepspeed_tpu.utils import compile_cache
from deepspeed_tpu.zero import LazyParts
from simple_model import SimpleModel, random_dataset

HIDDEN = 16
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def base_config(restore_threads, readahead_mb=256.0, **over):
    cfg = {
        "train_batch_size": 32,
        "steps_per_print": 10 ** 6,
        "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
        "fp16": {"enabled": True, "initial_scale_power": 8},
        "checkpoint": {"restore_threads": restore_threads,
                       "restore_readahead_mb": readahead_mb},
    }
    cfg.update(over)
    return cfg


def make_engine(config, seed=0, mp=1):
    model = SimpleModel(HIDDEN)
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=config, model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(seed)),
        mesh=make_mesh(model_parallel_size=mp) if mp > 1 else None)
    return engine


def train(engine, steps, data_seed=0):
    ds = random_dataset(64, HIDDEN, seed=data_seed)
    it = iter(engine.deepspeed_io(ds))
    for _ in range(steps):
        try:
            batch = next(it)
        except StopIteration:
            it = iter(engine.deepspeed_io(ds))
            batch = next(it)
        loss = engine(*batch)
        engine.backward(loss)
        engine.step()


def tree_bitwise(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------- parallel == serial parity

def test_parallel_equals_serial_zero1(tmpdir):
    """ZeRO-1 flat layout: the pooled reader path and the serial fallback
    restore bitwise-identical masters/moments/params, and the restore
    latency lands in the resilience counters."""
    e1 = make_engine(base_config(1, zero_optimization=True))
    train(e1, 6)
    e1.save_checkpoint(str(tmpdir), tag="t")

    e_ser = make_engine(base_config(1, zero_optimization=True), seed=91)
    e_par = make_engine(base_config(4, readahead_mb=0.05,
                                    zero_optimization=True), seed=92)
    COUNTERS.reset()
    assert e_ser.load_checkpoint(str(tmpdir), tag="t")[0] is not None
    assert COUNTERS.restore_seconds > 0.0
    assert e_par.load_checkpoint(str(tmpdir), tag="t")[0] is not None

    tree_bitwise(e_ser.master_flat, e1.master_flat)
    tree_bitwise(e_par.master_flat, e_ser.master_flat)
    tree_bitwise(e_par.opt_state, e_ser.opt_state)
    tree_bitwise(e_par.params, e_ser.params)


def _gpt2_engine(threads, seed=7, mp=1):
    """Tiny GPT-2 at ZeRO-3 (SimpleModel doesn't cooperate with parameter
    partitioning) — the stage whose shard-native per-(row, dp) records the
    reader pool fetches concurrently."""
    from deepspeed_tpu.models import GPT2
    model = GPT2.from_size("tiny", vocab_size=64, max_seq_len=16,
                           num_layers=2, hidden_size=32, num_heads=4)
    engine, _, _, _ = deepspeed_tpu.initialize(
        config={"train_batch_size": 8,
                "steps_per_print": 10 ** 6,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 3},
                "checkpoint": {"restore_threads": threads,
                               "restore_readahead_mb": 0.05}},
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(seed)),
        mesh=make_mesh(model_parallel_size=mp))
    return engine


def test_parallel_equals_serial_zero3_cross_topology(tmp_path):
    """ZeRO-3 shard-native records (per-(row, dp) files — the format whose
    per-shard chunks the reader pool fetches concurrently), restored into
    a DIFFERENT topology (mp=2): pooled == serial, bitwise."""
    e1 = _gpt2_engine(1)
    rng = np.random.default_rng(3)
    toks = rng.integers(0, 64, size=(8, 16)).astype(np.int32)
    labels = np.roll(toks, -1, axis=1)
    labels[:, -1] = -1
    for _ in range(3):
        float(e1.train_batch((toks, labels)))
    e1.save_checkpoint(str(tmp_path), tag="t")

    e_ser = _gpt2_engine(1, seed=81, mp=2)
    e_par = _gpt2_engine(4, seed=82, mp=2)
    assert e_ser.load_checkpoint(str(tmp_path), tag="t")[0] is not None
    assert e_par.load_checkpoint(str(tmp_path), tag="t")[0] is not None

    tree_bitwise(e_par.master, e_ser.master)
    tree_bitwise(e_par.opt_state, e_ser.opt_state)
    tree_bitwise(e_par.params, e_ser.params)


# ---------------------------------------------------- failure-mode hardening

def _container_with_arrays(path, n=3, elems=4096):
    arrs = [np.arange(i * elems, (i + 1) * elems, dtype=np.float32)
            for i in range(n)]
    ck._save_obj(str(path), {"leaves": arrs})
    return arrs, ck._load_obj(str(path))["leaves"]   # memmap views


@pytest.mark.parametrize("threads", [1, 4])
def test_truncated_chunk_raises_named_error(tmp_path, threads):
    """A chunk that extends past EOF (torn copy, truncated download) must
    raise CheckpointReadError promptly on the restoring thread — never
    hand back short data, never hang the consumer."""
    arrs, views = _container_with_arrays(tmp_path / "box.pt")
    with open(tmp_path / "box.pt", "r+b") as f:
        f.truncate(ck._HEADER_PREFIX + arrs[0].nbytes // 2)

    plan = ck._RestorePlan(threads=threads, io_retries=0)
    stream = ck._stream_leaves([LazyParts.wrap(v) for v in views], plan)
    with pytest.raises(ck.CheckpointReadError, match="truncated"):
        list(stream)


def test_io_retry_budget_applies_per_reader(tmp_path):
    """Each chunk read gets the FULL io_retries budget (the retry composes
    around the individual reader, not the whole restore): n_parts injected
    failures with a budget of n_parts retries always succeed no matter how
    the pool distributes them; with a zero budget any injected failure is
    fatal — as the named error."""
    arrs, views = _container_with_arrays(tmp_path / "box.pt", n=3)
    leaves = [LazyParts.wrap(v) for v in views]

    chaos.reset()
    chaos.configure(io_fail_reads=3)
    retries_before = COUNTERS.io_retries
    try:
        out = list(ck._stream_leaves(
            leaves, ck._RestorePlan(threads=4, io_retries=3)))
    finally:
        chaos.reset()
    for got, want in zip(out, arrs):
        np.testing.assert_array_equal(got, want)
    assert COUNTERS.io_retries - retries_before == 3

    chaos.configure(io_fail_reads=100)
    try:
        with pytest.raises(ck.CheckpointReadError):
            list(ck._stream_leaves(
                leaves, ck._RestorePlan(threads=4, io_retries=0)))
    finally:
        chaos.reset()


def test_readahead_window_bounds_inflight(tmp_path):
    """A window smaller than one chunk still makes progress (at least one
    read stays in flight) and yields every leaf in order."""
    arrs, views = _container_with_arrays(tmp_path / "box.pt", n=4)
    plan = ck._RestorePlan(threads=2, readahead_mb=1e-6, io_retries=0)
    out = list(ck._stream_leaves([LazyParts.wrap(v) for v in views], plan))
    for got, want in zip(out, arrs):
        np.testing.assert_array_equal(got, want)


def test_lazyparts_concat_matches_eager():
    parts = [np.arange(6, dtype=np.float32).reshape(2, 3) + 10 * i
             for i in range(3)]
    lz = LazyParts.concat(parts, 1)
    np.testing.assert_array_equal(lz.materialize(),
                                  np.concatenate(parts, axis=1))
    assert lz.nbytes == sum(p.nbytes for p in parts)
    # nested composition keeps every chunk an independent part
    lz2 = LazyParts.concat([lz, LazyParts.wrap(parts[0])], 1)
    assert len(lz2.parts) == 4
    np.testing.assert_array_equal(
        lz2.materialize(), np.concatenate(parts + [parts[0]], axis=1))


# --------------------------------------------------------- config validation

def _cfg(pd):
    base = {"train_batch_size": 32,
            "optimizer": {"type": "Adam", "params": {"lr": 0.01}}}
    base.update(pd)
    return DeepSpeedConfig(base, dp_world_size=8)


def test_restore_config_validation():
    c = _cfg({"checkpoint": {"restore_threads": 4,
                             "restore_readahead_mb": 64}})
    assert c.checkpoint_restore_threads == 4
    assert c.checkpoint_restore_readahead_mb == 64.0
    with pytest.raises(DeepSpeedConfigError, match="restore_thread"):
        _cfg({"checkpoint": {"restore_thread": 4}})     # typo'd key is loud
    with pytest.raises(DeepSpeedConfigError, match=">= 0"):
        _cfg({"checkpoint": {"restore_threads": -1}})
    with pytest.raises(DeepSpeedConfigError, match="> 0"):
        _cfg({"checkpoint": {"restore_readahead_mb": 0}})


def test_compile_cache_config_validation():
    c = _cfg({"compile_cache": {"dir": "/tmp/cc",
                                "min_entry_size_bytes": 4096}})
    assert c.compile_cache_dir == "/tmp/cc"
    assert c.compile_cache_min_entry_size_bytes == 4096
    assert _cfg({"compile_cache": "/tmp/cc2"}).compile_cache_dir == "/tmp/cc2"
    assert _cfg({}).compile_cache_dir is None
    with pytest.raises(DeepSpeedConfigError, match="unknown"):
        _cfg({"compile_cache": {"path": "/tmp/cc"}})
    with pytest.raises(DeepSpeedConfigError, match="must be"):
        _cfg({"compile_cache": 7})
    with pytest.raises(DeepSpeedConfigError, match=">= 0"):
        _cfg({"compile_cache": {"dir": "/tmp/cc",
                                "min_entry_size_bytes": -1}})


# ------------------------------------------------ persistent compile cache

def test_compile_cache_warm_process_skips_recompile(tmp_path):
    """The fast-resume contract: after ``jax.clear_caches()`` (= the
    in-memory executable state of a fresh process) the same program comes
    back as persistent-cache HITS, not a recompile."""
    d = str(tmp_path / "cc")
    try:
        assert compile_cache.enable(d) == d
        assert os.environ[compile_cache.ENV_DIR] == d

        f = jax.jit(lambda x: jnp.sin(x) @ x.T)
        x = jnp.ones((256, 256), jnp.float32)
        m0 = COUNTERS.compile_cache_misses
        f(x).block_until_ready()
        assert COUNTERS.compile_cache_misses > m0    # cold: wrote the cache
        assert any(n.endswith("-cache") for n in os.listdir(d))

        jax.clear_caches()                           # "relaunch"
        h0 = COUNTERS.compile_cache_hits
        f(x).block_until_ready()
        assert COUNTERS.compile_cache_hits > h0      # warm: skipped XLA
    finally:
        compile_cache.disable()
    assert compile_cache.ENV_DIR not in os.environ


def test_compile_cache_engine_wiring(tmp_path):
    """The engine enables the cache at build (before any step traces) from
    the config, exports the env fallback for relaunched workers, and its
    train path produces cache entries."""
    d = str(tmp_path / "cc")
    try:
        engine = make_engine(base_config(1, compile_cache=d))
        assert engine.compile_cache_dir == d
        assert os.environ[compile_cache.ENV_DIR] == d
        train(engine, 1)
        assert any(n.endswith("-cache") for n in os.listdir(d))

        # env fallback: a config WITHOUT a compile_cache block (the
        # relaunched-worker case — launcher exported the dir) resolves
        # to the same directory
        assert compile_cache.resolve_dir(
            _cfg({})) == d
    finally:
        compile_cache.disable()


def test_jax_compilation_cache_dir_outranks_config(tmp_path):
    """Where the machine sets JAX_COMPILATION_CACHE_DIR, that directory is
    the cache and the code sets no other: an engine whose config names
    another directory still compiles into jax's.  A fresh process, because
    jax reads the variable when it is imported."""
    jax_dir, cfg_dir = tmp_path / "from_env", tmp_path / "from_config"
    script = tmp_path / "build.py"
    script.write_text(
        "import json, os, sys\n"
        f"sys.path[:0] = [{REPO!r}, {os.path.join(REPO, 'tests')!r}]\n"
        "import jax, numpy as np\n"
        "import deepspeed_tpu\n"
        "from simple_model import SimpleModel\n"
        "model = SimpleModel(8)\n"
        "engine, _, _, _ = deepspeed_tpu.initialize(\n"
        "    model=model, model_parameters=model.init_params(\n"
        "        jax.random.PRNGKey(0)),\n"
        "    config={'train_batch_size': 8, 'optimizer': {'type': 'Adam',\n"
        "            'params': {'lr': 1e-3}},\n"
        f"            'compile_cache': {str(cfg_dir)!r}}})\n"
        "x = np.ones((8, 8), np.float32); y = np.zeros((8,), np.int32)\n"
        "float(engine.train_batch((x, y)))\n"
        "print(json.dumps({'jax': jax.config.jax_compilation_cache_dir,\n"
        "                  'engine': engine.compile_cache_dir,\n"
        "                  **engine.resilience_counters()}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(jax_dir))
    env.pop(compile_cache.ENV_DIR, None)
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["jax"] == out["engine"] == str(jax_dir)
    assert out["compile_cache_misses"] > 0       # the listener installed
    assert any(n.endswith("-cache") for n in os.listdir(jax_dir))
    assert not cfg_dir.exists()


def test_checkout_cache_dir_is_fixed_and_ignored():
    """With no JAX_COMPILATION_CACHE_DIR, chip_smoke.py and bench.py keep
    the cache at one fixed git-ignored path in the checkout (the test rig
    itself runs with the variable removed — tests/conftest.py)."""
    assert compile_cache.JAX_ENV_DIR not in os.environ
    assert compile_cache.checkout_dir(REPO) == os.path.join(REPO,
                                                            ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    for name in ("chip_smoke.py", "bench.py"):
        with open(os.path.join(REPO, name)) as f:
            src = f.read()
        assert "compile_cache.checkout_dir(" in src, name


def test_launcher_propagates_compile_cache_dir(tmp_path):
    """``dst --compile_cache_dir`` reaches every worker attempt — the
    first launch AND each --max_restarts relaunch — as
    DSTPU_COMPILE_CACHE_DIR, so all attempts land in one persistent
    cache (the engine's env fallback picks it up even when the
    ds_config carries no compile_cache block)."""
    from deepspeed_tpu.launcher import launch
    from deepspeed_tpu.launcher.run import encode_world_info
    from deepspeed_tpu.resilience import RESUME_EXIT_CODE

    script = tmp_path / "worker.py"
    seen = tmp_path / "seen.txt"
    script.write_text(
        "import os, sys\n"
        f"with open({str(seen)!r}, 'a') as f:\n"
        "    f.write(os.environ.get('DSTPU_COMPILE_CACHE_DIR', 'MISSING')"
        " + '\\n')\n"
        f"lines = open({str(seen)!r}).read().splitlines()\n"
        f"sys.exit(0 if len(lines) >= 2 else {RESUME_EXIT_CODE})\n")
    rc = launch.main([
        f"--world_info={encode_world_info({'localhost': [0]})}",
        "--max_restarts=3", "--restart_backoff=0.01",
        f"--compile_cache_dir={tmp_path / 'cc'}",
        str(script)])
    assert rc == 0
    attempts = seen.read_text().splitlines()
    assert attempts == [str(tmp_path / "cc")] * 2   # launch + relaunch


def test_compile_cache_hits_after_restore(tmp_path):
    """The full fast-resume sequence: train → save → fresh engine →
    restore → (clear in-memory executables = relaunch) → step, and the
    step comes back as persistent-cache hits with ZERO misses.

    The zero-misses half is the regression pin: restore used to rebuild
    ``opt_state.step`` with a bare ``jnp.asarray`` — an unpinned scalar
    where the engine's own path carries a replicated sharding — so the
    boundary program re-lowered to a DIFFERENT executable and every
    resume paid a recompile the cache could never serve."""
    d = str(tmp_path / "cc")
    ckdir = str(tmp_path / "ck")
    try:
        e1 = make_engine(base_config(1, compile_cache=d))
        # drop executables earlier tests left in jax's in-memory cache:
        # a program served from memory never compiles, so it would never
        # be WRITTEN to the persistent cache — and the warm step below
        # would pay a miss for it
        jax.clear_caches()
        train(e1, 1)
        e1.save_checkpoint(ckdir, tag="t")

        e2 = make_engine(base_config(1, compile_cache=d), seed=1)
        e2.load_checkpoint(ckdir, tag="t")
        jax.clear_caches()                           # "relaunch"
        h0 = COUNTERS.compile_cache_hits
        m0 = COUNTERS.compile_cache_misses
        train(e2, 1)
        assert COUNTERS.compile_cache_hits - h0 > 0
        assert COUNTERS.compile_cache_misses - m0 == 0
    finally:
        compile_cache.disable()
