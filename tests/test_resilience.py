"""Resilience subsystem: preemption drain + auto-resume, NaN sentinel,
hang watchdog, storage retry, checkpoint discovery, launcher restarts.

The chaos tier (``-m chaos``; docs/resilience.md): every fault is injected
DETERMINISTICALLY (resilience.chaos) and every resume asserts *bitwise*
parity with an uninterrupted run — "it recovered" means "the trajectory is
the one that would have happened anyway".
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu import resilience
from deepspeed_tpu.checkpoint import find_latest_valid_tag, validate_tag
from deepspeed_tpu.data import ArrayDataset, DeepSpeedDataLoader
from deepspeed_tpu.resilience import (COUNTERS, PreemptionHandler,
                                      RESUME_EXIT_CODE, WATCHDOG_EXIT_CODE,
                                      Watchdog, chaos)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from simple_model import SimpleModel  # noqa: E402

pytestmark = pytest.mark.chaos

HIDDEN = 8

ZERO_CFG = {
    "train_batch_size": 8,
    "steps_per_print": 1000,
    "optimizer": {"type": "Adam", "params": {"lr": 0.02}},
    "fp16": {"enabled": True, "loss_scale": 128.0},
    "zero_optimization": True,
}


@pytest.fixture(autouse=True)
def _clean_chaos():
    """Order-independence: every test starts with disarmed injection
    points, zeroed counters, and no leaked signal handlers."""
    chaos.reset()
    COUNTERS.reset()
    yield
    chaos.reset()
    COUNTERS.reset()


def _engine_factory(cfg):
    def factory():
        engine, _, _, _ = ds.initialize(model=SimpleModel(hidden_dim=HIDDEN),
                                        config=dict(cfg))
        return engine
    return factory


def _dataset(n=64, seed=0, dtype=np.float16):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, HIDDEN)).astype(dtype)
    y = rng.integers(0, HIDDEN, size=(n,)).astype(np.int32)
    return ArrayDataset(x, y)


def _loader(dataset, seed=3):
    return DeepSpeedDataLoader(dataset, batch_size=8, mesh=None, seed=seed)


def _split_step(engine, batch):
    loss = engine(*batch)
    engine.backward(loss)
    engine.step()
    return loss


from simple_model import master_bytes as _master_bytes  # noqa: E402


# ------------------------------------------------- preemption + auto-resume

def test_sigterm_drain_and_bitwise_resume(tmpdir):
    """SIGTERM mid-run → flag → boundary poll → emergency checkpoint →
    RESUME_EXIT_CODE; a relaunch (fresh engine + loader) auto-resumes —
    data-iterator state included — and finishes BITWISE identical to an
    uninterrupted run."""
    factory = _engine_factory(ZERO_CFG)
    dataset = _dataset()

    unbroken = resilience.run_resumable(
        factory, _split_step, steps=6,
        save_dir=str(tmpdir.join("unbroken")), data_loader=_loader(dataset))
    ref_bytes = _master_bytes(unbroken)

    save_dir = str(tmpdir.join("interrupted"))
    handler = PreemptionHandler(sentinel_file=str(tmpdir.join("nope")))
    chaos.configure(sigterm_step=3, sigterm_rank=0)
    try:
        with pytest.raises(SystemExit) as ei:
            resilience.run_resumable(factory, _split_step, steps=6,
                                     save_dir=save_dir,
                                     data_loader=_loader(dataset),
                                     handler=handler)
        assert ei.value.code == RESUME_EXIT_CODE
        # chaos fires BEFORE step 3's work: the drain lands after step 3
        # completes, i.e. at global step 4
        tag = find_latest_valid_tag(save_dir)
        assert tag is not None and tag.startswith("emergency/"), tag
        with open(os.path.join(save_dir, "latest")) as f:
            assert f.read().strip() == tag

        # "relaunch": fresh engine + fresh loader, same save_dir
        handler.clear()
        resumed = resilience.run_resumable(factory, _split_step, steps=6,
                                           save_dir=save_dir,
                                           data_loader=_loader(dataset),
                                           handler=handler)
    finally:
        handler.uninstall()
    assert resumed.global_steps == 6
    assert COUNTERS.preemptions >= 1 and COUNTERS.restarts == 1
    assert _master_bytes(resumed) == ref_bytes


def test_sentinel_file_drain(tmpdir):
    """The DSTPU_PREEMPT_FILE spelling: touching the sentinel requests the
    same drain as a signal, without racing signal delivery."""
    factory = _engine_factory(ZERO_CFG)
    dataset = _dataset()
    sentinel = str(tmpdir.join("preempt"))
    handler = PreemptionHandler(sentinel_file=sentinel)
    seen = []

    def step_and_touch(engine, batch):
        _split_step(engine, batch)
        seen.append(engine.global_steps)
        if len(seen) == 2:
            open(sentinel, "w").close()

    try:
        with pytest.raises(SystemExit) as ei:
            resilience.run_resumable(factory, step_and_touch, steps=6,
                                     save_dir=str(tmpdir.join("ck")),
                                     data_loader=_loader(dataset),
                                     handler=handler)
    finally:
        handler.uninstall()
    assert ei.value.code == RESUME_EXIT_CODE
    tag = find_latest_valid_tag(str(tmpdir.join("ck")))
    assert tag == "emergency/global_step2", tag


def test_periodic_saves_and_discovery(tmpdir):
    """save_interval checkpoints carry the data-iterator state and the
    newest one wins discovery."""
    factory = _engine_factory(ZERO_CFG)
    dataset = _dataset()
    save_dir = str(tmpdir.join("ck"))
    resilience.run_resumable(factory, _split_step, steps=5,
                             save_dir=save_dir, data_loader=_loader(dataset),
                             save_interval=2)
    assert validate_tag(save_dir, "global_step2")
    assert validate_tag(save_dir, "global_step4")
    assert find_latest_valid_tag(save_dir) == "global_step4"
    # the data-iterator snapshot rides in client_state
    engine = factory()
    _, client = engine.load_checkpoint(save_dir, tag="global_step4")
    assert client[resilience.DATA_ITER_KEY] == {
        "epoch": 0, "batch": 4, "seed": 3}


def test_resume_skips_half_written_tag(tmpdir):
    """A mid-save SIGKILL can leave a tag's model header durable but its
    ZeRO shard files missing — it then passes header-only validation, so
    the driver must exclude it after the full load fails and restore the
    next-newest valid tag instead of bricking every restart (and must
    RAISE, not silently train from scratch, when no candidate restores)."""
    import glob
    factory = _engine_factory(ZERO_CFG)
    dataset = _dataset()
    save_dir = str(tmpdir.join("ck"))
    resilience.run_resumable(factory, _split_step, steps=3,
                             save_dir=save_dir, data_loader=_loader(dataset),
                             save_interval=1)       # tags global_step1, 2
    for f in glob.glob(os.path.join(save_dir, "global_step2",
                                    "zero_pp_rank_*")):
        os.remove(f)                                 # half-written newest
    engine = factory()
    tag = resilience.restore_latest(engine, save_dir,
                                    io_retries=0)
    assert tag == "global_step1", tag
    assert engine.global_steps == 1
    # no restorable candidate at all -> raise (never silently restart)
    for f in glob.glob(os.path.join(save_dir, "global_step1",
                                    "zero_pp_rank_*")):
        os.remove(f)
    with pytest.raises(FileNotFoundError):
        resilience.restore_latest(factory(), save_dir, io_retries=0)


def test_discovery_mtime_tie_breaks_numerically(tmpdir):
    """Equal model-file mtimes (coarse-mtime FS, rsync'd dirs): the
    trailing STEP NUMBER breaks the tie, so global_step10 beats
    global_step9 even though '9' > '1' lexicographically."""
    factory = _engine_factory(ZERO_CFG)
    save_dir = str(tmpdir.join("ck"))
    engine = factory()
    for tag in ("global_step9", "global_step10"):
        engine.save_checkpoint(save_dir, tag=tag)
    probe = lambda t: os.path.join(save_dir, t, "mp_rank_00_model_states.pt")
    os.utime(probe("global_step9"), (1000.0, 1000.0))
    os.utime(probe("global_step10"), (1000.0, 1000.0))
    assert find_latest_valid_tag(save_dir) == "global_step10"


# ------------------------------------------------------------- NaN sentinel

NAN_CFG = {
    "train_batch_size": 8,
    "steps_per_print": 1000,
    "optimizer": {"type": "Adam", "params": {"lr": 0.02}},
    "resilience": {"nan_sentinel": True},
}


def _fp32_batch(i):
    rng = np.random.default_rng(100 + i)
    x = rng.normal(size=(8, HIDDEN)).astype(np.float32)
    y = rng.integers(0, HIDDEN, size=(8,)).astype(np.int32)
    return x, y


def test_nan_sentinel_skips_poisoned_step(tmpdir):
    """fp32 + nan_sentinel: a non-finite batch skips the boundary (master
    bitwise unchanged, no scheduler step, counter bumped) and training
    continues finite — the fp16 skip-on-overflow contract extended."""
    engine = _engine_factory(NAN_CFG)()
    _split_step(engine, _fp32_batch(0))
    before = _master_bytes(engine)

    x, y = _fp32_batch(1)
    _split_step(engine, chaos.poison_batch((x, y)))
    assert engine.overflow is True
    assert engine.skipped_steps == 1
    assert COUNTERS.nan_skips == 1
    assert _master_bytes(engine) == before          # boundary was a no-op

    loss = _split_step(engine, _fp32_batch(2))      # recovers immediately
    assert np.isfinite(float(loss))
    assert np.isfinite(np.frombuffer(_master_bytes(engine),
                                     np.float32)).all()


def test_without_sentinel_nan_poisons_params(tmpdir):
    """Negative control: the same poisoned batch WITHOUT the sentinel
    corrupts the fp32 master — proving the sentinel is load-bearing."""
    cfg = {k: v for k, v in NAN_CFG.items() if k != "resilience"}
    engine = _engine_factory(cfg)()
    _split_step(engine, _fp32_batch(0))
    x, y = _fp32_batch(1)
    _split_step(engine, chaos.poison_batch((x, y)))
    assert engine.overflow is False                 # fp32: no skip contract
    assert not np.isfinite(np.frombuffer(_master_bytes(engine),
                                         np.float32)).all()


def test_nan_sentinel_via_driver_chaos_point(tmpdir):
    """The driver-level injection: chaos nan_step poisons exactly one step
    and the run still reaches the target bitwise-finite.  fp32 on purpose:
    nan_skips counts only skips the SENTINEL caused — under fp16 the skip
    contract (and its skipped_steps accounting) pre-exists, and a dynamic
    scaler's calibration overflows must not read as NaN degradation."""
    factory = _engine_factory(NAN_CFG)
    dataset = _dataset()
    chaos.configure(nan_step=2)
    engine = resilience.run_resumable(
        factory, _split_step, steps=4, save_dir=str(tmpdir.join("ck")),
        data_loader=_loader(dataset))
    assert engine.global_steps == 4
    assert engine.skipped_steps == 1 and COUNTERS.nan_skips == 1
    assert np.isfinite(np.frombuffer(_master_bytes(engine),
                                     np.float32)).all()


# ------------------------------------------------------------ storage retry

def test_io_error_on_save_retries_then_succeeds(tmpdir):
    engine = _engine_factory(ZERO_CFG)()
    _split_step(engine, _fp32_batch(0))
    chaos.configure(io_fail_writes=2)
    save_dir = str(tmpdir.join("ck"))
    resilience.save_with_retry(engine, save_dir, tag="t0")   # io_retries=3
    assert COUNTERS.io_retries == 2
    assert validate_tag(save_dir, "t0")
    fresh = _engine_factory(ZERO_CFG)()
    path, _ = fresh.load_checkpoint(save_dir, tag="t0")
    assert path is not None


def test_io_retry_budget_exhausted_raises(tmpdir):
    engine = _engine_factory(ZERO_CFG)()
    chaos.configure(io_fail_writes=10)
    with pytest.raises(IOError, match="chaos: injected IO failure"):
        resilience.save_with_retry(engine, str(tmpdir.join("ck")), tag="t0",
                                   io_retries=2)
    assert COUNTERS.io_retries == 2


# ------------------------------------------------------------ hang watchdog

def test_watchdog_fires_and_names_stuck_frame():
    """An injected stall past the deadline produces a stack dump naming
    the stuck frame (chaos_stall) and the armed label, plus the recent
    step-timing history."""
    wd = Watchdog(timeout_s=0.3, abort=False, poll_s=0.05)
    with wd.armed("warmup step"):
        pass                                         # seeds the history
    with wd.armed("stalled collective"):
        chaos.chaos_stall(30.0, until=wd.fire_event)  # ends when it fires
    assert wd.fired
    assert COUNTERS.watchdog_fires == 1
    assert "chaos_stall" in wd.last_dump             # the stuck frame
    assert "stalled collective" in wd.last_dump      # the armed label
    assert "warmup step" in wd.last_dump             # timing history


def test_watchdog_near_miss_counter():
    wd = Watchdog(timeout_s=5.0, abort=False, near_miss_frac=0.02,
                  poll_s=0.05)
    with wd.armed("slowish step"):
        chaos.chaos_stall(0.2)
    assert not wd.fired
    assert COUNTERS.watchdog_near_misses == 1


def test_watchdog_abort_exit_code(tmpdir):
    """watchdog_abort: past the deadline the process dies with
    WATCHDOG_EXIT_CODE after flushing the dump — the launcher's restart
    contract."""
    script = tmpdir.join("stall.py")
    script.write(
        "from deepspeed_tpu.resilience import Watchdog, chaos\n"
        "wd = Watchdog(timeout_s=0.3, abort=True, poll_s=0.05)\n"
        "with wd.armed('stuck step'):\n"
        "    chaos.chaos_stall(60.0)\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", "")})
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == WATCHDOG_EXIT_CODE, (proc.returncode,
                                                   proc.stderr)
    assert "chaos_stall" in proc.stderr
    assert "stuck step" in proc.stderr


def test_engine_stall_injection_fires_watchdog(tmpdir):
    """The env/config-keyed stall lands INSIDE the engine's armed boundary
    region: the watchdog sees a hung collective and the dump names the
    stuck frame, the armed label AND — via the flight-recorder tail —
    the exact step the process stalled at, plus a loadable dump file
    (docs/observability.md "Flight recorder")."""
    from deepspeed_tpu.observability import flightrec

    cfg = dict(NAN_CFG)
    # a production-size deadline while the step programs compile: under a
    # loaded host the compile of step 0 outlasts any deadline short enough
    # to test with, and the dump would name the wrong step
    cfg["resilience"] = {"watchdog_timeout_s": 120.0}
    cfg["observability"] = {"flight_recorder_dir": str(tmpdir)}
    engine = _engine_factory(cfg)()
    _split_step(engine, _fp32_batch(0))      # boundary: global step 0 -> 1
    wd = engine._watchdog
    assert not wd.fired
    # compiled: a step is milliseconds now.  The stall ends when the
    # watchdog has fired (60 s is a ceiling, not a wait)
    wd.timeout_s, wd.poll_s = 1.0, 0.05
    chaos.configure(stall_step=1, stall_s=60.0, stall_until=wd.fire_event)
    _split_step(engine, _fp32_batch(1))      # stalls at global step 1
    assert wd.fired
    assert "chaos_stall" in wd.last_dump
    assert "optimizer boundary step" in wd.last_dump
    # dump enrichment: the recorder tail names the stalled step (the last
    # armed entry is the boundary that never completed)
    assert "recent flight-recorder entries:" in wd.last_dump
    assert "arm label=boundary step=1" in wd.last_dump
    # ...and the ring was persisted as a loadable post-mortem artifact
    payload = flightrec.load_dump(
        str(tmpdir.join("flightrec_rank0_watchdog.json")))
    assert payload["reason"] == "watchdog"
    assert payload["entries"][-1]["kind"] == "arm"
    assert payload["entries"][-1]["step"] == 1
    assert COUNTERS.watchdog_fires >= 1


def test_engine_arms_watchdog_from_config():
    cfg = dict(NAN_CFG)
    cfg["resilience"] = {"watchdog_timeout_s": 120.0}
    engine = _engine_factory(cfg)()
    assert engine._watchdog is not None
    _split_step(engine, _fp32_batch(0))
    labels = [lbl for lbl, _ in engine._watchdog.timings]
    assert "backward (fused fwd+bwd)" in labels
    assert "optimizer boundary step" in labels


# --------------------------------------------- latest pointer + discovery

def test_corrupt_latest_falls_back_to_newest_valid_tag(tmpdir):
    """Regression (ISSUE 4 satellite): an empty/corrupt/stale `latest`
    pointer must fall back to the newest VALID tag dir, not break resume."""
    engine = _engine_factory(ZERO_CFG)()
    _split_step(engine, _fp32_batch(0))
    save_dir = str(tmpdir.join("ck"))
    engine.save_checkpoint(save_dir, tag="older")
    _split_step(engine, _fp32_batch(1))
    engine.save_checkpoint(save_dir, tag="newer")
    # deterministic mtime ordering regardless of filesystem timestamp
    # granularity
    for i, tag in enumerate(("older", "newer")):
        d = os.path.join(save_dir, tag)
        for f in os.listdir(d):
            os.utime(os.path.join(d, f), (1000 + i, 1000 + i))

    # (a) empty pointer
    with open(os.path.join(save_dir, "latest"), "w"):
        pass
    fresh = _engine_factory(ZERO_CFG)()
    path, _ = fresh.load_checkpoint(save_dir)
    assert path is not None and path.endswith("newer"), path

    # (b) pointer naming a deleted tag
    with open(os.path.join(save_dir, "latest"), "w") as f:
        f.write("gone_tag")
    fresh = _engine_factory(ZERO_CFG)()
    path, _ = fresh.load_checkpoint(save_dir)
    assert path is not None and path.endswith("newer"), path

    # (c) newest tag itself corrupt -> next-newest valid wins
    mfile = os.path.join(save_dir, "newer", "mp_rank_00_model_states.pt")
    with open(mfile, "wb") as f:
        f.write(b"DSTPUCK1garbage")
    assert not validate_tag(save_dir, "newer")
    assert find_latest_valid_tag(save_dir) == "older"

    # (d) nothing valid at all -> (None, None), not an exception
    import shutil
    shutil.rmtree(os.path.join(save_dir, "older"))
    fresh = _engine_factory(ZERO_CFG)()
    path, client = fresh.load_checkpoint(save_dir)
    assert path is None and client is None


def test_latest_pointer_written_atomically(tmpdir):
    """The pointer publish goes through temp + os.replace: after any save
    there is never a lingering temp file, and the pointer content is the
    full tag."""
    engine = _engine_factory(ZERO_CFG)()
    _split_step(engine, _fp32_batch(0))
    save_dir = str(tmpdir.join("ck"))
    engine.save_checkpoint(save_dir, tag="t0")
    assert not os.path.exists(os.path.join(save_dir, "latest.tmp"))
    with open(os.path.join(save_dir, "latest")) as f:
        assert f.read() == "t0"


# -------------------------------------------------------- launcher restarts

def _encode_world(info):
    from deepspeed_tpu.launcher.run import encode_world_info
    return encode_world_info(info)


RESTART_SCRIPT = """\
import os, sys
marker = os.environ["RESTART_MARKER"]
n = int(open(marker).read()) if os.path.exists(marker) else 0
open(marker, "w").write(str(n + 1))
sys.exit(0 if n + 1 >= int(os.environ["RESTART_SUCCEED_AT"]) else {code})
"""


def test_launcher_restarts_until_success(tmpdir, monkeypatch):
    """launch.py --max_restarts relaunches on the resilience exit codes
    and stops at the first clean exit."""
    from deepspeed_tpu.launcher import launch
    script = tmpdir.join("worker.py")
    script.write(RESTART_SCRIPT.format(code=RESUME_EXIT_CODE))
    marker = str(tmpdir.join("count"))
    monkeypatch.setenv("RESTART_MARKER", marker)
    monkeypatch.setenv("RESTART_SUCCEED_AT", "3")
    rc = launch.main([
        f"--world_info={_encode_world({'localhost': [0]})}",
        "--max_restarts=5", "--restart_backoff=0.01",
        str(script)])
    assert rc == 0
    assert open(marker).read() == "3"      # 1 launch + 2 restarts


def test_launcher_restart_budget_exhausted(tmpdir, monkeypatch):
    from deepspeed_tpu.launcher import launch
    script = tmpdir.join("worker.py")
    script.write(RESTART_SCRIPT.format(code=WATCHDOG_EXIT_CODE))
    marker = str(tmpdir.join("count"))
    monkeypatch.setenv("RESTART_MARKER", marker)
    monkeypatch.setenv("RESTART_SUCCEED_AT", "100")
    rc = launch.main([
        f"--world_info={_encode_world({'localhost': [0]})}",
        "--max_restarts=2", "--restart_backoff=0.01",
        str(script)])
    assert rc == WATCHDOG_EXIT_CODE
    assert open(marker).read() == "3"      # 1 launch + 2 restarts, then stop


def test_launcher_does_not_restart_real_crashes(tmpdir, monkeypatch):
    """A plain exit-1 crash would crash again: the budget must not be
    burned on it."""
    from deepspeed_tpu.launcher import launch
    script = tmpdir.join("worker.py")
    script.write(RESTART_SCRIPT.format(code=1))
    marker = str(tmpdir.join("count"))
    monkeypatch.setenv("RESTART_MARKER", marker)
    monkeypatch.setenv("RESTART_SUCCEED_AT", "100")
    rc = launch.main([
        f"--world_info={_encode_world({'localhost': [0]})}",
        "--max_restarts=5", "--restart_backoff=0.01",
        str(script)])
    assert rc == 1
    assert open(marker).read() == "1"      # no relaunch


def test_restart_delay_jittered_exponential():
    from deepspeed_tpu.launcher.launch import restart_delay_s
    lo = restart_delay_s(1, base=1.0, rand=lambda: 0.0)
    hi = restart_delay_s(1, base=1.0, rand=lambda: 1.0)
    assert lo == pytest.approx(0.5) and hi == pytest.approx(1.5)
    assert restart_delay_s(3, base=1.0, rand=lambda: 0.5) \
        == pytest.approx(4.0)
    assert restart_delay_s(30, base=1.0, cap=60.0, rand=lambda: 0.0) \
        == pytest.approx(30.0)             # capped before jitter


# ----------------------------------------------------------- observability

def test_counters_exported_through_engine():
    engine = _engine_factory(NAN_CFG)()
    _split_step(engine, _fp32_batch(0))
    got = engine.resilience_counters()
    assert set(got) == {"restarts", "preemptions", "nan_skips", "io_retries",
                        "watchdog_near_misses", "watchdog_fires",
                        "restore_seconds", "compile_cache_hits",
                        "compile_cache_misses"}

    class FakeWriter:
        def __init__(self):
            self.scalars = {}

        def add_scalar(self, name, value, step):
            self.scalars[name] = value

    engine.summary_writer = FakeWriter()
    x, y = _fp32_batch(1)
    _split_step(engine, chaos.poison_batch((x, y)))
    assert engine.summary_writer.scalars["Train/Resilience/nan_skips"] == 1
