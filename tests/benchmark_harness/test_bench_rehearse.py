"""``python -m benchmark.run`` end to end, each time in a child process (it
sets the platform before it imports jax): the CPU rehearsal of a BERT cell
and of the four-chip cell ends in a well-formed line that carries no metric,
and without a TPU the command refuses to report anything."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import cell as cells

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(args, cwd=cells.ROOT, extra_path=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # the rehearsal asks for its own devices
    if extra_path:
        env["PYTHONPATH"] = extra_path + os.pathsep + env.get("PYTHONPATH",
                                                              "")
    return subprocess.run([sys.executable, "-m", "benchmark.run", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=600)


@pytest.mark.parametrize("name,trace,chips", [
    ("bert-large.seq128", "0", 1), ("gpt2-xl.dp4-zero1", "1", 4)])
def test_rehearsal_ends_in_a_well_formed_line(name, trace, chips, tmp_path):
    args = ["--workload", name, "--seed", "3", "--seconds", "1", "--trace",
            trace, "--rehearse-cpu"]
    if trace == "1":
        # the two options a builder uses by hand: keep the profiler's files,
        # and price the loss tolerance against lower precisions
        args += ["--keep-trace", str(tmp_path / "trace"),
                 "--probe-reference"]
    proc = run(args)
    assert proc.returncode == 0, proc.stderr[-2000:]
    if trace == "1":
        from benchmark import trace_reduce
        assert trace_reduce.find_xplane(str(tmp_path / "trace"))
        assert "at 3 (fp8 e4m3):" in proc.stdout
    assert "platform: cpu" in proc.stdout
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert LINE_KEYS <= set(line)
    # a number from the CPU is never printed under a metric's name
    assert line["metrics"] == {} and "breakdown" not in line
    assert line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 4
    assert line["attempted"] >= 1 and line["failed"] == 0
    # the checks ran: the reference agreed with the engine's first loss, the
    # window compiled nothing, and on four devices the state was split
    assert "check reference:" in proc.stdout
    assert "'abs_diff'" in proc.stdout
    assert "check no_compile_in_window: {'compile_requests': 0, 'ok': True}" \
        in proc.stdout
    assert ("check state_split:" in proc.stdout) == (chips == 4)
    # opening the device is reported as a phase and left out of setup_s
    assert "open_chip" in line["setup_phases_s"]
    assert "which leaves out open_chip" in proc.stdout


def test_without_a_tpu_nothing_is_reported():
    proc = run(["--workload", "bert-large.seq128", "--seed", "0",
                "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert "platform: cpu" in proc.stdout
    assert '"correct"' not in proc.stdout


def test_unknown_workload_is_refused():
    proc = run(["--workload", "nope", "--rehearse-cpu"])
    assert proc.returncode != 0 and "no workload 'nope'" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_the_benchmark_alone_reports_nothing(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` there is no system under test: non-zero, no result."""
    manifest = cells.manifest()
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), tmp_path)
    for path in manifest["paths"]:
        shutil.copytree(os.path.join(cells.ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "bert-large.seq128", "--rehearse-cpu", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode != 0
    assert "deepspeed_tpu" in proc.stderr
    assert '"correct"' not in proc.stdout
