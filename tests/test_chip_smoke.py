"""Bring-up contracts that hold without a chip.

``chip_smoke.py`` proves the trainer on a TPU; what the CPU rig can pin is
that it refuses to report anything without one, and that nothing which
spawns chip-owning workers (the launchers, a bare ``import deepspeed_tpu``)
opens a device first — a chip belongs to one process.
"""

import json
import os
import subprocess
import sys

import pytest

from deepspeed_tpu.launcher import launch
from deepspeed_tpu.launcher.run import encode_world_info

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_needs_a_tpu():
    """No accelerator → non-zero exit and no result line; the tiny CPU
    rehearsal exists only behind its explicit argument."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "platform: cpu" in proc.stdout
    assert "needs a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_imports_and_launchers_open_no_device():
    """Importing the package and both launcher modules must leave jax's
    backends uninitialised: the parent that held the chips would starve
    the workers it spawns."""
    code = (
        "import deepspeed_tpu, deepspeed_tpu.launcher.run, "
        "deepspeed_tpu.launcher.launch\n"
        "import jax\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n")
    env = dict(os.environ,
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr


def test_launch_refuses_several_processes_per_tpu_host(monkeypatch, tmp_path):
    """Two slots on one host are two processes; off the CPU platform each
    would try to own every local chip, so the per-node launcher refuses
    before spawning anything."""
    marker = tmp_path / "ran"
    script = tmp_path / "worker.py"
    script.write_text(f"open({str(marker)!r}, 'a').write('x')\n")
    argv = [f"--world_info={encode_world_info({'localhost': [0, 1]})}",
            str(script)]
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert launch.main(argv) == 2
    assert not marker.exists()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")      # a virtual fleet may
    assert launch.main(argv) == 0
    assert marker.read_text() == "xx"


@pytest.mark.slow
def test_rehearsal_result_names_the_cpu(tmp_path):
    """``--rehearse-cpu`` runs the smoke's own code end to end at a tiny
    size and labels the result as the CPU's."""
    report = tmp_path / "report.json"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--rehearse-cpu", "--report", str(report)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 4}}
    rep = json.loads(report.read_text())
    assert rep["rehearsal"] is True and rep["leg3"]["dp"] == 4
