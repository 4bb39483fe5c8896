"""Test rig: run everything on a virtual 8-device CPU mesh.

The reference tests "multi-node" semantics by forking N local processes
(/root/reference/tests/unit/common.py:14-100).  On TPU/XLA we get the same
coverage cheaper: ``--xla_force_host_platform_device_count=8`` gives 8 fake
devices in one process, so sharding, ZeRO partition math and collectives all
execute for real.

The environment below must be in place before jax is imported; pytest loads
this file before any test module, so a plain ``python -m pytest tests/``
works whatever the caller's environment says.
"""

import os
import sys

os.environ["_DSTPU_TEST_ENV"] = "1"
os.environ["JAX_PLATFORMS"] = "cpu"
if "jax" in sys.modules:    # a plugin imported it first: env already read
    sys.modules["jax"].config.update("jax_platforms", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# a machine-wide persistent compile cache would turn the exact miss counts
# the cache tests assert into hits (and switch donation off on XLA-CPU)
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import tempfile  # noqa: E402

# flight-recorder dumps from bare-watchdog tests (no engine-configured
# dump dir) must not litter the checkout: route the env-fallback dump
# directory to a throwaway location (observability/flightrec.py resolve
# order: configured dir > this env var > cwd)
os.environ.setdefault("DSTPU_FLIGHTREC_DIR",
                      tempfile.mkdtemp(prefix="dstpu_flightrec_test_"))

import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Tier markers by location: tests/model/ is the 300-step convergence
    tier (slow); everything else is the fast tier.  `-m fast` gives <5 min
    signal; CI still runs the full suite (reference CI split:
    azure-pipelines.yml unit vs model stages)."""
    for item in items:
        path = str(item.fspath).replace(os.sep, "/")
        if "/tests/model/" in path:
            item.add_marker(pytest.mark.slow)
        elif (item.get_closest_marker("slow") is None
              and item.get_closest_marker("distributed") is None):
            item.add_marker(pytest.mark.fast)
