"""Test environment: 8 placeholder CPU devices for distribution tests.

Must run before any jax import.  The production dry-run (512 devices) sets
its own flag in its own process (launch/dryrun.py); benchmarks run with the
default single device.

Also makes the suite runnable without PYTHONPATH=src (falls back to the
src/ layout when ``repro`` isn't installed, e.g. before ``pip install -e .``).
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

try:
    import repro  # noqa: F401  (installed via pip install -e . ?)
except ImportError:
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402  (initialize after the flag)
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro.launch.mesh import auto_mesh  # noqa: E402


@pytest.fixture(scope="session")
def mesh222():
    return auto_mesh((2, 2, 2), ("pod", "data", "model"))


@pytest.fixture(scope="session")
def mesh42():
    return auto_mesh((4, 2), ("data", "model"))


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
