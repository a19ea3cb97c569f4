"""Shared test fixtures.

IMPORTANT: no XLA_FLAGS / device-count forcing here — smoke tests and
benches must see the real (single-CPU) device topology.  Tests that need
multiple devices run their body in a subprocess with forced host devices via
:func:`run_forced_devices` (shared by tests/test_multidevice.py and
tests/test_sharded_backends.py so the mesh plumbing lives in ONE place).
"""

import os
import subprocess
import sys

# Hermeticity: a developer's ~/.cache/repro-dip tuning cache must not leak
# measured block-size entries into the suite's lookup_blocks expectations.
# Must be set before the first `repro.api` import (the cache loads there).
os.environ.setdefault("REPRO_DIP_NO_TUNING_CACHE", "1")

import numpy as np
import pytest
from hypothesis import settings

# The first example of a property test pays for JAX tracing and compiling,
# which no per-example deadline can budget for.
settings.register_profile("repro", deadline=None)
settings.load_profile("repro")

_FORCED_PREAMBLE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.plan import make_mesh
"""


def run_forced_devices(body: str, devices: int = 4, timeout: int = 600) -> str:
    """Run ``body`` in a fresh interpreter with ``devices`` forced host CPU
    devices (XLA locks the device count at first init, so multi-device code
    can never run in the pytest process itself).  ``jax``/``jnp``/``np`` and
    ``make_mesh`` (Auto-axes meshes) are pre-imported; asserts on the
    child's exit code and returns its stdout."""
    code = _FORCED_PREAMBLE.format(n=devices) + body
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=timeout,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.path.expanduser("~"), "JAX_PLATFORMS": "cpu",
             "REPRO_DIP_NO_TUNING_CACHE": "1"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


@pytest.fixture
def rng():
    return np.random.default_rng(0)
