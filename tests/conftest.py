"""Shared fixtures. NOTE: no XLA_FLAGS here -- smoke tests and benches see
the real single CPU device; only launch/dryrun.py forces 512 devices.
Multi-device shard_map tests spawn a subprocess with the flag instead
(``run_on_cpu_devices``)."""

import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def run_on_cpu_devices():
    """``run(code, n_devices=8, timeout=900)``: execute a Python snippet in
    a child process on ``n_devices`` virtual CPU devices and require it to
    exit 0 and print ``OK``. The device count is locked at first JAX use,
    so it cannot be set in-process. The child pins ``JAX_PLATFORMS=cpu`` in
    its own environment: it can never reach for an accelerator that this
    process may hold."""

    def run(code: str, n_devices: int = 8, timeout: int = 900) -> None:
        from repro.dist import cpu_child_env
        env = dict(cpu_child_env(n_devices), PYTHONPATH=SRC)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=timeout)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "OK" in out.stdout

    return run
