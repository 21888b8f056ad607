import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.graphs import sbm, rand_local, grid3d


def run_subprocess_json(script: str, timeout: int = 900) -> dict:
    """Run a python script in a subprocess and parse its ``RESULT:<json>``
    line — the shared recipe for the 8-host-device distributed tests
    (the child sets its own ``XLA_FLAGS`` device count before importing
    jax, so the parent's flags are scrubbed to keep the recipe hermetic,
    and it is pinned to the CPU: the parent may hold the chip)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"   # virtual CPU devices, never the chip
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][0]
    return json.loads(line[len("RESULT:"):])


@pytest.fixture(scope="session")
def sbm_graph():
    """8 planted clusters of 100 vertices (ground truth for recovery tests)."""
    return sbm(k=8, size=100, p_in=0.15, p_out=0.002, seed=1)


@pytest.fixture(scope="session")
def local_graph():
    return rand_local(2000, degree=5, seed=3)


@pytest.fixture(scope="session")
def grid_graph():
    return grid3d(10)


def dense_from_dict(d, n):
    out = np.zeros(n, dtype=np.float64)
    for k, v in d.items():
        out[k] = v
    return out
