"""Pipeline parallelism: pp_forward == sequential layer application
(subprocess with 4 host devices)."""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
import jax, jax.numpy as jnp
from repro.models.pipeline import pp_forward

mesh = jax.make_mesh((4,), ("pipe",),
                     axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(0)
L, D, M, MB = 8, 16, 6, 4              # 8 layers, 6 microbatches of 4
params = {"w": jnp.asarray(rng.standard_normal((L, D, D)) * 0.3, jnp.float32),
          "b": jnp.asarray(rng.standard_normal((L, D)) * 0.1, jnp.float32)}
x = jnp.asarray(rng.standard_normal((M, MB, D)), jnp.float32)

def block_fn(lp, h):
    return jnp.tanh(h @ lp["w"] + lp["b"])

out = pp_forward(mesh, "pipe", params, x, block_fn)

# sequential oracle
ref = x
for i in range(L):
    lp = jax.tree.map(lambda a: a[i], params)
    ref = block_fn(lp, ref)
print("RESULT:" + json.dumps({
    "maxdiff": float(jnp.abs(out - ref).max()),
    "shape_ok": list(out.shape) == [M, MB, D],
}))
"""


@pytest.mark.slow
def test_pp_forward_matches_sequential():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"   # virtual CPU devices, never the chip
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][0]
    out = json.loads(line[len("RESULT:"):])
    assert out["shape_ok"]
    assert out["maxdiff"] < 1e-5
