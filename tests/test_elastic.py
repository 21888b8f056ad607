"""Elastic resize: reshard a checkpoint across different device layouts
(subprocess with 8 host devices: save sharded on 8, restore on 4+others)."""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.train import save_pytree, load_pytree, reshard_state

mesh8 = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
mesh4 = jax.make_mesh((4, 2), ("data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 2)
rng = np.random.default_rng(0)
state = {"w": jnp.asarray(rng.standard_normal((16, 32)), jnp.float32),
         "b": jnp.asarray(rng.standard_normal((32,)), jnp.float32)}
spec8 = {"w": P("data", None), "b": P()}
sharded = jax.tree.map(lambda x, s: jax.device_put(x, NamedSharding(mesh8, s)),
                       state, spec8)
with tempfile.TemporaryDirectory() as d:
    save_pytree(sharded, d, 7)
    # restore onto a DIFFERENT mesh (8x1 -> 4x2) with different specs
    spec42 = {"w": P("data", "model"), "b": P("model")}
    shard42 = jax.tree.map(lambda s: NamedSharding(mesh4, s), spec42)
    restored, step = load_pytree(state, d, shardings=shard42)
    ok_step = step == 7
    maxdiff = max(float(jnp.abs(restored[k] - state[k]).max()) for k in state)
    # reshard in place too
    back = reshard_state(restored, mesh8, spec8)
    maxdiff2 = max(float(jnp.abs(back[k] - state[k]).max()) for k in state)
print("RESULT:" + json.dumps({"ok_step": ok_step, "maxdiff": maxdiff,
                              "maxdiff2": maxdiff2}))
"""


@pytest.mark.slow
def test_checkpoint_reshard_across_meshes():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"   # virtual CPU devices, never the chip
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][0]
    out = json.loads(line[len("RESULT:"):])
    assert out["ok_step"]
    assert out["maxdiff"] == 0.0
    assert out["maxdiff2"] == 0.0
