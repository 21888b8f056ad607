"""Every Pallas kernel compiles for a TPU v5e at the shapes the served path
gives it, single and vmapped over the engine's lanes.

Nothing runs: the chip is *described* (``jax.experimental.topologies``),
not attached, and the TPU compiler refuses here what it would refuse on the
chip — block shapes off the (8, 128) tiling, unlowerable primitives, too
much on-chip memory.  Each case goes through ``repro.core.ops`` with
``backend="pallas"`` and asserts the kernel is in the compiled program
(``tpu_custom_call``).  The topology is described inside a fixture, never
at import: only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ops
from repro.kernels import ops as kops

# Served shapes: an R-MAT scale-22 graph under the engine's default
# capacities (cap_f 2^12, cap_e 2^16, cap_v 2^12, cap_n 2^11,
# sweep_cap_e 2^17) and its default 8 lanes.
N = 1 << 22
CAP_F, CAP_E, CAP_V, CAP_N, SWEEP_CAP_E = 1 << 12, 1 << 16, 1 << 12, 1 << 11, 1 << 17
LANES = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                          # pragma: no cover
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the kernels compiled (interpret off)
    and the persistent compile cache off: a deviceless compile written to
    it could not be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kops, "on_tpu", lambda: True)
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _scatter(vec, idx, vals, valid):
    return ops.scatter_add(vec, idx, vals, valid, backend="pallas")


def _merge(ids, vals):
    return ops.segment_merge(ids, vals, N, CAP_V, backend="pallas")


def _scan(x):
    return ops.prefix_sum(x, backend="pallas")


# (name, op, [(shape, dtype) per argument] for one lane)
CASES = [
    ("scatter_add_f32_push", _scatter,
     [((N,), jnp.float32), ((CAP_E,), jnp.int32), ((CAP_E,), jnp.float32),
      ((CAP_E,), jnp.bool_)]),
    # the dense sweep: one group of 2,050 destinations takes all 131,072
    # contributions, 16 SMEM blocks of them
    ("scatter_add_i32_sweep", _scatter,
     [((CAP_N + 2,), jnp.int32), ((SWEEP_CAP_E,), jnp.int32),
      ((SWEEP_CAP_E,), jnp.int32), ((SWEEP_CAP_E,), jnp.bool_)]),
    ("segment_merge_sparse_round", _merge,
     [((CAP_V + CAP_E,), jnp.int32), ((CAP_V + CAP_E,), jnp.float32)]),
    ("prefix_sum_i32_edges", _scan, [((CAP_E,), jnp.int32)]),
    ("prefix_sum_f32", _scan, [((CAP_N,), jnp.float32)]),
]


@pytest.mark.parametrize("vmapped", [False, True], ids=["single", "lanes"])
@pytest.mark.parametrize("name,fn,args", CASES, ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(one_chip, name, fn, args, vmapped):
    lead = (LANES,) if vmapped else ()
    avals = [jax.ShapeDtypeStruct(lead + shape, dtype, sharding=one_chip)
             for shape, dtype in args]
    f = jax.vmap(fn) if vmapped else fn
    compiled = jax.jit(f).lower(*avals).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
