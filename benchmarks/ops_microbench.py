"""Op-layer micro-benchmarks: the three hot primitives, per backend.

Times each ``repro.core.ops`` op under ``backend="xla"`` and
``backend="pallas"`` on representative driver shapes (scatter batches the
size of an edge workspace, merges the size of a SparseVec round, scans the
size of a sweep grid).  On CPU the Pallas backend runs in interpret mode —
wall time there measures the *dispatch pipeline*, not the kernel (the TPU
story lives in the roofline docs) — but every row doubles as a smoke-level
correctness probe: each pallas timing asserts bitwise agreement with the
xla reference before it is reported, so the CI ``--smoke`` gate exercises
the full kernel path on every run.
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import ops
from .common import emit, timeit


def _interp_tag() -> str:
    """";interpret=true" on non-TPU hosts, where Pallas runs in interpret
    mode: those 10–18× pallas-vs-xla slowdowns measure the interpreter, not
    hardware, and the artifact must say so."""
    return ";interpret=true" if jax.default_backend() != "tpu" else ""


def _assert_bitwise(a, b, what):
    an = [np.atleast_1d(np.asarray(t))
          for t in (a if isinstance(a, tuple) else (a,))]
    bn = [np.atleast_1d(np.asarray(t))
          for t in (b if isinstance(b, tuple) else (b,))]
    for x, y in zip(an, bn):
        if not np.array_equal(x.view(np.uint8), y.view(np.uint8)):
            raise AssertionError(f"{what}: pallas != xla")


def run(smoke: bool = False):
    rng = np.random.default_rng(0)
    n = 1 << 12 if smoke else 1 << 16
    m = 1 << 13 if smoke else 1 << 18

    # scatter_add — the fetchAdd batch of one push round
    vec = jnp.asarray(rng.random(n), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, m), jnp.int32)
    vals = jnp.asarray(rng.random(m), jnp.float32)
    valid = jnp.asarray(rng.random(m) < 0.9)
    outs = {}
    for backend in ("xla", "pallas"):
        us, outs[backend] = timeit(ops.scatter_add, vec, idx, vals, valid,
                                   backend=backend, prime=not smoke)
        tag = _interp_tag() if backend == "pallas" else ""
        emit(f"ops/scatter_add_{backend}", us, f"n={n};m={m}{tag}")
    _assert_bitwise(outs["xla"], outs["pallas"], "scatter_add")

    # segment_merge — one sv_merge_add of a sparse round
    cap = 1 << 10 if smoke else 1 << 12
    ids = jnp.asarray(rng.integers(0, n + 1, cap + m // 4), jnp.int32)
    mvals = jnp.asarray(rng.random(cap + m // 4), jnp.float32)
    for backend in ("xla", "pallas"):
        us, outs[backend] = timeit(ops.segment_merge, ids, mvals, n, cap,
                                   backend=backend, prime=not smoke)
        tag = _interp_tag() if backend == "pallas" else ""
        emit(f"ops/segment_merge_{backend}", us,
             f"stream={int(ids.shape[0])};cap={cap}{tag}")
    _assert_bitwise(outs["xla"], outs["pallas"], "segment_merge")

    # prefix_sum — the sweep's int32 difference-array scan
    x = jnp.asarray(rng.integers(-3, 4, m), jnp.int32)
    for backend in ("xla", "pallas"):
        us, outs[backend] = timeit(ops.prefix_sum, x, backend=backend,
                                   prime=not smoke)
        tag = _interp_tag() if backend == "pallas" else ""
        emit(f"ops/prefix_sum_i32_{backend}", us, f"n={m}{tag}")
    _assert_bitwise(outs["xla"], outs["pallas"], "prefix_sum")

    # artifact-level flag, mirrored per-row above: BENCH_ops.json numbers
    # from an interpret-mode host must never be read as TPU numbers
    return dict(default_backend=jax.default_backend(),
                interpret=jax.default_backend() != "tpu")


if __name__ == "__main__":
    run()
