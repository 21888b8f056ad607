"""Sharded batched engine benchmark — exchange volume vs boundary mass.

The distributed engine's performance claim is the locality argument
(Spielman–Teng via PAPERS.md): per round, the bucketed all_to_all moves one
contribution slot per *frontier* edge that crosses a shard boundary, so the
exchange volume is bounded by the partition's boundary mass — never O(n).
This benchmark measures exactly that ratio on a host mesh: it runs the
batched dist driver (`repro.core.batched_dist.batched_dist_pr_nibble`) over
a seed batch and reports

  * ``exchange_per_round`` — cross-shard contribution slots routed per push
    round (averaged over all lanes' rounds), vs
  * ``boundary_edges`` — directed edges crossing shard boundaries (the
    partition's boundary mass, the locality bound), and their ratio.

Because the main benchmark process runs single-device, the measurement runs
in a subprocess on 8 virtual CPU devices
(``JAX_PLATFORMS=cpu``, ``--xla_force_host_platform_device_count=8``; the
same recipe as tests/test_distributed.py), tiny enough for the CI smoke
gate.  The child is pinned to the CPU because the parent may hold the
chip; its rows say ``platform=cpu``.
Emits the usual CSV rows; the returned dict lands in
``BENCH_dist_batched.json``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import emit

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import jax
import numpy as np
from repro.launch.mesh import make_host_mesh
from repro.graphs import sbm, rand_local, GraphHandle
from repro.core.batched_dist import batched_dist_pr_nibble

cfg = json.loads(os.environ["DIST_BENCH_CFG"])
mesh = make_host_mesh()
if cfg["graph"] == "sbm":
    g = sbm(k=8, size=100, p_in=0.15, p_out=0.002, seed=1)
else:
    g = rand_local(20_000, degree=5, seed=3)
h = GraphHandle.shard(g, mesh)
pg = h.partitioned()

# boundary mass: directed edges whose endpoints live on different shards
deg = np.asarray(g.deg)
src = np.repeat(np.arange(g.n), deg)
dst = np.asarray(g.indices)[: src.shape[0]]
boundary = int(((src // pg.rows_per) != (dst // pg.rows_per)).sum())

rng = np.random.default_rng(0)
seeds = rng.choice(np.flatnonzero(deg > 0), size=cfg["B"]).astype(np.int32)

t0 = time.perf_counter()
out = batched_dist_pr_nibble(h, seeds, eps=cfg["eps"], alpha=cfg["alpha"],
                             cap_f=cfg["cap_f"], cap_e=cfg["cap_e"],
                             cap_x=cfg["cap_x"])
wall_us = (time.perf_counter() - t0) * 1e6

rounds = int(out.iterations.sum())
exchanged = int(out.exchanged.sum())
res = dict(
    platform=jax.devices()[0].platform, graph=cfg["graph"], n=g.n, m=g.m, num_shards=pg.num_shards, B=cfg["B"],
    wall_us=wall_us, rounds_total=rounds, exchange_total=exchanged,
    exchange_per_round=exchanged / max(rounds, 1),
    boundary_edges=boundary,
    exchange_over_boundary=(exchanged / max(rounds, 1)) / max(boundary, 1),
    buckets=[list(b) for b in out.buckets],
    overflow_lanes=int(out.overflow.sum()),
)
print("RESULT:" + json.dumps(res))
"""


def _src_path() -> str:
    import repro
    return os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))


def run(smoke: bool = False) -> dict:
    cfg = dict(graph="sbm" if smoke else "randLocal",
               B=4 if smoke else 16, eps=1e-5 if smoke else 1e-6,
               alpha=0.05 if smoke else 0.01,
               cap_f=256 if smoke else 1 << 11,
               cap_e=1 << 13 if smoke else 1 << 15,
               cap_x=1 << 11 if smoke else 1 << 13)
    env = dict(os.environ)
    env["PYTHONPATH"] = _src_path() + os.pathsep + env.get("PYTHONPATH", "")
    env["DIST_BENCH_CFG"] = json.dumps(cfg)
    env.pop("XLA_FLAGS", None)   # the child sets its own device count
    env["JAX_PLATFORMS"] = "cpu"  # never the chip: the parent may hold it
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(
            f"dist_batched subprocess failed:\n{proc.stderr[-3000:]}")
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][0]
    res = json.loads(line[len("RESULT:"):])
    emit(f"dist_batched/{res['graph']}/B={res['B']}_D={res['num_shards']}",
         res["wall_us"],
         f"platform={res['platform']};exch_per_round={res['exchange_per_round']:.1f};"
         f"boundary_edges={res['boundary_edges']};"
         f"exch_over_boundary={res['exchange_over_boundary']:.3f};"
         f"rounds={res['rounds_total']}")
    return res


if __name__ == "__main__":
    print(json.dumps(run(smoke=True), indent=2))
