"""What the tick tells about the chip: per-pool executable names, the
round phases of their ops, the edge work carried by the one status readback,
and named compiles (serve/aot.py, serve/cluster_engine.py,
serve/tracing.py).  None of it may change an answer (guarantee #8)."""
import gc
import re
import weakref
from collections import defaultdict

import jax
import numpy as np
import pytest

from repro.core.hk_pr import hk_pr
from repro.core.pr_nibble import pr_nibble
from repro.core.pr_nibble_sparse import (pr_nibble_sparse_alive,
                                         pr_nibble_sparse_init,
                                         pr_nibble_sparse_round)
from repro.serve import ClusterRequest, LocalClusterEngine, Tracer
from repro.serve.aot import PHASES, op_scopes

CAPS = dict(cap_f=1 << 11, cap_e=1 << 15, cap_n=1 << 10, sweep_cap_e=1 << 15,
            cap_v=1 << 10)
PROTOS = [ClusterRequest(seed=0, method="hk_pr", N=10, t=5.0),
          ClusterRequest(seed=0, backend="sparse"),
          ClusterRequest(seed=0, backend="dense", optimized=True),
          ClusterRequest(seed=0, backend="dense", optimized=False)]


def _module(compiled) -> str:
    return re.match(r"HloModule ([\w.\-]+)", compiled.as_text()).group(1)


@pytest.fixture(scope="module")
def warm_engine(sbm_graph):
    eng = LocalClusterEngine(sbm_graph, batch_slots=4, **CAPS)
    eng.warmup(PROTOS, max_bucket=1)
    return eng


def test_every_pool_names_its_executables(warm_engine):
    names, steps = [], []
    for key, ex in warm_engine._exec_cache._entries.items():
        for kernel, compiled in ex._asdict().items():
            name = _module(compiled)
            names.append(name)
            if kernel == "step":
                steps.append(name)
    assert len(names) == len(set(names)) == 5 * 2 * len(PROTOS)
    assert sorted(n for n in names if n.startswith("jit_step")) == \
        sorted(steps)
    for name in ("jit_step_hk_pr_dense_b0", "jit_step_pr_nibble_sparse_b1",
                 "jit_sweep_hk_pr_dense_b1", "jit_status_pr_nibble_dense_b0"):
        assert name in names
    assert all(re.fullmatch(r"jit_\w+", n) for n in names)


def test_step_ops_fall_under_every_phase(warm_engine):
    table = op_scopes()
    for ex in warm_engine._exec_cache._entries.values():
        phases = set(table[_module(ex.step)].values())
        assert set(PHASES) <= phases, _module(ex.step)


def _pushed_degrees(graph, req) -> int:
    """Σ deg(v) over every push of the sparse single-seed rounds."""
    n, deg = graph.n, np.asarray(graph.deg)
    rnd = jax.jit(lambda s: pr_nibble_sparse_round(
        graph, s, req.eps, req.alpha, req.optimized, CAPS["cap_e"]))
    s = pr_nibble_sparse_init(req.seed, n, min(CAPS["cap_f"], n + 1),
                              min(CAPS["cap_v"], n + 1))
    total = 0
    while bool(pr_nibble_sparse_alive(s)):
        ids = np.asarray(s.frontier.ids)[:int(s.frontier.count)]
        total += int(deg[ids].sum())
        s = rnd(s)
    return total


def _reference_edges(graph, req) -> int:
    cap_f = min(CAPS["cap_f"], graph.n + 1)
    if req.method == "hk_pr":
        return int(hk_pr(graph, req.seed, req.N, req.eps, req.t, cap_f,
                         CAPS["cap_e"]).edge_work)
    if req.backend == "dense":
        return int(pr_nibble(graph, req.seed, req.eps, req.alpha,
                             req.optimized, cap_f, CAPS["cap_e"]).edge_work)
    return _pushed_degrees(graph, req)


@pytest.mark.parametrize("kind", [
    dict(method="hk_pr", N=10, t=5.0, eps=1e-4),
    dict(method="pr_nibble", backend="dense", alpha=0.05, eps=1e-4),
    dict(method="pr_nibble", backend="sparse", alpha=0.05, eps=1e-4)],
    ids=["hk_pr_dense", "pr_nibble_dense", "pr_nibble_sparse"])
def test_tick_edges_add_up_to_the_single_seed_edge_work(sbm_graph, kind):
    seeds = [3, 150, 420, 612, 777]
    reqs = [ClusterRequest(seed=s, **kind) for s in seeds]
    tracer = Tracer()
    eng = LocalClusterEngine(sbm_graph, batch_slots=2, tracer=tracer,
                             rounds_per_step=4, **CAPS)
    eng.run(reqs)
    assert eng.stats["promotions"] == 0
    spans = tracer.spans()
    seed_of = {s.rid: s.attrs["seed"] for s in spans if s.name == "request"}
    edges = defaultdict(int)
    for s in spans:
        if s.name == "lane_obs":
            edges[seed_of[s.rid]] += s.attrs["edges"]
    assert edges == {r.seed: _reference_edges(sbm_graph, r) for r in reqs}
    ticks = [s for s in spans if s.name == "tick"]
    assert sum(s.attrs["edges"] for s in ticks) == \
        eng.stats["edges_touched"] == sum(edges.values())
    assert sum(s.attrs["edge_slots"] for s in ticks) == \
        eng.stats["edge_slots"]
    for s in ticks:
        assert s.attrs["edge_slots"] == \
            2 * CAPS["cap_e"] * s.attrs["rounds"]
        assert 0 < s.attrs["edges"] <= s.attrs["edge_slots"]


def test_traced_stream_bit_identical_one_sync_per_tick(sbm_graph):
    rng = np.random.default_rng(5)
    seeds = rng.choice(np.flatnonzero(np.asarray(sbm_graph.deg) > 0), 9)
    reqs = [ClusterRequest(seed=int(s), eps=1e-4, alpha=0.05,
                           method=("hk_pr" if i % 3 == 0 else "pr_nibble"),
                           backend=("sparse" if i % 3 == 1 else None))
            for i, s in enumerate(seeds)]
    runs = {}
    for traced in (True, False):
        tracer = Tracer() if traced else None
        eng = LocalClusterEngine(sbm_graph, batch_slots=2, tracer=tracer,
                                 rounds_per_step=4, **CAPS)
        for r in reqs:
            eng.submit(r)
        ticks_with_work = 0
        while True:     # tick_pool returns None for a pool with no work
            done = [eng.tick_pool(k) for k in list(eng.pools)]
            if all(d is None for d in done):
                break
            ticks_with_work += sum(d is not None for d in done)
        assert eng.stats["status_syncs"] == ticks_with_work
        runs[traced] = ([eng.result(t) for t in range(len(reqs))],
                        {k: eng.stats[k] for k in
                         ("edges_touched", "edge_slots", "status_syncs")})
        if traced:
            spans = tracer.spans()
            names = {s.name for s in spans}
            assert {"status_wait", "sweep_dispatch", "refill",
                    "step"} <= names
            ticks = {s.sid for s in spans if s.name == "tick"}
            assert len(ticks) == ticks_with_work
            for s in spans:
                if s.name in ("status_wait", "sweep_dispatch"):
                    assert s.parent in ticks
    (traced, stats_t), (plain, stats_p) = runs[True], runs[False]
    assert stats_t == stats_p
    for a, b in zip(traced, plain):
        assert a.conductance == b.conductance and a.size == b.size
        assert a.pushes == b.pushes and a.iterations == b.iterations
        assert np.array_equal(a.cluster, b.cluster)


def test_compile_after_warmup_is_named(sbm_graph):
    tracer = Tracer()
    eng = LocalClusterEngine(sbm_graph, batch_slots=2, tracer=tracer, **CAPS)
    eng.warmup([ClusterRequest(seed=0, backend="dense")], max_bucket=0)
    before = eng.stats["backend_compiles"]
    assert before >= 5                 # the warmed pool's five executables
    with tracer.span("tick", cat="pool") as sid, tracer.scope(parent=sid):
        jax.jit(lambda x: x * 3 + 1)(np.arange(7.0))
    compiles = [s for s in tracer.spans() if s.name == "compile"]
    assert compiles[-1].attrs["fun_name"] == "jit(<lambda>)"
    assert compiles[-1].parent == sid and compiles[-1].attrs["seconds"] >= 0
    assert eng.stats["backend_compiles"] == before + 1
    # an untraced engine counts too; a dropped engine stops listening
    plain = LocalClusterEngine(sbm_graph, batch_slots=2, **CAPS)
    jax.jit(lambda x: x - 2)(np.arange(5.0))
    assert plain.stats["backend_compiles"] == 1
    ref = weakref.ref(plain)
    del plain
    gc.collect()
    assert ref() is None


def test_fusions_without_metadata_take_their_callee_phase():
    """The TPU compiler leaves some fusions without ``op_name`` metadata;
    those take the phase of the computation they call (its root's, else
    the most common one inside it)."""
    from repro.serve import aot
    text = "\n".join([
        "HloModule jit_step_synthetic_b0, is_scheduled=true",
        "",
        "%fused_computation.1 (param_0: s32[8]) -> s32[8] {",
        '  %a.1 = s32[8] add(%param_0, %param_0), metadata={op_name='
        '"jit(step)/while/body/expand/add"}',
        '  ROOT %m.1 = s32[8] multiply(%a.1, %a.1), metadata={op_name='
        '"jit(step)/while/body/scatter/mul"}',
        "}",
        "",
        "%fused_computation.2 (param_0: s32[8]) -> s32[8] {",
        '  %a.2 = s32[8] add(%param_0, %param_0), metadata={op_name='
        '"jit(step)/while/body/frontier/add"}',
        "  ROOT %s.2 = s32[8] select(%a.2, %a.2, %a.2)",
        "}",
        "",
        "ENTRY %main.3 (p: s32[8]) -> s32[8] {",
        "  %p = s32[8] parameter(0)",
        "  %fusion.1 = s32[8] fusion(%p), kind=kLoop, "
        "calls=%fused_computation.1",
        "  %fusion.2 = s32[8] fusion(%fusion.1), kind=kLoop, "
        "calls=%fused_computation.2",
        "  ROOT %fusion.3 = s32[8] fusion(%fusion.2), kind=kLoop, "
        'calls=%fused_computation.2, metadata={op_name="jit(step)/while"}',
        "}"])
    aot._record_scopes(text)
    table = op_scopes()["jit_step_synthetic_b0"]
    assert table["%fusion.1"] == "scatter"     # the callee's root
    assert table["%fusion.2"] == "frontier"    # the callee's only phase
    assert table["%fusion.3"] is None          # its own metadata decides
    assert table["%p"] is None
