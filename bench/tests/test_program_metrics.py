"""The readers of the program's own records (``bench/program.py``) on a
synthetic run: a real tracer holding hand-made tick spans, and a reduced
trace with a fake op → phase table."""
import types

import pytest

from bench import harness, xplane
from repro.serve import Tracer, aot

T0, SECONDS = 100.0, 10.0
NAMES = ("hk_tick_ms", "prn_tick_ms", "edge_slot_use_pct",
         "step_expand_ms", "step_scatter_ms", "step_frontier_ms")


def _tick(tracer, pool, t0, ms, **work):
    sid = tracer.begin("tick", cat="pool", t0=t0, pool=pool)
    tracer.end(sid, t1=t0 + ms / 1e3, **work)


def _run(tracer=None, profile=None):
    trace = None if tracer is None else types.SimpleNamespace(tracer=tracer)
    return types.SimpleNamespace(
        records=[types.SimpleNamespace(trace=trace)], t0=T0,
        seconds=SECONDS, profile=profile, compiles=0)


@pytest.fixture
def tracer():
    tr = Tracer()
    hk = "hk_pr:dense:xla:(10, 5.0):b0"
    prn = "pr_nibble:sparse:xla:(True, 1.0):b1"
    _tick(tr, hk, T0 - 1.0, 999.0, edges=9, edge_slots=9)   # before the window
    _tick(tr, hk, T0 + 1.0, 600.0, edges=30, edge_slots=100)
    _tick(tr, hk, T0 + 2.0, 800.0, edges=10, edge_slots=100)
    _tick(tr, prn, T0 + 3.0, 200.0, edges=40, edge_slots=200)
    _tick(tr, prn, T0 + SECONDS, 999.0, edges=9, edge_slots=9)  # after it
    tr.begin("tick", cat="pool", t0=T0 + 4.0, pool=prn)           # still open
    return tr


def test_tick_means_by_method(tracer):
    run = _run(tracer)
    assert harness.metric_reader("hk_tick_ms")(run) == pytest.approx(700.0)
    assert harness.metric_reader("prn_tick_ms")(run) == pytest.approx(200.0)


def test_edge_slot_use_over_the_window(tracer):
    use = harness.metric_reader("edge_slot_use_pct")(_run(tracer))
    assert use == pytest.approx(100.0 * (30 + 10 + 40) / (100 + 100 + 200))


def test_edge_slot_use_needs_the_counters():
    tr = Tracer()
    _tick(tr, "hk_pr:dense:xla:(10, 5.0):b0", T0 + 1.0, 600.0)
    assert harness.metric_reader("edge_slot_use_pct")(_run(tr)) is None
    assert harness.metric_reader("hk_tick_ms")(_run(tr)) == pytest.approx(
        600.0)


def _profile():
    """Two step executables (3 s over 4 runs: 750 ms a run) and a sweep."""
    ops = {"jit_step_hk_pr_dense_b0/%fusion.1": 0.5,      # expand
           "jit_step_hk_pr_dense_b0/%fusion.2": 1.0,      # scatter
           "jit_step_hk_pr_dense_b0/%while.3": 0.5,       # no phase
           "jit_step_pr_nibble_sparse_b0/%fusion.1": 0.25,  # frontier
           "jit_step_pr_nibble_sparse_b0/%sort.9": 0.75,  # not in the table
           "jit_sweep_hk_pr_dense_b0/%fusion.1": 7.0}     # not a step
    modules = {"jit_step_hk_pr_dense_b0": [2.0, 2],
               "jit_step_pr_nibble_sparse_b0": [1.0, 2],
               "jit_sweep_hk_pr_dense_b0": [7.0, 9]}
    return xplane.Reduced(10.0, 10.0, ops, modules, [])


TABLE = {"jit_step_hk_pr_dense_b0": {"%fusion.1": "expand",
                                     "%fusion.2": "scatter",
                                     "%while.3": None},
         "jit_step_pr_nibble_sparse_b0": {"%fusion.1": "frontier"},
         "jit_sweep_hk_pr_dense_b0": {"%fusion.1": "expand"}}


def test_step_phases_split_step_device_ms(monkeypatch):
    monkeypatch.setattr(aot, "op_scopes", lambda: TABLE)
    run = _run(profile=_profile())
    step_ms = harness.metric_reader("step_device_ms")(run)
    assert step_ms == pytest.approx(750.0)
    got = {ph: harness.metric_reader(f"step_{ph}_ms")(run)
           for ph in ("expand", "scatter", "frontier")}
    assert got == pytest.approx({"expand": 750.0 * 0.5 / 3.0,
                                 "scatter": 750.0 * 1.0 / 3.0,
                                 "frontier": 750.0 * 0.25 / 3.0})


def test_step_phases_without_the_table(monkeypatch):
    monkeypatch.delattr(aot, "op_scopes")
    run = _run(profile=_profile())
    assert harness.metric_reader("step_expand_ms")(run) is None


def test_readers_return_nothing_without_a_trace():
    for name in NAMES:
        assert harness.metric_reader(name)(_run()) is None
