"""The trace reduction and its metric readers on a small trace recorded on
one v5e chip: 20 interactive-mix requests served on an R-MAT graph of
scale 14 (16,384 vertices), three pool ticks, with the tracer's device
annotations on."""
import gzip
import types

import pytest

from bench import harness, xplane

DATA = harness.ROOT / "bench" / "tests" / "data" / "trace_small.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    path.write_bytes(gzip.decompress(DATA.read_bytes()))
    return xplane.reduce_file(str(path))


def test_window_busy_and_gaps_add_up(reduced):
    assert reduced.window_s == pytest.approx(2.542415737, abs=1e-9)
    assert reduced.busy_s == pytest.approx(2.411066541, abs=1e-9)
    idle = sum(s for _, s in reduced.gaps)
    assert idle == pytest.approx(reduced.window_s - reduced.busy_s,
                                 abs=1e-9)


def test_executables_and_ticks(reduced):
    assert reduced.module_time("jit_step") == pytest.approx(
        (2.040594622, 3), abs=1e-9)
    assert reduced.modules["jit_sweep"][1] == 20
    # self times never count a nested operation twice
    assert sum(reduced.ops_s.values()) <= reduced.busy_s + 1e-9


def test_breakdown_names_ops_and_gaps(reduced):
    b = reduced.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["device_ops"][0] == ["jit_step/%fusion.103",
                                  pytest.approx(0.783545938, abs=1e-9)]
    top_gap = b["idle_gaps"][0][0]
    assert top_gap.startswith("tick:pr_nibble:dense:xla:(True, 1.0):b0 > ")
    assert " after jit_" in top_gap


def _run(reduced):
    return types.SimpleNamespace(profile=reduced, records=[], compiles=0)


def test_metric_readers(reduced):
    run = _run(reduced)
    idle = harness.metric_reader("device_idle_pct")(run)
    assert idle == pytest.approx(100 * (1 - 2.411066541 / 2.542415737))
    step = harness.metric_reader("step_device_ms")(run)
    assert step == pytest.approx(2040.594622 / 3, abs=1e-6)


def test_readers_return_nothing_without_a_trace():
    run = types.SimpleNamespace(profile=None, records=[], compiles=0)
    for name in ("device_idle_pct", "step_device_ms", "queue_ms",
                 "resident_ms", "sweep_ms"):
        assert harness.metric_reader(name)(run) is None
