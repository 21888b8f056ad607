"""The control — the reference in bfloat16 in the program's place — comes
out as not correct by the run's own judge, and the reference passes
against itself."""
import pytest

from bench import harness, loadgen
from bench.control import control_verdict
from bench.graphs import csr, rmat
from bench.reference import HostGraph

TRAFFIC = harness.resolve(harness.load_spec(), "rmat24.lookups").traffic


@pytest.fixture(scope="module")
def graph():
    cfg = dict(scale=12, edge_factor=16, a=0.57, b=0.19, c=0.19)
    src, dst, n = rmat.generate(harness.graph_key(7), cfg)
    g = csr.build_csr(src, dst, n, int(csr.count_unique(src, dst, n)))
    return HostGraph.of(g)


@pytest.mark.parametrize("seed", [7, 2**33 + 1])
def test_control_is_not_correct(graph, seed):
    traffic = dict(TRAFFIC, population=48, check_sample=24)
    stream = loadgen.make_stream(traffic, graph.deg, seed)
    verdict = control_verdict(graph, traffic, stream.window, seed)
    assert verdict.correct is False, verdict.checks
    assert verdict.failed > 0


def test_reference_passes_against_itself(graph):
    traffic = dict(TRAFFIC, population=24, check_sample=12)
    stream = loadgen.make_stream(traffic, graph.deg, 7)
    verdict = control_verdict(graph, traffic, stream.window, 7,
                              dtype="float64")
    assert verdict.correct is True, verdict.checks
    checks = {k: c["value"] for k, c in verdict.checks.items()}
    assert max(checks[k] for k in ("phi_gap", "support_gap",
                                   "pushes_gap", "unanswered")) == 0
    assert checks["answer_gap"] < 1e-12
