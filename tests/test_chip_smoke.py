"""chip_smoke.py rehearsed on the CPU at a tiny size: its phase functions
run end to end (Pallas in interpret mode, small engine capacities so the
ladder is climbed), and the script refuses to run without a TPU."""
import os
import sys

import jax
import pytest

from conftest import run_subprocess_json

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402

# small capacities: PR-Nibble resolves to sparse lanes at n = 2048 and
# requests climb the capacity ladder
SMALL = dict(cap_f=64, cap_e=1024, cap_v=64, cap_n=64, sweep_cap_e=2048)


def test_exits_nonzero_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert cs.main([]) != 0
    out, err = capsys.readouterr()
    assert out == ""                      # no graph built, no result line
    assert "no TPU" in err


@pytest.fixture(scope="module")
def served():
    graph = cs.build_graph(11, 0)
    eng, reqs, results = cs.main_phase(graph, 12, 0, engine_kw=SMALL)
    return graph, eng, reqs, results


def test_main_phase_serves_and_checks(served):
    graph, eng, reqs, results = served
    assert len(results) == 12
    assert eng.stats["promotions"] >= 1
    assert {r.backend for r in results} == {"dense", "sparse"}
    cs.seq_phase(graph, eng, reqs, results)


def test_pallas_phases(served):
    graph, eng, reqs, results = served
    found = cs.pallas_ops_phase(graph, eng, 0)
    # interpret mode folds in XLA's order: every op but the f32 scan is exact
    assert {k for k, same in found.items() if not same} <= {"prefix_sum_f32"}
    assert cs.pallas_serve_phase(graph, eng, reqs, results, count=2) == 2


def test_dist_phase_on_four_cpu_devices():
    out = run_subprocess_json(f"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {ROOT!r})
import jax
import chip_smoke as cs
cs.dist_phase(cs.build_graph(11, 0), jax.devices()[:4], 12, 0,
              engine_kw={SMALL!r})
print("RESULT:" + json.dumps(dict(ok=True)))
""")
    assert out["ok"]
