"""The harness at a tiny size on the CPU: resolution by name, the result
line, and ``correct`` coming out false when the served path is broken."""
import copy
import json
import shutil
import time

import numpy as np
import pytest


from repro.serve import cluster_engine

from bench import harness, loadgen
from bench.graphs import csr, rmat

SPEC = harness.load_spec()
TINY_SCALE = 10
CELL = "rmat24.lookups"


def test_every_workload_resolves_to_its_files():
    for w in SPEC["workloads"]:
        cell = harness.resolve(SPEC, w["name"])
        assert (harness.ROOT / "bench" / "graphs" /
                f"{cell.config['generator']}.py").exists()
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
        for m in cell.per_layer:
            assert callable(harness.metric_reader(m["name"]))


def test_a_new_cell_needs_only_new_files_and_an_entry(tmp_path):
    shutil.copytree(harness.ROOT / "bench", tmp_path / "bench")
    spec = copy.deepcopy(SPEC)
    (tmp_path / "bench" / "traffic" / "new_mix.json").write_text(
        (harness.ROOT / "bench" / "traffic" /
         "lookups.rmat24.json").read_text())
    (tmp_path / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 1.0\n")
    spec["workloads"].append(dict(spec["workloads"][0], name="rmat24.new",
                                  traffic="new_mix"))
    spec["per_layer"].append(dict(spec["per_layer"][0], name="new_metric",
                                  workloads=["rmat24.new"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.resolve(harness.load_spec(tmp_path), "rmat24.new",
                           tmp_path)
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    assert harness.metric_reader("new_metric", tmp_path)(None) == 1.0


def tiny_cell(seed=3):
    """The cell cut to scale 10 and a short, light load."""
    cell = harness.resolve(SPEC, CELL)
    cfg = dict(cell.config, scale=TINY_SCALE)
    src, dst, n = rmat.generate(harness.graph_key(harness.graph_seed(cell)),
                                cfg)
    cfg["undirected_edges"] = int(csr.count_unique(src, dst, n)) - 5
    traffic = dict(cell.traffic, outstanding=4, population=300,
                   warm_buckets={"pr_nibble": 0, "hk_pr": 0},
                   warm_requests=2, check_sample=6)
    traffic["seeds"] = dict(traffic["seeds"], max_degree=10**6)
    return harness.Cell(cell.name, cell.chips, cfg, traffic,
                        cell.end_to_end, cell.per_layer)


def run_tiny(trace=False, seed=3, seconds=1.5):
    return harness.run_cell(tiny_cell(seed=seed), seed, seconds, trace,
                            time.monotonic())


@pytest.mark.parametrize("trace", [False, True])
def test_result_line_has_the_contract_keys(trace):
    out = run_tiny(trace)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[:5] == keys
    assert list(out)[-1] == "checks"
    assert set(out) - set(keys) - {"checks", "resident_bytes"} <= (
        {"breakdown"} if trace else set())
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 4
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    want = SPEC["per_layer"] if trace else SPEC["end_to_end"]
    want = {m["name"] for m in want if CELL in m.get("workloads", [CELL])}
    if trace:
        assert {"busy_s", "window_s"} <= set(out["device"])
        # no device plane, and so no device operations, on the CPU
        want -= {m["name"] for m in SPEC["per_layer"]
                 if m["source"] == "device_trace"}
        assert want <= set(out["metrics"])
    else:
        assert set(out["metrics"]) == want
        assert out["metrics"]["seeds_per_s"]["value"] > 0
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"}
    json.dumps(out)


def test_an_altered_answer_is_not_correct(monkeypatch):
    finalize = cluster_engine._Pool._finalize

    def altered(self, i, req, overflowed):
        res = finalize(self, i, req, overflowed)
        res.cluster = res.cluster[:-1] if res.size > 1 else res.cluster + 1
        return res
    monkeypatch.setattr(cluster_engine._Pool, "_finalize", altered)
    out = run_tiny()
    assert out["correct"] is False
    assert out["checks"]["answer_gap"]["value"] == 1.0


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    monkeypatch.setattr(harness, "GRACE_S", 2.0)
    warm = harness._warm

    def warm_then_break(srv, eng, traffic, stream):
        warm(srv, eng, traffic, stream)
        monkeypatch.setattr(cluster_engine._Pool, "step", lambda self: None)
    monkeypatch.setattr(harness, "_warm", warm_then_break)
    out = run_tiny()
    assert out["correct"] is False
    assert out["checks"]["unanswered"]["value"] > 0
    assert out["metrics"]["seeds_per_s"]["value"] == 0


def test_a_population_seed_fixes_the_work_and_the_seed_deals_it():
    traffic = harness.resolve(SPEC, CELL).traffic
    deg = np.arange(5000) % 70
    a = loadgen.make_stream(traffic, deg, 1)
    b = loadgen.make_stream(traffic, deg, 2)
    block = traffic["outstanding"]
    assert a.window != b.window
    for i in range(0, len(a.window), block):
        assert sorted(map(repr, a.window[i:i + block])) == \
            sorted(map(repr, b.window[i:i + block]))
    assert a.warm == b.warm
