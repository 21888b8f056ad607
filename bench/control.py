#!/usr/bin/env python3
"""The control of ``bench/check.py``: the reference in bfloat16, put in the
program's place, has to come out as not correct.

    python3 bench/control.py --workload rmat24.lookups --seeds 1,2,3

For each seed, builds the cell's graph as a run does, draws the run's
requests, and answers a sample of the size a run checks, taken from the
first ``ANSWERED`` requests (about half of what a window sends), with the
plain reference in bfloat16 (the precision below the configuration's
float32).
Those answers go through the run's own :func:`bench.check.judge`.  Prints
one JSON line per seed with ``correct`` and each number beside its limit;
the smallest reading of each over the seeds is the upper reading its limit
is set under.
"""
import argparse
import json
import os
import sys
import types

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [_ROOT, os.path.join(_ROOT, "src")]

ANSWERED = 48


def control_verdict(host, traffic: dict, requests: list, seed: int,
                    dtype: str = "bfloat16"):
    """``check.judge`` of ``requests`` answered by the reference computed in
    ``dtype``, in the program's place."""
    import numpy as np
    from bench import check
    from bench.reference import Workspace
    low = Workspace(host, dtype)
    count = min(traffic["check_sample"], len(requests))
    pick = np.random.default_rng([seed, 0xC4EC]).choice(
        len(requests), count, replace=False)
    records = [types.SimpleNamespace(
        refused=False, result=check.served(requests[i],
                                           low.answer(requests[i]), host))
        for i in sorted(pick)]
    return check.judge(records, host, traffic, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    from bench import harness, loadgen
    from bench.reference import HostGraph
    cell = harness.resolve(harness.load_spec(), args.workload)
    host = HostGraph.of(harness.build_graph(cell))
    for seed in (int(s) for s in args.seeds.split(",")):
        stream = loadgen.make_stream(cell.traffic, host.deg, seed)
        verdict = control_verdict(host, cell.traffic,
                                  stream.window[:ANSWERED], seed)
        print(json.dumps(dict(workload=args.workload, seed=seed,
                              correct=verdict.correct,
                              checks=verdict.checks)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
