"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  --fast trims graph sizes (default);
--full runs the complete suite; --smoke runs each benchmark's smallest
config (the CI gate — must finish in a couple of minutes on one CPU core).

Every requested suite runs even if an earlier one fails; failures are
reported as ``<suite>/ERROR`` rows and the process exits nonzero at the end
(the CI gate must fail loudly, not skip silently).

Artifacts: EVERY suite writes a ``BENCH_<suite>.json`` next to the CWD,
containing the CSV rows it emitted (captured via ``common.emit``) plus —
when its ``run()`` returns a dict — that dict merged in (the serving
suite's latency summary, the dist suite's exchange-volume accounting).
CI uploads all of them, so the ops/batched/dist perf trajectories
accumulate across runs alongside the serving latencies.
"""
import argparse
import json
import sys
import time
import traceback

# Versioned artifact header (satellite of the tracing PR): accumulated
# BENCH_<suite>.json files must be comparable across PRs without guessing
# their vintage.  Bump when the artifact shape changes.
BENCH_SCHEMA = "repro.bench/v1"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="smallest config per benchmark; used by CI")
    ap.add_argument("--only", default=None,
                    help="comma list: table1,table3,fig2,fig6,fig9,fig10,"
                         "batched,sparse_batched,ops,serve,"
                         "dist_batched")
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    from . import (table1_pushes, table3_runtimes, fig2_opt_rule, fig6_params,
                   fig9_sweep_scaling, fig10_ncp, batched_bench,
                   sparse_batched_bench, ops_microbench, serve_bench,
                   dist_batched_bench)
    from .common import drain_rows
    smoke = args.smoke
    suites = {
        "table1": lambda: table1_pushes.run(smoke=smoke),
        "table3": lambda: table3_runtimes.run(fast=not args.full, smoke=smoke),
        "fig2": lambda: fig2_opt_rule.run(smoke=smoke),
        "fig6": lambda: fig6_params.run(smoke=smoke),
        "fig9": lambda: fig9_sweep_scaling.run(smoke=smoke),
        "fig10": lambda: fig10_ncp.run(smoke=smoke),
        "batched": lambda: batched_bench.run(smoke=smoke),
        "sparse_batched": lambda: sparse_batched_bench.run(smoke=smoke),
        "ops": lambda: ops_microbench.run(smoke=smoke),
        "serve": lambda: serve_bench.run(smoke=smoke),
        "dist_batched": lambda: dist_batched_bench.run(smoke=smoke),
    }
    only = args.only.split(",") if args.only else list(suites)
    print("name,us_per_call,derived")
    failures = []
    for k in only:
        drain_rows()   # rows are per-suite; discard anything stale
        try:
            ret = suites[k]()
        except Exception as e:
            print(f"{k}/ERROR,0,{type(e).__name__}:{str(e)[:120]}",
                  file=sys.stdout, flush=True)
            traceback.print_exc(file=sys.stderr)
            failures.append(k)
            drain_rows()
            continue
        artifact = dict(schema=BENCH_SCHEMA, suite=k, smoke=smoke,
                        generated_unix=time.time(), rows=drain_rows())
        if isinstance(ret, dict):
            artifact.update(ret)
        path = f"BENCH_{k}.json"
        with open(path, "w") as f:
            json.dump(artifact, f, indent=2, sort_keys=True)
        print(f"wrote {path}", file=sys.stderr)
    if failures:
        print(f"FAILED suites: {','.join(failures)}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()
