#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's graph on the chip from ``--seed``, serves the cell's
traffic through ``AsyncClusterEngine`` over a ``LocalClusterEngine`` with
its defaults, measures for ``--seconds``, checks the answers against the
plain reference (``bench/check.py``) and prints one JSON object.  With
``--trace 0`` its metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from the program's spans and a
profiler trace of a few seconds of the window.  Exits 2 before building
anything when JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.monotonic()      # set-up is timed from process start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [_ROOT, os.path.join(_ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.resolve(harness.load_spec(), args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
