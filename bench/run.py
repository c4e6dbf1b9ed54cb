#!/usr/bin/env python3
"""Runs one cell of the chip benchmark and prints its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, its traffic
mix and its per-layer metrics are found by name from ``BENCHMARK.json``.
With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
run.  The last line of standard output is one JSON object; the last lines
of standard error are the numbers compared with the reference, each beside
its limit.  Exits 2, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench.lib import harness, spec

    cell = spec.load(ROOT, args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from repro import enable_compile_cache
    # every program goes to the cache, so only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    harness.log(f"compile cache: {enable_compile_cache()}")
    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START,
                              devices[:cell.chips], cell=cell)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
