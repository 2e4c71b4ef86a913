#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload products-b1024 --seed 7 --seconds 10 \
        --trace 0

``--trace 0`` reports the cell's end-to-end metrics (``step_ms``,
``setup_s``); ``--trace 1`` traces the window with the profiler and
reports the per-layer metrics, the device's busy time and a breakdown.
Either way the first chunk is checked against the plain reference and the
numbers compared are printed last on standard error and under ``checks``.
The run fails, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE = os.path.join(ROOT, ".bench_cache", "jax")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw .xplane.pb of a traced run here")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import harness, manifest
    cell = manifest.load_cell(args.workload, ROOT)
    devices = harness.open_chips(cell, CACHE)
    if devices is None:
        return 1
    import repro  # noqa: F401  (fails here in a tree without the program)

    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices, T_START,
                              keep_trace=args.keep_trace)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
