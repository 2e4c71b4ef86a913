#!/usr/bin/env python3
"""Readings a cell's limits are set from, on the chip, in one process.

    python3 bench/calibrate.py --workload products-b1024 \
        --seeds 11,12,...  --control-seeds 11,12,13 [--write-limits]

For every seed in ``--seeds`` the program's two checked chunks are compared
with the float32 reference: the lower readings, from sound runs of the
program.
For every seed in ``--control-seeds`` the reference's variants stand in
the program's place and are compared with the same float32 reference:
``fp8`` (the control), ``half_batch`` (a fault) and ``bf16`` (what JAX's
default matmul precision does, a reading only). A state left unchanged
reads 1 on ``grad`` and ``update`` and 0 on ``loss`` (the held chunk's
losses do not see the state) by construction, and needs no run.

Each number's upper reading is the smallest of the control's readings,
where that is at least three times the lower reading, of the half-batch
fault's, where that is at least ten times, and of the unchanged state's,
where that is at least three times. ``--write-limits`` then sets each
limit at ``lower * (upper / lower) ** 0.6`` -- above the lower, below the
upper, with more room above the lower -- and writes
``bench/limits/<workload>.json`` with the readings it came from; a number
with no upper reading gets ``null``, and is reported but not compared.

One JSON line per seed, then a summary line.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE = os.path.join(ROOT, ".bench_cache", "jax")
UNCHANGED_STATE = {"loss": 0.0, "grad": 1.0, "update": 1.0}
PLACE = 0.6


def upper_reading(number, lower, control, half_batch):
    """The smallest reading that fails this number, or None."""
    cands = []
    if control is not None and control >= 3 * lower:
        cands.append(control)
    if half_batch is not None and half_batch >= 10 * lower:
        cands.append(half_batch)
    if UNCHANGED_STATE[number] >= 3 * lower:
        cands.append(UNCHANGED_STATE[number])
    return min(cands) if cands else None


def limit_between(lower, upper):
    x = lower * (upper / lower) ** PLACE
    return float(f"{x:.2g}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--write-limits", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import check, harness, manifest
    cell = manifest.load_cell(args.workload, ROOT)
    devices = harness.open_chips(cell, CACHE)
    if devices is None:
        return 1

    tc = harness.TrainCell(cell, devices)
    tc.setup()
    graph = tc.ref_graph()
    kinds = ("program", "fp8", "half_batch", "bf16")
    worst = {k: {} for k in kinds}
    for seed in sorted(set(seeds) | set(controls)):
        t0 = time.perf_counter()
        state, step, got, params = tc.start(seed)
        del state
        first = step - 2 * tc.chunk
        ref = tc.reference(params, first, graph)
        line = {"seed": seed, "first_step": first,
                "ref_losses": ref.held.losses + ref.trained.losses}
        runs = {}
        if seed in seeds:
            runs["program"] = got
            line["program_losses"] = got.held.losses + got.trained.losses
        if seed in controls:
            for v in kinds[1:]:
                runs[v] = tc.reference(params, first, graph, v)
        for kind, run in runs.items():
            vals = check.readings(run, ref)
            line[kind] = vals
            for k in check.NUMBERS:
                pick = max if kind == "program" else min
                worst[kind][k] = pick(worst[kind].get(k, vals[k]), vals[k])
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)

    summary = {"workload": cell.name, "device": devices[0].device_kind,
               "seeds": seeds, "control_seeds": controls,
               "largest_program": worst["program"],
               "smallest_fp8": worst["fp8"],
               "smallest_half_batch": worst["half_batch"],
               "smallest_bf16": worst["bf16"],
               "unchanged_state": UNCHANGED_STATE}
    limits, upper = {}, {}
    for k in check.NUMBERS:
        lo = worst["program"].get(k)
        up = (upper_reading(k, lo, worst["fp8"].get(k),
                            worst["half_batch"].get(k))
              if lo else None)
        upper[k] = up
        limits[k] = limit_between(lo, up) if up else None
    summary["upper"] = upper
    summary["limits"] = limits
    print(json.dumps(summary), flush=True)
    if args.write_limits:
        # a number with no upper reading gets no limit: it is reported,
        # not compared
        path = os.path.join(ROOT, "bench", "limits", f"{cell.name}.json")
        with open(path, "w") as f:
            json.dump({"limits": limits,
                       "rule": f"lower * (upper / lower) ** {PLACE}",
                       "readings": summary}, f, indent=1)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
