#!/usr/bin/env python3
"""The knee of a serving cell: the highest offered rate it sustains.

    python3 benchmarks/chip/knee.py --workload <cell> --rates 200,400,800 \
        --seconds 8 --seed 1

Runs the cell's open loop at each rate in turn, in one process, and prints
one JSON line per rate: offered and completed rates, the backlog (requests
due and not yet answered) at the middle and at the end of the window, and
the 95th percentile latency. A rate is sustained when the completed rate is
at least 98% of the offered rate and the backlog at the end exceeds the
backlog at the middle by no more than one full batch per batch in flight.
The knee is the highest swept rate that is sustained, with every lower
swept rate sustained too; the last line says which it is. Sweep at the
cell's window or longer and over several seeds: the cell's knee is the
lowest of theirs.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time

import numpy as np

import bench  # noqa: F401  (puts the program and the harness on the path)
from harness import device, spec


def backlog(rec, t: float) -> int:
    due = np.sum(rec.due_s <= t)
    done = np.sum(np.nan_to_num(rec.done_s, nan=np.inf) <= t)
    return int(due - done)


def sweep_point(cell, rate: float, seed: int, seconds: float, devs) -> dict:
    cell = copy.copy(cell)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.traffic["arrivals"]["rate_per_s"] = rate
    rec = cell.runner.run(cell, seed, seconds, False, time.perf_counter(), devs)
    pipe = cell.config["pipeline"]
    slack = int(pipe["cells_per_batch"]) * int(pipe["max_in_flight"])
    mid, end = backlog(rec, seconds / 2), backlog(rec, seconds)
    done_rate = rec.completed_in_window / seconds
    offered = rec.attempted / seconds
    return dict(rate=rate, offered_per_s=offered, completed_per_s=done_rate,
                backlog_mid=mid, backlog_end=end,
                p95_ms=1e3 * float(np.percentile(rec.latency_s, 95)),
                p50_ms=1e3 * float(np.percentile(rec.latency_s, 50)),
                compiles_in_window=rec.compiles_in_window,
                sustained=bool(done_rate >= 0.98 * offered
                               and end - mid <= slack))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    try:
        devs = device.require_tpu(cell.chips)
    except device.NoAccelerator as e:
        print(f"knee: {e}; nothing was run", file=sys.stderr)
        return 3
    device.use_compile_cache()
    knee = None
    for rate in sorted(float(r) for r in args.rates.split(",")):
        point = sweep_point(cell, rate, args.seed, args.seconds, devs)
        print(json.dumps(point), flush=True)
        if not point["sustained"]:
            break
        knee = rate
    print(json.dumps({"knee_per_s": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
