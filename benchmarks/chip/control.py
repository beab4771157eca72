#!/usr/bin/env python3
"""Readings that set a cell's limits of `correct`, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 2

For each seed, in one process: a short run of the cell's timed path, its
answers compared with the plain reference (the program's reading), and the
control: the same reference computed one precision lower (bfloat16 for the
configuration's float32) put in the program's place on the same problems
and compared alike (the control's reading). One JSON line per seed on
standard output. A limit lies between the largest program reading over a
dozen seeds or more and the smallest control reading.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import bench  # noqa: E402
from harness import device, spec  # noqa: E402


def readings(cell, seed: int, seconds: float, devs: list) -> dict:
    rec = cell.runner.run(cell, seed, seconds, False, time.perf_counter(), devs)
    by32, stats = bench.reference(cell, rec.checks)
    by16, _ = bench.reference(cell, rec.checks, dtype="bfloat16")
    control = [(k, p, by16[k]) for k, p, _ in rec.checks]
    return dict(seed=seed, program=bench.compare(rec.checks, by32),
                control=bench.compare(control, by32), **stats,
                answers=len(rec.checks), attempted=rec.attempted,
                compiles_in_window=rec.compiles_in_window)


def main(argv=None, require=device.require_tpu, root=bench.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload, root)
    try:
        devs = require(cell.chips)
    except device.NoAccelerator as e:
        print(f"control: {e}; nothing was run", file=sys.stderr)
        return 3
    device.use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds, devs)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
