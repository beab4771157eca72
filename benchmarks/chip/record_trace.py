#!/usr/bin/env python3
"""Record the small chip trace the benchmark's tests read.

    python3 benchmarks/chip/record_trace.py <out.xplane.pb>

On one TPU, in one profiler session marked as the harness marks its
window (`bench.window`, `bench.*` phases): a cold re-plan of a fleet of
2 cells x 256 devices through `repro.solve`, then one batch of two cells
through the region pipeline. Every shape is compiled before the session
opens. The trace carries the solver's named scopes on its device ops and
the program's `repro.*` spans on the host.
"""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (str(HERE.parents[1] / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

from harness import device, trace  # noqa: E402
from harness.record import phase  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out")
    args = ap.parse_args(argv)
    try:
        device.require_tpu(1)
    except device.NoAccelerator as e:
        print(f"record_trace: {e}; nothing was recorded", file=sys.stderr)
        return 3

    import repro
    from repro import Problem, SolverSpec, Weights, make_fleet, make_system
    from repro.region import AllocationRequest, MaxWait, RegionPipeline

    spec = SolverSpec(max_iters=8, tol=1e-4)
    fleet = make_fleet(jax.random.PRNGKey(0), 2, 256)
    w = [Weights(0.5, 0.5, 1.0), Weights(0.2, 0.8, 1.0)]
    pipe = RegionPipeline(Weights(0.5, 0.5, 1.0), cells_per_batch=2,
                          min_bucket=16, spec=spec, policy=MaxWait(0.05),
                          max_in_flight=2)
    cells = [make_system(jax.random.PRNGKey(k), n_devices=12)
             for k in range(1, 7)]
    ids = iter(range(len(cells)))

    def replan():
        with phase("enqueue", True):
            res = repro.solve(Problem(system=fleet, weights=w), spec)
        with phase("block", True):
            jax.block_until_ready((res.objective, res.allocation))

    def batch():
        with phase("submit", True):
            futs = [pipe.submit(AllocationRequest(cell_id=i, sys=cells[i]))
                    for i in (next(ids), next(ids))]
        with phase("poll", True):
            pipe.pump(force=True)
        with phase("materialize", True):
            futs[0].result()

    for _ in range(2):          # compile and warm every shape
        replan()
        batch()
    tmp = tempfile.mkdtemp(prefix="record_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            replan()
            batch()
        jax.profiler.stop_trace()
        newest = max(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                               recursive=True), key=os.path.getmtime)
        shutil.copyfile(newest, args.out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"record_trace: {args.out} {os.path.getsize(args.out)} bytes",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
