#!/usr/bin/env python3
"""The chip benchmark: one cell of `BENCHMARK.json`, measured on a TPU.

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell's configuration, traffic mix,
limits and per-layer readers are files found by name (`harness/spec.py`).
Set-up draws the cell's inputs from the seed, compiles and warms up every
shape the window uses; then the window runs for `--seconds`. With
`--trace 0` the result carries the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read from the last part of the window
under the profiler. After the window the answers are checked against the
plain reference; the numbers compared and their limits end standard error
and the result line, which is the last line of standard output. Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(ROOT / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from harness import check, device, spec  # noqa: E402


def reference(cell, checks: list, dtype="float32"):
    """The cell's reference solution of every distinct problem checked,
    solved in one batch (its cells spread over the cell's chips): {key: B,
    p, f, s}, and its iteration stats."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    keyed = {}
    for key, problem, _ in checks:
        keyed.setdefault(key, problem)
    keys = list(keyed)
    probs = [keyed[k] for k in keys]
    N = max(p["active"].shape[1] for p in probs)

    def cat(parts, pad):
        return np.concatenate([
            np.pad(x, ((0, 0), (0, N - x.shape[1])), constant_values=pad)
            if x.ndim == 2 else x for x in map(np.asarray, parts)])

    def cold(p):
        C, n = p["active"].shape
        return dict(B=np.zeros((C, n)), p=np.zeros((C, n)),
                    warm=np.zeros((C,), bool))

    inits = [p.get("init") or cold(p) for p in probs]
    C = sum(p["active"].shape[0] for p in probs)
    devs = jax.devices()[:cell.chips]
    sharding = NamedSharding(Mesh(np.asarray(devs), ("cells",)),
                             PartitionSpec("cells")) \
        if len(devs) > 1 and C % len(devs) == 0 else None
    ref = cell.reference.solve(
        {k: cat([p["arrays"][k] for p in probs], 1.0 if k == "gain" else 0.0)
         for k in probs[0]["arrays"]},
        cat([p["active"] for p in probs], False),
        {k: cat([p["scalars"][k] for p in probs], 0.0)
         for k in probs[0]["scalars"]},
        cat([p["weights"] for p in probs], 0.0),
        probs[0]["accuracy"], probs[0]["menu"],
        tol=float(cell.config["solver"]["tol"]), dtype=jnp.dtype(dtype),
        init={k: cat([i[k] for i in inits], 0.0) for k in ("B", "p", "warm")},
        sharding=sharding)
    start, by_key = 0, {}
    for k, p in zip(keys, probs):
        C, n = p["active"].shape
        by_key[k] = {a: ref[a][start:start + C, :n] for a in "Bpfs"}
        start += C
    stats = dict(ref_iters_max=int(ref["iters"].max()),
                 ref_unconverged=int((~ref["converged"]).sum()))
    return by_key, stats


def compare(checks: list, by_key: dict) -> dict:
    """The widest of each number compared over every answer checked."""
    worst = {k: 0.0 for k in check.NUMBERS}
    for key, problem, answer in checks:
        got = check.compare(problem, answer, by_key[key])
        for k in check.NUMBERS:
            worst[k] = max(worst[k], got[k])
    return worst


def result(cell, rec, traced: bool, numbers: dict, stamp: dict) -> dict:
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    dev = dict(stamp, memory_peak_bytes=rec.memory_peak_bytes)
    out = {}
    if traced:
        values = {}
        for m in cell.per_layer:
            v = cell.readers[m["name"]](rec)
            if v is not None:
                values[m["name"]] = float(v)
        if rec.trace is not None:
            dev["busy_s"] = rec.trace.busy_mean_s
            dev["window_s"] = rec.trace.window_s
            ops = sorted(rec.trace.op_self_s.items(), key=lambda kv: -kv[1])
            out["breakdown"] = dict(
                device_ops=[[k, v] for k, v in ops[:10]],
                idle_gaps=[[k, v] for k, v in rec.trace.gaps[:10]])
    else:
        e2e = cell.runner.end_to_end(rec)
        values = {m["name"]: e2e[m["name"]] for m in cell.end_to_end}
    correct = check.verdict(numbers, cell.limits) and rec.failed == 0
    line = dict(correct=bool(correct), attempted=int(rec.attempted),
                failed=int(rec.failed),
                metrics={k: {"value": v, "unit": units[k]}
                         for k, v in values.items()},
                device=dev)
    line.update(out)
    line["compared"] = {k: {"value": numbers[k], "limit": cell.limits[k]}
                        for k in check.NUMBERS}
    return line


def main(argv=None, require=device.require_tpu, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    import jax

    cell = spec.resolve(args.workload, root)
    try:
        devs = require(cell.chips)
    except device.NoAccelerator as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 3
    if jax.config.jax_enable_x64:
        print("bench: x64 is on; the chip path runs float32",
              file=sys.stderr)
        return 2
    cache = device.use_compile_cache()
    stamp = device.stamp(devs)
    print(f"bench: {cell.name} seed {args.seed} on {stamp['kind']} x "
          f"{stamp['count']}, compile cache {cache}", file=sys.stderr)
    rec = cell.runner.run(cell, args.seed, args.seconds, bool(args.trace),
                     T_PROCESS, devs)
    print(f"bench: setup_s {rec.setup_s:.3f}, window {rec.window_s:.3f} s, "
          f"attempted {rec.attempted}, failed {rec.failed}",
          file=sys.stderr)
    print(f"bench: compiles_in_window {rec.compiles_in_window}",
          file=sys.stderr)
    by_key, stats = reference(cell, rec.checks)
    numbers = compare(rec.checks, by_key)
    print(f"bench: reference iterations max {stats['ref_iters_max']}, "
          f"unconverged cells {stats['ref_unconverged']}", file=sys.stderr)
    line = result(cell, rec, bool(args.trace), numbers, stamp)
    for k, v in line["compared"].items():
        print(f"compared {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
