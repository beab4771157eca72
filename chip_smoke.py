#!/usr/bin/env python3
"""Smoke check: the allocator's main paths on a TPU, at full width.

    python chip_smoke.py              # one chip: phases (a) and (b)
    python chip_smoke.py --chips 4    # four chips: phase (c) only

(a) Fleet solve: 64 cells x 2048 devices through `solve()`. The compiled
    program must hold the SP1 sweep kernel (`tpu_custom_call`), every cell
    must converge, and each cell's objective must be within 1e-4 relative
    of the same solve with the nested-bisection SP1 oracle.
(b) Region pipeline: 32 requests with mixed pool sizes up to 2048 devices,
    per-request weights and warm re-requests through `RegionPipeline`.
    Every request must be answered, converged, and match a direct `solve()`
    of the same cell (from the same warm start).
(c) Four chips: the 64 x 2048 region solve on a 4-device `cells` mesh
    against the one-device fleet solve. Per-cell objectives must agree to
    1e-6 relative, and the result must hold one shard on each device.

Runs in one process, with JAX's default dtypes (x64 off). Without a TPU it
exits non-zero before solving anything. Lines starting "smoke:" are smoke
numbers, not benchmark numbers. The last line of a passing run is the JSON
verdict `{"ok": true, "device": {...}}`; a failed check exits non-zero
before it is printed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import (Problem, SolverSpec, Weights, make_fleet,  # noqa: E402
                   make_system, solve)
from repro.compile_cache import use_compile_cache  # noqa: E402

SPEC = SolverSpec(max_iters=8, tol=1e-4)
W = Weights(0.5, 0.5, 1.0)
FLEET_CELLS, FLEET_DEVICES = 64, 2048


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def rel_diff(a, b) -> np.ndarray:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b) / np.maximum(np.abs(b), np.finfo(np.float32).tiny)


def timed(fn):
    """Run a solve; its wall time, ended by the device finishing."""
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready((out.objective, out.allocation))
    return out, time.perf_counter() - t0


def fleet_problem(seed: int, cells: int, devices: int) -> Problem:
    """The `examples/allocate_fleet.py` fleet: 20 MHz per 50 devices."""
    fleet = make_fleet(jax.random.PRNGKey(seed), n_cells=cells,
                       n_devices=devices,
                       bandwidth_total=20e6 * devices / 50)
    return Problem(system=fleet, weights=W)


def phase_fleet(seed: int, cells: int = FLEET_CELLS,
                devices: int = FLEET_DEVICES) -> None:
    from repro.obs.profile import compile_solve

    problem = fleet_problem(seed, cells, devices)
    t0 = time.perf_counter()
    label, compiled = compile_solve(problem, SPEC)
    n_kernels = compiled.as_text().count("tpu_custom_call")
    say(f"(a) {label}: compile {time.perf_counter() - t0:.3f} s, "
        f"{n_kernels} tpu_custom_call sites in the program")
    check(n_kernels > 0, "(a) the fleet program holds no SP1 kernel "
          "(no tpu_custom_call)")

    res, first = timed(lambda: solve(problem, SPEC))
    res, warm = timed(lambda: solve(problem, SPEC))
    ref, first_ref = timed(
        lambda: solve(problem, SPEC.replace(sp1_method="bisect")))
    conv = np.asarray(res.converged)
    obj, obj_ref = np.asarray(res.objective), np.asarray(ref.objective)
    rel = rel_diff(obj, obj_ref)
    say(f"(a) solve first call {first:.3f} s (incl. compile), "
        f"warm call {warm:.3f} s, bisect oracle first call {first_ref:.3f} s")
    say(f"(a) converged {int(conv.sum())}/{cells} cells, BCD iters "
        f"max {int(np.max(np.asarray(res.iters)))}, objective vs bisect: "
        f"max rel diff {float(rel.max()):.3e}")
    check(np.isfinite(obj).all(), "(a) non-finite objective")
    for name, leaf in (("bandwidth", res.allocation.bandwidth),
                       ("power", res.allocation.power),
                       ("freq", res.allocation.freq)):
        leaf = np.asarray(leaf)
        check(leaf.shape == (cells, devices) and np.isfinite(leaf).all(),
              f"(a) {name} is not a finite ({cells}, {devices}) array")
    check(bool(conv.all()), f"(a) only {int(conv.sum())}/{cells} cells "
          f"converged")
    check(bool(np.asarray(ref.converged).all()),
          "(a) the bisect oracle did not converge")
    check(float(rel.max()) <= 1e-4,
          f"(a) objective differs from the bisect oracle by "
          f"{float(rel.max()):.3e} > 1e-4 relative")


def region_trace(seed: int, sizes, n_cold: int, n_warm: int):
    """`n_cold` cells with mixed pool sizes and per-request weights, then
    `n_warm` re-requests of the first cells after a 1% channel drift."""
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    cold = []
    for cid in range(n_cold):
        w1 = float(rng.uniform(0.1, 0.9))
        sysc = make_system(jax.random.fold_in(key, cid),
                           n_devices=int(sizes[cid % len(sizes)]))
        cold.append((cid, sysc, Weights(w1, 1.0 - w1,
                                        float(rng.uniform(1.0, 30.0)))))
    warm = []
    for cid, sysc, w in cold[:n_warm]:
        drift = 1.0 + 0.01 * rng.standard_normal(sysc.n)
        warm.append((cid, sysc.replace(
            gain=sysc.gain * jnp.asarray(np.abs(drift), sysc.gain.dtype)),
            w))
    return cold, warm


def phase_region(seed: int, sizes=(40, 200, 700, 2048), n_cold: int = 24,
                 n_warm: int = 8, cells_per_batch: int = 8,
                 rtol: float = 1e-4) -> None:
    from repro.region import AllocationRequest, RegionPipeline

    cold, warm = region_trace(seed, sizes, n_cold, n_warm)
    pipe = RegionPipeline(W, cells_per_batch=cells_per_batch, spec=SPEC)
    answered = {}
    t0 = time.perf_counter()
    for wave in (cold, warm):
        for cid, sysc, w in wave:
            pipe.submit(AllocationRequest(cell_id=cid, sys=sysc, w=w))
        answered[id(wave)] = {r.cell_id: r for r in pipe.drain()}
    wall = time.perf_counter() - t0
    n_req = n_cold + n_warm
    n_ans = sum(len(v) for v in answered.values())
    say(f"(b) pipeline answered {n_ans}/{n_req} requests in {wall:.3f} s "
        f"(incl. compiles), batch shapes {sorted(pipe.compiled_shapes)}")
    check(n_ans == n_req, f"(b) {n_req - n_ans} requests were not answered")

    worst, worst_at, t0 = 0.0, "", time.perf_counter()
    direct_converged = 0
    cold_resp = answered[id(cold)]
    for wave, warm_start in ((cold, False), (warm, True)):
        for cid, sysc, w in wave:
            r = answered[id(wave)][cid]
            check(r.warm == warm_start,
                  f"(b) cell {cid}: warm={r.warm}, expected {warm_start}")
            check(r.converged, f"(b) cell {cid} did not converge")
            init = cold_resp[cid].allocation if warm_start else None
            direct = solve(Problem(system=sysc, weights=w, init=init), SPEC)
            direct_converged += direct.converged
            rel = float(rel_diff(r.objective, direct.objective))
            if rel >= worst:
                worst, worst_at = rel, (
                    f"cell {cid}, n={sysc.n}, warm={warm_start}, BCD iters "
                    f"{r.iters} batched vs {direct.iters} direct, direct "
                    f"converged={direct.converged}")
            check(np.isfinite(r.objective) and rel <= rtol,
                  f"(b) cell {cid} (n={sysc.n}, warm={warm_start}): "
                  f"objective {r.objective!r} vs direct solve "
                  f"{direct.objective!r}, rel diff {rel:.3e} > {rtol}")
            bw = np.asarray(r.allocation.bandwidth)
            check(bw.shape == (sysc.n,) and np.isfinite(bw).all(),
                  f"(b) cell {cid}: bandwidth is not a finite ({sysc.n},)")
    say(f"(b) {n_req} responses vs direct solve(): max rel objective diff "
        f"{worst:.3e} ({worst_at}); {direct_converged}/{n_req} direct "
        f"solves converged in {time.perf_counter() - t0:.3f} s")


def phase_four_chips(seed: int, cells: int = FLEET_CELLS,
                     devices: int = FLEET_DEVICES, n_mesh: int = 4) -> None:
    from repro.region import region_mesh

    check(len(jax.devices()) >= n_mesh,
          f"(c) needs {n_mesh} devices, JAX sees {len(jax.devices())}")
    mesh = region_mesh(n_mesh)
    problem = fleet_problem(seed, cells, devices)
    region, t_region = timed(
        lambda: solve(Problem(system=problem.system, weights=W, mesh=mesh),
                      SPEC).fleet)
    fleet, t_fleet = timed(lambda: solve(problem, SPEC))
    say(f"(c) region solve on a {n_mesh}-device mesh first call "
        f"{t_region:.3f} s, one-device fleet first call {t_fleet:.3f} s "
        f"(both incl. compile)")
    shards = region.allocation.bandwidth.addressable_shards
    per_device = {}
    for s in shards:
        per_device[s.device] = per_device.get(s.device, 0) + 1
    rows = sorted(int(s.data.shape[0]) for s in shards)
    say(f"(c) bandwidth shards: {len(shards)} on {len(per_device)} devices, "
        f"{rows} cells each")
    check(set(per_device) == set(mesh.devices.flat)
          and all(v == 1 for v in per_device.values())
          and rows == [cells // n_mesh] * n_mesh,
          f"(c) expected one {cells // n_mesh}-cell shard on each of the "
          f"{n_mesh} mesh devices, got {per_device}")
    fleet_devs = {s.device for s in
                  fleet.allocation.bandwidth.addressable_shards}
    check(len(fleet_devs) == 1, f"(c) the fleet solve spans {fleet_devs}")
    obj_r, obj_f = np.asarray(region.objective), np.asarray(fleet.objective)
    rel = rel_diff(obj_r, obj_f)
    bitwise = all(
        np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(
            jax.tree_util.tree_leaves(region.allocation),
            jax.tree_util.tree_leaves(fleet.allocation))) \
        and np.array_equal(obj_r, obj_f)
    say(f"(c) per-cell objective region vs fleet: max rel diff "
        f"{float(rel.max()):.3e}; allocations and objectives bit-identical: "
        f"{bitwise}")
    check(np.isfinite(obj_r).all(), "(c) non-finite region objective")
    check(bool(np.asarray(region.converged).all()),
          "(c) not every region cell converged")
    check(float(rel.max()) <= 1e-6,
          f"(c) region objectives differ from the fleet's by "
          f"{float(rel.max()):.3e} > 1e-6 relative")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: fleet + region pipeline phases on one chip; "
                         "4: the region solve on a 4-chip mesh vs the "
                         "one-device fleet, and nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if jax.config.jax_enable_x64:
        print("chip_smoke: x64 is on; the chip path runs JAX's default "
              "dtypes", file=sys.stderr)
        return 2
    say(f"compile cache {use_compile_cache()}")
    say(f"device {dev.device_kind} x {len(jax.devices())}, jax "
        f"{jax.__version__}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_four_chips(args.seed)
        else:
            phase_fleet(args.seed)
            phase_region(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    say(f"all phases passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
