"""Benchmark harness — one function per paper figure/table.

    PYTHONPATH=src python -m benchmarks.run                   # all
    PYTHONPATH=src python -m benchmarks.run fig3 fig8         # subset
    PYTHONPATH=src python -m benchmarks.run --json out.json fleet

Prints ``name,us_per_call,derived`` CSV rows (derived = the figure's metric).
``--json PATH`` additionally writes the rows as a BENCH_*.json-style artifact
for the perf trajectory (list of {name, us_per_call, derived} objects).
``--metrics PATH`` writes the run's `repro.obs` metrics registry (latency
histograms with derived p50/p90/p99) as metrics JSONL — the CI artifact.
Scaled down from the paper's N=50/100-rep setup to run on one CPU core; the
trends, not the absolute magnitudes, are the reproduction target
(EXPERIMENTS.md compares against the paper's claims).
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import (Problem, SolverSpec, Weights, make_fleet, make_system,
                   obs, solve)
from repro.compile_cache import use_compile_cache
from repro.core import total_energy, total_time
from repro.core.baselines import comm_only, comp_only, min_pixel, rand_pixel, scheme1
from repro.core.types import dbm_to_watt

N_DEV = 12
REPS = 2

_ROWS: list = []


def _row(name, t0, t1, derived, calls=1):
    us = (t1 - t0) / max(calls, 1) * 1e6
    _ROWS.append(dict(name=name, us_per_call=round(us), derived=str(derived)))
    print(f"{name},{us:.0f},{derived}", flush=True)


def _lat_pcts(lat):
    """p50/p99 of a latency sample through the repo's fixed-bucket
    `repro.obs` Histogram — the same layout (and thus the same ~7%
    quantization) as the live metrics and the compare.py gate, replacing
    the ad-hoc np.percentile math the rows used to carry."""
    h = obs.Histogram("lat")
    h.observe_many(float(x) for x in lat)
    return dict(p50=h.percentile(50), p99=h.percentile(99))


def _mean_over_seeds(fn, reps=REPS):
    es, ts = [], []
    for r in range(reps):
        e, t = fn(jax.random.PRNGKey(100 + r))
        es.append(e)
        ts.append(t)
    return sum(es) / len(es), sum(ts) / len(ts)


def fig3_weight_sweep_power():
    """Fig. 3: energy/time vs p_max for three (w1,w2) pairs + MinPixel (rho=1)."""
    for pmax_dbm in [4.0, 8.0, 12.0]:
        for w1, w2 in [(0.9, 0.1), (0.5, 0.5), (0.1, 0.9)]:
            def run(key, w1=w1, w2=w2):
                sysp = make_system(key, n_devices=N_DEV, p_max=dbm_to_watt(pmax_dbm))
                res = solve(Problem(system=sysp, weights=Weights(w1, w2, 1.0)),
                            SolverSpec(max_iters=6))
                return (float(total_energy(sysp, res.allocation)),
                        float(total_time(sysp, res.allocation)))
            t0 = time.time()
            e, t = _mean_over_seeds(run)
            _row(f"fig3.w{w1}-{w2}.pmax{pmax_dbm:g}dBm", t0, time.time(),
                 f"E={e:.4g}J;T={t:.4g}s", REPS)

        def run_bench(key):
            sysp = make_system(key, n_devices=N_DEV, p_max=dbm_to_watt(pmax_dbm))
            a = min_pixel(sysp, key, sweep="power")
            return (float(total_energy(sysp, a)), float(total_time(sysp, a)))
        t0 = time.time()
        e, t = _mean_over_seeds(run_bench)
        _row(f"fig3.MinPixel.pmax{pmax_dbm:g}dBm", t0, time.time(),
             f"E={e:.4g}J;T={t:.4g}s", REPS)


def fig4_weight_sweep_freq():
    """Fig. 4: energy/time vs f_max (rho=10)."""
    for fmax in [0.5e9, 1.0e9, 2.0e9]:
        for w1, w2 in [(0.9, 0.1), (0.5, 0.5), (0.1, 0.9)]:
            def run(key, w1=w1, w2=w2):
                sysp = make_system(key, n_devices=N_DEV, f_max=fmax)
                res = solve(Problem(system=sysp, weights=Weights(w1, w2, 10.0)),
                            SolverSpec(max_iters=6))
                return (float(total_energy(sysp, res.allocation)),
                        float(total_time(sysp, res.allocation)))
            t0 = time.time()
            e, t = _mean_over_seeds(run)
            _row(f"fig4.w{w1}-{w2}.fmax{fmax/1e9:g}GHz", t0, time.time(),
                 f"E={e:.4g}J;T={t:.4g}s", REPS)

        def run_bench(key):
            sysp = make_system(key, n_devices=N_DEV, f_max=fmax)
            a = min_pixel(sysp, key, sweep="freq")
            return (float(total_energy(sysp, a)), float(total_time(sysp, a)))
        t0 = time.time()
        e, t = _mean_over_seeds(run_bench)
        _row(f"fig4.MinPixel.fmax{fmax/1e9:g}GHz", t0, time.time(),
             f"E={e:.4g}J;T={t:.4g}s", REPS)


def fig5_rho_sweep():
    """Fig. 5: energy/time vs rho, + MinPixel/RandPixel, (w1,w2)=(0.5,0.5)."""
    for rho in [1.0, 10.0, 30.0, 50.0]:
        def run(key, rho=rho):
            sysp = make_system(key, n_devices=N_DEV)
            res = solve(Problem(system=sysp, weights=Weights(0.5, 0.5, rho)),
                        SolverSpec(max_iters=6))
            a = res.allocation
            return (float(total_energy(sysp, a)), float(total_time(sysp, a)),
                    float(jnp.mean(a.resolution)))
        t0 = time.time()
        outs = [run(jax.random.PRNGKey(100 + r)) for r in range(REPS)]
        e = sum(o[0] for o in outs) / REPS
        t = sum(o[1] for o in outs) / REPS
        s = sum(o[2] for o in outs) / REPS
        _row(f"fig5.rho{rho:g}", t0, time.time(),
             f"E={e:.4g}J;T={t:.4g}s;mean_s={s:.0f}px", REPS)
    for name, fn in [("MinPixel", min_pixel), ("RandPixel", rand_pixel)]:
        def run(key, fn=fn):
            sysp = make_system(key, n_devices=N_DEV)
            a = fn(sysp, key)
            return (float(total_energy(sysp, a)), float(total_time(sysp, a)))
        t0 = time.time()
        e, t = _mean_over_seeds(run)
        _row(f"fig5.{name}", t0, time.time(), f"E={e:.4g}J;T={t:.4g}s", REPS)


def fig7_rho_vs_fl_accuracy():
    """Fig. 6/7: rho -> chosen resolutions -> actual FedAvg accuracy
    (synthetic resolution-sensitive dataset; see DESIGN.md §6)."""
    from repro.fl import make_federated_dataset, simulate

    key = jax.random.PRNGKey(0)
    ds = make_federated_dataset(jax.random.fold_in(key, 1), n_clients=6,
                                per_client=64, base_resolution=16)
    ds_unb = make_federated_dataset(jax.random.fold_in(key, 1), n_clients=6,
                                    per_client=64, base_resolution=16,
                                    unbalanced=True)
    for tag, dset in [("", ds), (".unbalanced", ds_unb)]:
        for rho in [1.0, 30.0, 60.0]:
            if tag and rho != 60.0:
                continue   # one unbalanced point suffices for the trend
            sysp = make_system(key, n_devices=6)
            t0 = time.time()
            res = simulate(jax.random.fold_in(key, 2), sysp,
                           Weights(0.5, 0.5, rho), dataset=dset,
                           dataset_resolutions=(4, 8, 12, 16),
                           global_rounds=12, local_iters=4)
            _row(f"fig7.rho{rho:g}{tag}", t0, time.time(),
                 f"acc={res.ledger['final_accuracy']:.3f};"
                 f"mean_s={res.ledger['mean_resolution']:.0f}px;"
                 f"E={res.ledger['energy_total_J']:.4g}J")


def fig8_joint_vs_single():
    """Fig. 8: joint optimization vs communication-only vs computation-only."""
    for T_total in [80.0, 120.0, 200.0]:
        key = jax.random.PRNGKey(7)
        sysp = make_system(key, n_devices=N_DEV, p_max=dbm_to_watt(10.0))
        w = Weights(0.99, 0.01, 1.0)
        t0 = time.time()
        ours = solve(Problem(system=sysp, weights=w, deadline=T_total),
                     SolverSpec(max_iters=6))
        e_ours = float(total_energy(sysp, ours.allocation))
        a_comm = comm_only(sysp, w, T_total, jax.random.fold_in(key, 1))
        e_comm = float(total_energy(sysp, a_comm))
        a_comp = comp_only(sysp, w, T_total)
        e_comp = float(total_energy(sysp, a_comp))
        _row(f"fig8.T{T_total:g}s", t0, time.time(),
             f"joint={e_ours:.4g}J;comm_only={e_comm:.4g}J;"
             f"comp_only={e_comp:.4g}J")


def fig9_vs_scheme1():
    """Fig. 9: deadline-constrained energy, the paper's conference algorithm
    (joint p/B/f, s pinned) vs Scheme 1 (Yang et al. [11] proxy)."""
    from repro.core.baselines import conference_version

    for T_total in [80.0, 150.0]:
        for pmax_dbm in [6.0, 12.0]:
            key = jax.random.PRNGKey(9)
            sysp = make_system(key, n_devices=N_DEV, p_max=dbm_to_watt(pmax_dbm))
            w = Weights(0.99, 0.01, 0.0)
            t0 = time.time()
            ours = conference_version(sysp, w, T_total, max_iters=6)
            s1 = scheme1(sysp, w, T_total)
            _row(f"fig9.T{T_total:g}s.pmax{pmax_dbm:g}dBm", t0, time.time(),
                 f"ours={float(total_energy(sysp, ours.allocation)):.4g}J;"
                 f"scheme1={float(total_energy(sysp, s1)):.4g}J")


def table_allocator_scaling():
    """Complexity: paper's CVX path is O(N^4.5); ours is closed-form —
    measure wall time vs N."""
    from repro.core.energy import t_cmp
    from repro.core.sp2 import r_min, solve_sp2_direct

    for N in [64, 1024, 16384]:
        key = jax.random.PRNGKey(11)
        sysp = make_system(key, n_devices=N, bandwidth_total=20e6 * N / 50)
        f = jnp.full((N,), 1e9)
        s = jnp.full((N,), 320.0)
        T = float(jnp.max(t_cmp(sysp, f, s))) * 1.2
        rmin = r_min(sysp, f, s, jnp.asarray(T))
        p, B = solve_sp2_direct(sysp, rmin)    # compile
        jax.block_until_ready(B)
        t0 = time.time()
        p, B = solve_sp2_direct(sysp, rmin)
        jax.block_until_ready(B)
        t1 = time.time()
        _row(f"scaling.N{N}", t0, t1, f"sp2_direct={1e3*(t1-t0):.1f}ms")


def fleet_scale():
    """Fleet allocation: one vmap'd BCD solve across C cells x N devices —
    the fleet acceptance row (>= 64 cells x 2048 devices), now through the
    unified `solve()` dispatcher (median-of-3 protocol: one compile/warm
    call, then the median of 3 timed solves — the recorded wall is the
    steady-state dispatcher cost, so a solve()-layer regression shows up
    directly against the BENCH_fleet.json baseline).
    max_iters=8 is calibrated to the fleet regime: the BCD rel-step contracts
    ~5x per iteration and hits the f32 convergence floor around iteration 6
    (the old max_iters=3 could not converge any cell except by luck)."""
    import statistics

    C, N = 64, 2048
    key = jax.random.PRNGKey(31)
    fleet = make_fleet(key, n_cells=C, n_devices=N,
                       bandwidth_total=20e6 * N / 50)
    problem = Problem(system=fleet, weights=Weights(0.5, 0.5, 1.0))
    spec = SolverSpec(max_iters=8)
    res = solve(problem, spec)   # compile / warm
    jax.block_until_ready(res.allocation.bandwidth)
    walls = []
    for _ in range(3):
        t0 = time.time()
        jax.block_until_ready(solve(problem, spec).allocation.bandwidth)
        walls.append(time.time() - t0)
    wall = statistics.median(walls)
    conv = int(jnp.sum(res.converged))
    t0 = time.time()
    _row(f"fleet.C{C}.N{N}", t0, t0 + wall,
         f"devices={C * N};cells_converged={conv}/{C};"
         f"mean_obj={float(jnp.mean(res.objective)):.4g};"
         f"wall_s={wall:.1f}")


def region_scale():
    """Region sharding acceptance row: the fleet row's 64 x 2048 workload
    solved on 1 device via `allocate_fleet` vs sharded over all local
    devices via `allocate_region` (shard_map: each shard's BCD while_loop
    exits when its own cells converge instead of the global lockstep — on
    the 2-core recording host that early exit is what pushes the speedup
    past the core-count ceiling). Run under
    XLA_FLAGS=--xla_force_host_platform_device_count=8 to expose a mesh on
    one CPU host. Also reports the SP2-direct carried-bracket dual-search
    eval count (ledger `sp2_iters` column) vs the non-carried reference."""
    from repro.core.sp2 import direct_eval_counts
    from repro.region import region_mesh

    import os
    import statistics

    C, N = 64, 2048
    key = jax.random.PRNGKey(31)
    fleet = make_fleet(key, n_cells=C, n_devices=N,
                       bandwidth_total=20e6 * N / 50)
    w = Weights(0.5, 0.5, 1.0)
    spec = SolverSpec(max_iters=8)
    ndev = jax.device_count()
    cores = os.cpu_count() or 1

    def median_wall(fn, reps=3):
        fn()   # compile / warm
        walls = []
        for _ in range(reps):
            t0 = time.time()
            fn()
            walls.append(time.time() - t0)
        return statistics.median(walls)

    res1 = solve(Problem(system=fleet, weights=w), spec)
    t_1dev = median_wall(lambda: jax.block_until_ready(
        solve(Problem(system=fleet, weights=w),
              spec).allocation.bandwidth))
    walls = {}
    for nd in sorted({min(4, ndev), ndev}):
        if nd <= 1:
            continue
        mesh = region_mesh(nd)
        walls[nd] = median_wall(lambda m=mesh: jax.block_until_ready(
            solve(Problem(system=fleet, weights=w, mesh=m),
                  spec).fleet.allocation.bandwidth))
    reg = solve(Problem(system=fleet, weights=w, mesh=region_mesh()), spec)

    # measured SP2 dual-search evals (sp2_iters ledger col) vs reference
    led = jnp.asarray(res1.history)                      # (C, it, cols)
    ev = float(jnp.nanmean(led[..., 4]))
    ev_ref = direct_eval_counts(res1.objective.dtype)
    conv = int(jnp.sum(reg.converged))
    t_shard = walls.get(ndev, t_1dev)
    scaling = ";".join(
        f"speedup_{nd}dev={t_1dev / max(wl, 1e-9):.2f}x"
        for nd, wl in sorted(walls.items()))
    t0 = time.time()
    _row(f"region.C{C}.N{N}", t0, t0 + t_shard,
         f"devices={C * N};mesh={ndev};host_cores={cores};"
         f"wall_1dev_s={t_1dev:.1f};wall_shard_s={t_shard:.1f};{scaling};"
         f"cells_converged={conv}/{C};"
         f"mean_obj={float(jnp.nanmean(reg.objective)):.4g};"
         f"sp2_evals_per_iter={ev:.0f}_vs_ref_{ev_ref}"
         f"({ev_ref / max(ev, 1.0):.1f}x)")


def rounds_dynamics():
    """Round-dynamics engine acceptance row: R=32 rounds x C=64 cells x
    N=2048 devices as ONE jitted scan (vmap'd over cells, no per-round host
    sync), Gauss-Markov fading + stragglers/staleness + dropouts.

    Warm-vs-cold: the warm engine re-allocates each round from the previous
    round's allocation (bcd_iters=3, tol=1e-3 — the per-round solve residual
    only needs to sit well below the percent-scale channel drift); the cold
    reference is the SAME engine with warm_start=False, i.e. a cold
    `allocate_fleet` (paper init, fleet-row max_iters=8 calibration) every
    round. Both walls include one compile amortized over the 32 rounds."""
    from repro.dynamics import RoundsConfig

    R, C, N = 32, 64, 2048
    key = jax.random.PRNGKey(51)
    fleet = make_fleet(key, n_cells=C, n_devices=N,
                       bandwidth_total=20e6 * N / 50)
    w = Weights(0.5, 0.5, 1.0)

    # round-0 allocation the warm engine starts from (one cold fleet solve)
    t0 = time.time()
    base = solve(Problem(system=fleet, weights=w), SolverSpec(max_iters=8))
    jax.block_until_ready(base.allocation.bandwidth)
    t_base = time.time() - t0

    kw = dict(rounds=R, channel_mode="markov", drift_rho=0.95,
              participation="stale", dropout_prob=0.02, bcd_tol=1e-3)
    walls, conv_min, iters_mean, rr_warm = {}, {}, {}, None
    for tag, cfg in [
        ("warm", RoundsConfig(bcd_iters=3, **kw)),
        ("cold", RoundsConfig(bcd_iters=8, warm_start=False, **kw)),
    ]:
        t0 = time.time()
        rr = solve(Problem(system=fleet, weights=w, rounds=cfg,
                           key=jax.random.PRNGKey(52),
                           init=base.allocation))
        jax.block_until_ready(rr.ledger)
        walls[tag] = time.time() - t0
        per_round_cells = jnp.mean(rr.col("bcd_converged"), axis=0)
        conv_min[tag] = float(jnp.min(per_round_cells))
        iters_mean[tag] = float(jnp.mean(rr.col("bcd_iters")))
        if tag == "warm":
            rr_warm = rr
        del rr   # don't retain the cold run's (C, R, N) arrays

    rr = rr_warm
    t0 = time.time()
    _row(f"rounds.R{R}.C{C}.N{N}", t0, t0 + walls["warm"],
         f"devices={C * N};s_per_round={walls['warm'] / R:.2f};"
         f"warm_vs_cold={walls['cold'] / walls['warm']:.1f}x;"
         f"conv_min={conv_min['warm']:.3f};"
         f"mean_bcd_iters={iters_mean['warm']:.2f};"
         f"arrived_frac={float(jnp.mean(rr.col('arrived_frac'))):.3f};"
         f"mean_obj={float(jnp.mean(rr.col('objective'))):.4g};"
         f"fleet_solve_s={t_base:.1f}")
    t0 = time.time()
    _row(f"rounds.cold_restart.R{R}.C{C}.N{N}", t0, t0 + walls["cold"],
         f"s_per_round={walls['cold'] / R:.2f};"
         f"conv_min={conv_min['cold']:.3f};"
         f"mean_bcd_iters={iters_mean['cold']:.2f}")


def serve_latency():
    """Pipelined region serving acceptance: p50/p99 request latency and
    sustained req/s on a 256-request mixed-size trace (4 device buckets ->
    <= 4 compiled shapes), under Poisson and bursty arrivals.

    `sync` replays the trace through the pre-pipeline monolith loop (the
    PR 4-5 `RegionAllocator._solve_chunk`, reconstructed below verbatim):
    eager jnp padding/stacking enqueued on the device stream, one blocking
    solve per chunk, then a per-cell jnp-slice gather — host assembly and
    device compute strictly serialized. `pipelined` is the four-layer
    `RegionPipeline` at depth 2: numpy host assembly, async dispatch,
    double-buffered batches, one deferred numpy gather per batch. The
    acceptance gate is pipelined >= 1.3x the sync req/s on the Poisson
    trace (checked by compare.py --strict via the speedup_vs_sync field).

    Arrival offsets span half the pipelined serial drain wall, so both
    paths run saturated and the sustained rate reflects each path's
    capacity; request latency = completion - arrival. All cell ids are
    unique (every solve cold) so both paths do identical device work."""
    import numpy as np

    from repro.core.bcd import initial_allocation, stack_systems
    from repro.core.types import Allocation
    from repro.region import AllocationRequest, MaxWait, RegionPipeline
    from repro.region.batch import bucket_size, pad_allocation, pad_system

    n_req, cells_per_batch, min_bucket = 256, 16, 16
    spec = SolverSpec(max_iters=8, tol=1e-4)
    w = Weights(0.5, 0.5, 1.0)
    # paper-scale cells (~N=50 pools): buckets 16, 32, 64, 128
    sizes = [12, 24, 48, 90]
    key = jax.random.PRNGKey(61)
    systems = [make_system(jax.random.fold_in(key, i),
                           n_devices=sizes[i % len(sizes)])
               for i in range(n_req)]

    def pipe(depth):
        return RegionPipeline(w, cells_per_batch=cells_per_batch,
                              min_bucket=min_bucket, spec=spec,
                              policy=MaxWait(0.05), max_in_flight=depth)

    def trace():
        return [AllocationRequest(cell_id=i, sys=systems[i])
                for i in range(n_req)]

    # ---------------- the PR 4-5 synchronous monolith, reconstructed ----
    class _LegacyAllocator:
        """The pre-pipeline `RegionAllocator` chunk loop: eager jnp
        assembly, blocking solve, immediate per-cell jnp-slice gather."""

        def __init__(self):
            self._cache = {}
            self.shapes = set()

        def solve_chunk(self, chunk, bucket):
            C = cells_per_batch
            padded = [pad_system(r.sys, bucket) for r in chunk]
            inits = []
            for r, ps in zip(chunk, padded):
                got = self._cache.get(r.cell_id)
                init = pad_allocation(got[1], bucket, ps) \
                    if got is not None and got[0] == r.sys.n \
                    else initial_allocation(ps)
                if init.s_relaxed is None or init.T is None:
                    dt = jnp.asarray(init.bandwidth).dtype
                    init = Allocation(
                        bandwidth=init.bandwidth, power=init.power,
                        freq=init.freq, resolution=init.resolution,
                        s_relaxed=init.resolution if init.s_relaxed is None
                        else init.s_relaxed,
                        T=jnp.zeros((), dt) if init.T is None else init.T)
                inits.append(init)
            n_real = len(chunk)
            while len(padded) < C:   # short chunks replicated cell 0
                padded.append(padded[0])
                inits.append(inits[0])
            sys_batch = stack_systems(padded)
            init_batch = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *inits)
            res = solve(Problem(system=sys_batch, weights=[w] * C,
                                init=init_batch), spec)
            self.shapes.add((C, bucket))
            objs = np.asarray(res.objective[:n_real])
            for c, r in enumerate(chunk):
                n = r.sys.n
                a = res.allocation
                alloc = Allocation(
                    bandwidth=a.bandwidth[c, :n], power=a.power[c, :n],
                    freq=a.freq[c, :n], resolution=a.resolution[c, :n],
                    s_relaxed=None if a.s_relaxed is None
                    else a.s_relaxed[c, :n],
                    T=None if a.T is None else a.T[c])
                self._cache[r.cell_id] = (n, alloc)
                float(objs[c])   # the old CellResponse sync point

    # compile the bucket menu for BOTH paths once, outside every timed
    # replay: the first 4 * cells_per_batch requests cover all four
    # buckets exactly. The paths do NOT share compiled programs — the
    # monolith's eager-jnp operands carry weak_type leaves (python-float
    # scalars), the planner's numpy operands are strong-typed, and the
    # jit cache keys on weak_type.
    warm = pipe(1)
    for r in trace()[:4 * cells_per_batch]:
        warm.submit(r)
    warm.drain()
    warm_legacy = _LegacyAllocator()
    by_bucket = {}
    for r in trace()[:4 * cells_per_batch]:
        by_bucket.setdefault(bucket_size(r.sys.n, min_bucket), []).append(r)
    for b, chunk in sorted(by_bucket.items()):
        warm_legacy.solve_chunk(chunk, b)

    def replay_sync(arrivals):
        alloc = _LegacyAllocator()
        reqs = trace()
        done_t = np.full(n_req, np.nan)
        queues = {}
        i, completed = 0, 0
        t0 = time.monotonic()
        while completed < n_req:
            now = time.monotonic() - t0
            while i < n_req and arrivals[i] <= now:
                b = bucket_size(reqs[i].sys.n, min_bucket)
                queues.setdefault(b, []).append((i, reqs[i]))
                i += 1
            full = [b for b, q in queues.items()
                    if len(q) >= cells_per_batch]
            if full:
                b = full[0]
            elif i >= n_req and any(queues.values()):
                # end of trace: flush leftovers, still one chunk at a time
                b = max(queues, key=lambda k: len(queues[k]))
            else:
                time.sleep(5e-4)   # idle until the next arrival is due
                continue
            batch = queues[b][:cells_per_batch]
            queues[b] = queues[b][cells_per_batch:]
            alloc.solve_chunk([r for _, r in batch], b)
            stamp = time.monotonic() - t0
            for k, _ in batch:
                done_t[k] = stamp
            completed += len(batch)
        lat = done_t - np.asarray(arrivals)
        wall = float(np.max(done_t))
        assert len(alloc.shapes) <= 4, alloc.shapes
        return dict(lat=lat, req_s=n_req / wall, wall=wall,
                    **_lat_pcts(lat))

    def replay(arrivals, depth):
        p = pipe(depth)
        reqs = trace()
        futs = [None] * n_req
        done_t = np.full(n_req, np.nan)
        open_idx = set(range(n_req))
        i = 0
        t0 = time.monotonic()
        while open_idx:
            now = time.monotonic() - t0
            n_new = 0
            while i < n_req and arrivals[i] <= now:
                futs[i] = p.submit(reqs[i])
                i += 1
                n_new += 1
            p.pump(force=(i >= n_req))
            if i >= n_req and p.in_flight:
                # no more arrivals: block on the oldest open future so
                # completions keep getting per-batch timestamps
                j = min(k for k in open_idx if futs[k].dispatched)
                futs[j].result()
            stamp = time.monotonic() - t0
            resolved = [k for k in open_idx
                        if futs[k] is not None and futs[k].done()]
            for k in resolved:
                done_t[k] = stamp
                open_idx.discard(k)
            if not resolved and not n_new and i < n_req:
                time.sleep(5e-4)   # idle until the next arrival is due
        lat = done_t - np.asarray(arrivals)
        wall = float(np.max(done_t))
        assert len(p.compiled_shapes) <= 4, p.compiled_shapes
        return dict(lat=lat, req_s=n_req / wall, wall=wall,
                    **_lat_pcts(lat))

    # the pipelined drain wall calibrates the arrival span: arrivals must
    # outpace the FASTER path so both replays measure capacity, not the
    # arrival rate
    t0 = time.monotonic()
    replay(np.zeros(n_req), 2)
    span = 0.5 * (time.monotonic() - t0)

    rng = np.random.RandomState(3)
    ia = rng.exponential(1.0, n_req)
    arrivals = dict(
        poisson=np.cumsum(ia) * (span / np.sum(ia)),
        bursty=np.repeat(np.arange(8), n_req // 8) * (span / 8))

    for trace_name, arr in arrivals.items():
        out_sync = replay_sync(arr)
        out_pipe = replay(arr, 2)
        for tag, out in (("sync", out_sync), ("pipelined", out_pipe)):
            # metric plane: the same latencies land in the global registry
            # so --metrics exports them with derived percentiles
            obs.REGISTRY.histogram("serve_latency_seconds",
                                   trace=trace_name, path=tag
                                   ).observe_many(float(x)
                                                  for x in out["lat"])
            extra = ""
            if tag == "pipelined":
                speedup = out["req_s"] / out_sync["req_s"]
                extra = f";speedup_vs_sync={speedup:.2f}x"
            t0 = time.time()
            _row(f"serve_latency.{trace_name}.{tag}.R{n_req}",
                 t0, t0 + out["wall"],
                 f"p50_ms={1e3 * out['p50']:.0f};"
                 f"p99_ms={1e3 * out['p99']:.0f};"
                 f"req_s={out['req_s']:.1f}{extra}")


def obs_overhead():
    """Telemetry overhead acceptance (the `repro.obs` rows): one saturated
    serving trace replayed under three recorder arms — off (the default
    no-op), on (a memory recorder), jsonl (a streaming `JsonlRecorder`) —
    for Poisson and bursty arrivals. Rows carry req/s plus
    histogram-derived p50/p99 and the enabled arms' measured slowdown vs
    the off arm.

    The hard gate is the *no-op* overhead: the measured per-call cost of
    a disabled span/point site times the trace's telemetry site count
    must stay under 2% of the off arm's wall time (asserted here and in
    tests/test_obs.py). The enabled arms are informational — they pay for
    real event capture."""
    import os
    import tempfile

    from repro.region import AllocationRequest, MaxWait, RegionPipeline

    n_req, cells_per_batch, min_bucket = 64, 8, 16
    spec = SolverSpec(max_iters=8, tol=1e-4)
    w = Weights(0.5, 0.5, 1.0)
    sizes = [12, 24]
    key = jax.random.PRNGKey(71)
    systems = [make_system(jax.random.fold_in(key, i),
                           n_devices=sizes[i % len(sizes)])
               for i in range(n_req)]

    def pipe():
        return RegionPipeline(w, cells_per_batch=cells_per_batch,
                              min_bucket=min_bucket, spec=spec,
                              policy=MaxWait(0.02), max_in_flight=2)

    def trace():
        return [AllocationRequest(cell_id=i, sys=systems[i])
                for i in range(n_req)]

    def replay(arrivals):
        p = pipe()
        reqs = trace()
        futs = [None] * n_req
        done_t = np.full(n_req, np.nan)
        open_idx = set(range(n_req))
        i = 0
        t0 = time.monotonic()
        while open_idx:
            now = time.monotonic() - t0
            n_new = 0
            while i < n_req and arrivals[i] <= now:
                futs[i] = p.submit(reqs[i])
                i += 1
                n_new += 1
            p.pump(force=(i >= n_req))
            if i >= n_req and p.in_flight:
                j = min(k for k in open_idx if futs[k].dispatched)
                futs[j].result()
            stamp = time.monotonic() - t0
            resolved = [k for k in open_idx
                        if futs[k] is not None and futs[k].done()]
            for k in resolved:
                done_t[k] = stamp
                open_idx.discard(k)
            if not resolved and not n_new and i < n_req:
                time.sleep(5e-4)   # idle until the next arrival is due
        lat = done_t - np.asarray(arrivals)
        wall = float(np.max(done_t))
        return dict(lat=lat, req_s=n_req / wall, wall=wall,
                    **_lat_pcts(lat))

    # compile the bucket menu + warm every cache outside the timed arms,
    # then calibrate the arrival span off a saturated drain
    replay(np.zeros(n_req))
    t0 = time.monotonic()
    replay(np.zeros(n_req))
    span = 0.5 * (time.monotonic() - t0)

    rng = np.random.RandomState(5)
    ia = rng.exponential(1.0, n_req)
    arrivals = dict(
        poisson=np.cumsum(ia) * (span / np.sum(ia)),
        bursty=np.repeat(np.arange(4), n_req // 4) * (span / 4))

    # measured per-call cost of a DISABLED span/point site, and the site
    # count of one enabled trace: together they bound the no-op overhead
    reps = 20000
    t0 = time.monotonic()
    for _ in range(reps):
        with obs.span("x"):
            pass
        obs.point("x")
    per_site = (time.monotonic() - t0) / (2 * reps)
    rec = obs.MemoryRecorder()
    with obs.recording(rec):
        replay(np.zeros(n_req))
    n_sites = len(rec.events)

    tmp = tempfile.mkdtemp(prefix="obs_overhead_")
    for trace_name, arr in arrivals.items():
        out_off = replay(arr)
        with obs.recording(obs.MemoryRecorder()):
            out_on = replay(arr)
        with obs.recording(obs.JsonlRecorder(
                os.path.join(tmp, f"{trace_name}.jsonl"))):
            out_jsonl = replay(arr)

        noop_overhead = n_sites * per_site / out_off["wall"]
        assert noop_overhead < 0.02, (
            f"no-op telemetry overhead {noop_overhead:.2%} "
            f"({n_sites} sites x {per_site * 1e9:.0f}ns) >= 2%")

        for tag, out in (("off", out_off), ("on", out_on),
                         ("jsonl", out_jsonl)):
            obs.REGISTRY.histogram("obs_overhead_latency_seconds",
                                   trace=trace_name, recorder=tag
                                   ).observe_many(float(x)
                                                  for x in out["lat"])
            extra = (f";noop_overhead_pct={100 * noop_overhead:.3f}"
                     if tag == "off" else
                     f";slowdown_vs_off="
                     f"{out_off['req_s'] / out['req_s']:.2f}x")
            t0 = time.time()
            _row(f"obs_overhead.{trace_name}.{tag}.R{n_req}",
                 t0, t0 + out["wall"],
                 f"p50_ms={1e3 * out['p50']:.0f};"
                 f"p99_ms={1e3 * out['p99']:.0f};"
                 f"req_s={out['req_s']:.1f}{extra}")


def slo():
    """SLO plane acceptance rows: a deadlined serving trace replayed
    through the pipeline with the default SLO set evaluated live over the
    global registry (the same wiring `examples/serve_observed.py` and the
    `/slo` endpoint use). The row's derived fields are the gate inputs for
    `compare.py --slo`: `slo_breaches` (total breach verdicts) and one
    `slo_<name>_ok` flag per objective (1 = verdict was not a breach), so
    a baseline-vs-fresh comparison fails --strict when an objective that
    used to hold starts breaching."""
    from repro.region import AllocationRequest, MaxWait, RegionPipeline

    n_req, cells_per_batch, min_bucket = 48, 8, 16
    spec = SolverSpec(max_iters=8, tol=1e-4)
    w = Weights(0.5, 0.5, 1.0)
    sizes = [12, 24]
    key = jax.random.PRNGKey(81)
    systems = [make_system(jax.random.fold_in(key, i),
                           n_devices=sizes[i % len(sizes)])
               for i in range(n_req)]

    def pipe():
        return RegionPipeline(w, cells_per_batch=cells_per_batch,
                              min_bucket=min_bucket, spec=spec,
                              policy=MaxWait(0.02), max_in_flight=2)

    def replay(deadline_budget=None, plane=None):
        p = pipe()
        t_start = time.monotonic()
        futs = []
        for i in range(n_req):
            dl = None if deadline_budget is None \
                else time.monotonic() + deadline_budget
            futs.append(p.submit(AllocationRequest(
                cell_id=i, sys=systems[i], deadline=dl)))
            if i % cells_per_batch == 0:
                p.poll()
                if plane is not None:
                    plane.observe()
        p.drain()
        return time.monotonic() - t_start, p.stats

    replay()   # compile the bucket menu + warm caches, no deadlines

    plane = obs.SloPlane(obs.default_slos(
        latency_threshold_s=2.0, latency_objective=0.9,
        deadline_objective=0.9, convergence_objective=0.5))
    plane.observe()
    t0 = time.time()
    wall, stats = replay(deadline_budget=10.0, plane=plane)
    verdicts = plane.check()
    breaches = sum(v["verdict"] == "breach" for v in verdicts)
    flags = ";".join(
        f"slo_{v['name']}_ok={0 if v['verdict'] == 'breach' else 1}"
        for v in verdicts)
    hit = stats["deadline_hits"]
    total = stats["deadline_requests"]
    _row(f"slo.serve.R{n_req}", t0, t0 + wall,
         f"slo_breaches={breaches};{flags};"
         f"deadline_hit_rate={hit / max(total, 1):.3f};"
         f"cells_converged={stats['cells_converged']}/"
         f"{stats['cells_solved']}")


def xla_cost():
    """XLA compiled-cost trajectory rows: AOT-lower the solver's
    single-cell and fleet programs and record the backend cost model's
    FLOPs / bytes-accessed per compiled shape (`repro.obs.profile`).
    Nothing executes — the rows track compute-per-shape across PRs, so an
    algorithmic change that bloats the compiled program shows up in the
    BENCH artifact even when wall time hides it."""
    from repro.obs import profile

    spec = SolverSpec(max_iters=8, tol=1e-4)
    w = Weights(0.5, 0.5, 1.0)
    key = jax.random.PRNGKey(91)

    shapes = [("bcd", make_system(key, n_devices=N_DEV), f"N{N_DEV}"),
              ("fleet", make_fleet(jax.random.fold_in(key, 1), n_cells=8,
                                   n_devices=N_DEV), f"C8.N{N_DEV}")]
    for kind, sysp, tag in shapes:
        t0 = time.time()
        cost = profile.solve_cost(Problem(system=sysp, weights=w),
                                  spec=spec)
        t1 = time.time()
        if cost is None:
            _row(f"xla_cost.{kind}.{tag}", t0, t1, "flops=nan;bytes=nan")
            continue
        _row(f"xla_cost.{kind}.{tag}", t0, t1,
             f"flops={cost['flops']:.4g};bytes={cost['bytes_accessed']:.4g}")


def assoc_mobility():
    """Cross-cell association + mobility churn acceptance rows.

    Row 1: BCD-over-association vs the static nearest-cell (max-gain)
    baseline on a bandwidth-heterogeneous region — the realized global
    weighted objective after per-cell re-solves must improve on the
    baseline (objectives[0] IS the nearest-assignment solve, so the win is
    measured on identical solver settings).

    Row 2: a seeded random-waypoint trace replayed through
    `RegionAllocator` — handovers purge warm-cache entries on both sides
    of each move, and the row records the measured hit rate and mean
    warm/cold re-solve iterations under churn, with the compiled batch
    shape count bounded (<= 5)."""
    from repro import (AssocConfig, MobilityConfig, RegionAllocator,
                      make_multicell, replay_mobility, simulate_mobility)

    C, N, R = 6, 48, 10
    w = Weights(0.5, 0.5, 5.0)
    spec = SolverSpec(max_iters=6, tol=1e-4)
    key = jax.random.PRNGKey(71)
    bands = [5e6 * (1 + 7 * c / (C - 1)) for c in range(C)]
    sysb = make_multicell(key, n_cells=C, n_devices=N,
                          bandwidth_total=bands)

    t0 = time.time()
    res = solve(Problem(system=sysb, weights=w,
                        assoc=AssocConfig(outer_iters=8)), spec)
    t1 = time.time()
    nearest_obj, assoc_obj = res.objectives[0], res.objective
    assert assoc_obj <= nearest_obj
    win = 100.0 * (nearest_obj - assoc_obj) / abs(nearest_obj)
    _row(f"assoc_mobility.bcd_vs_nearest.C{C}.N{N}", t0, t1,
         f"nearest_obj={nearest_obj:.4g};assoc_obj={assoc_obj:.4g};"
         f"win={win:.1f}%;outer_iters={res.outer_iters};"
         f"moves={sum(res.moves)}")

    # drift_rho=0.98: step-to-step shadowing stays correlated so handovers
    # come from movement, not fading noise — the hit rate under churn is
    # then a real cache measurement instead of ~0
    cfg = MobilityConfig(model="rwp", steps=R, dt=2.0, v_min=2.0,
                         v_max=20.0, drift_rho=0.98)
    trace = simulate_mobility(jax.random.PRNGKey(72), n_devices=N,
                              n_cells=C, cfg=cfg)
    base = make_system(jax.random.PRNGKey(73), n_devices=N)
    svc = RegionAllocator(w, cells_per_batch=4, min_bucket=16, spec=spec)
    t0 = time.time()
    rep = replay_mobility(svc, trace, base)
    t1 = time.time()
    assert rep["handover_purges"] <= 2 * rep["handovers"]
    assert len(rep["compiled_shapes"]) <= 5, rep["compiled_shapes"]
    _row(f"assoc_mobility.churn.R{R}.C{C}.N{N}", t0, t1,
         f"handovers={rep['handovers']};purges={rep['handover_purges']};"
         f"hit_rate={rep['hit_rate']:.2f};"
         f"warm_iters={rep['mean_warm_iters']:.1f};"
         f"cold_iters={rep['mean_cold_iters']:.1f};"
         f"shapes={len(rep['compiled_shapes'])}")


def sp1_sweep_scale():
    """SP1 engines head-to-head: the batched T-grid dual sweep vs the nested
    56x56 bisection oracle, one solve at region scale (per-iteration SP1 cost
    inside the fleet BCD). Reports the wall-time ratio and the relative
    deadline parity between the two engines."""
    from repro.core.accuracy import default_accuracy
    from repro.core.sp1 import solve_sp1

    N = 1 << 15
    key = jax.random.PRNGKey(41)
    sysp = make_system(key, n_devices=N, bandwidth_total=20e6 * N / 50)
    acc = default_accuracy()
    w = Weights(0.5, 0.5, 1.0).normalized()
    B = jnp.full((N,), sysp.bandwidth_total / N)
    p = jnp.full((N,), sysp.p_max)

    walls, T_by = {}, {}
    for method in ("sweep", "bisect"):
        out = solve_sp1(sysp, w, acc, B, p, method=method)   # compile
        jax.block_until_ready(out[0])
        t0 = time.time()
        out = solve_sp1(sysp, w, acc, B, p, method=method)
        jax.block_until_ready(out[0])
        walls[method] = time.time() - t0
        T_by[method] = float(out[3])
    rel = abs(T_by["sweep"] - T_by["bisect"]) / abs(T_by["bisect"])
    t0 = time.time()
    _row(f"sp1_sweep.N{N}", t0, t0 + walls["sweep"],
         f"sweep_ms={1e3 * walls['sweep']:.1f};"
         f"bisect_ms={1e3 * walls['bisect']:.1f};"
         f"speedup={walls['bisect'] / max(walls['sweep'], 1e-9):.1f}x;"
         f"T_rel_err={rel:.2e}")


def autodiff():
    """Implicit-KKT gradient overhead (PR 10): `diff.solve_and_grad` vs the
    forward `solve()` on the same spec/shape. The differentiable path
    re-runs the fixed point under one linearization and pulls 4 metric
    cotangents through the Neumann adjoint, so the budget is <= 3x a
    forward solve — exported as an `slo_grad_overhead_ok` flag for the
    compare.py --slo/--strict gate. A second row times the 17-point
    Pareto weight sweep (one vmapped fleet program)."""
    from repro.diff import pareto_sweep, solve_and_grad

    key = jax.random.PRNGKey(7)
    sysp = make_system(key, n_devices=N_DEV)
    prob = Problem(system=sysp, weights=Weights(0.5, 0.5, 0.3))
    spec = SolverSpec(sp1_method="bisect", tol=1e-5, max_iters=200)

    r = solve(prob, spec)                                  # compile both
    jax.block_until_ready(r.objective)
    g = solve_and_grad(prob, spec)
    jax.block_until_ready(g.value["objective"])

    reps = 5
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(solve(prob, spec).objective)
    fwd_s = (time.time() - t0) / reps
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(solve_and_grad(prob, spec).value["objective"])
    grad_s = (time.time() - t0) / reps
    overhead = grad_s / max(fwd_s, 1e-9)
    _row(f"autodiff.grad_overhead.N{N_DEV}", t0, t0 + grad_s,
         f"fwd_ms={1e3 * fwd_s:.2f};grad_ms={1e3 * grad_s:.2f};"
         f"overhead={overhead:.2f}x;"
         f"slo_grad_overhead_ok={1 if overhead <= 3.0 else 0}")

    t0 = time.time()
    sweep = pareto_sweep(prob, spec, n=17)
    t1 = time.time()
    _row("autodiff.pareto_sweep.n17", t0, t1,
         f"front_points={int(sweep.front.sum())};"
         f"converged={int(np.asarray(sweep.converged).sum())}/17")


def roofline_table():
    """Dry-run roofline summary (reads dryrun_baseline.jsonl if present)."""
    import os

    from repro.roofline import full_table

    path = "dryrun_baseline.jsonl" if os.path.exists("dryrun_baseline.jsonl") else None
    t0 = time.time()
    rows = full_table(path)
    for r in rows:
        _row(f"roofline.{r['arch']}.{r['shape']}", t0, time.time(),
             f"dominant={r['dominant']};tc={r['t_compute_s']:.2e};"
             f"tm={r['t_memory_s']:.2e};tx={r['t_collective_s']:.2e};"
             f"useful={r['useful_ratio']:.2f}")


def ablations():
    """Component ablations of the allocator (beyond-paper analyses)."""
    from repro.core.accuracy import log_fit
    from repro.core.baselines import scheme1

    # (a) SP2 engine: exact direct vs paper's Algorithm 1 (damped)
    key = jax.random.PRNGKey(21)
    sysp = make_system(key, n_devices=N_DEV)
    w = Weights(0.5, 0.5, 1.0)
    t0 = time.time()
    r_dir = solve(Problem(system=sysp, weights=w),
                  SolverSpec(max_iters=6, sp2_method="direct"))
    r_jng = solve(Problem(system=sysp, weights=w),
                  SolverSpec(max_iters=6, sp2_method="jong"))
    _row("ablation.sp2_engine", t0, time.time(),
         f"direct_E={r_dir.history[-1]['energy']:.4g}J;"
         f"jong_E={r_jng.history[-1]['energy']:.4g}J")

    # (b) deadline split optimization on/off (the BCD deadlock fix)
    t0 = time.time()
    with_split = solve(Problem(system=sysp, weights=Weights(0.99, 0.01, 0.0),
                               deadline=150.0), SolverSpec(max_iters=6))
    s1 = scheme1(sysp, Weights(0.99, 0.01, 0.0), 150.0)
    _row("ablation.deadline_split", t0, time.time(),
         f"with_split={float(total_energy(sysp, with_split.allocation)):.4g}J;"
         f"stuck_baseline~scheme1={float(total_energy(sysp, s1)):.4g}J")

    # (b2) SP2-direct dual search: Newton polish on the pmin-branch
    # stationarity vs the bisection-only carried bracket (PR 10 satellite;
    # gated by the measured dE/dB eval counter the ledger already carries)
    from repro.core.energy import t_cmp
    from repro.core.sp2 import _sp2_direct_impl, r_min

    sys_n = make_system(jax.random.PRNGKey(11), n_devices=50,
                        bandwidth_total=20e6)
    f_n = jnp.full((50,), 1e9)
    s_n = jnp.full((50,), 320.0)
    rmin = r_min(sys_n, f_n, s_n,
                 jnp.asarray(float(jnp.max(t_cmp(sys_n, f_n, s_n))) * 1.1))
    t0 = time.time()
    _, _, ev_newton = _sp2_direct_impl(sys_n, rmin, True, True)
    _, _, ev_bisect = _sp2_direct_impl(sys_n, rmin, True, False)
    _row("ablation.sp2_newton", t0, time.time(),
         f"newton_evals={int(ev_newton)};bisect_evals={int(ev_bisect)};"
         f"saved={int(ev_bisect) - int(ev_newton)}")

    # (c) accuracy model: linear (paper) vs concave log fit
    t0 = time.time()
    r_lin = solve(Problem(system=sysp, weights=Weights(0.5, 0.5, 40.0)),
                  SolverSpec(max_iters=6))
    r_log = solve(Problem(system=sysp, weights=Weights(0.5, 0.5, 40.0),
                          acc=log_fit()), SolverSpec(max_iters=6))
    _row("ablation.accuracy_model", t0, time.time(),
         f"linear_mean_s={float(jnp.mean(r_lin.allocation.resolution)):.0f}px;"
         f"logfit_mean_s={float(jnp.mean(r_log.allocation.resolution)):.0f}px")


BENCHES = {
    "fig3": fig3_weight_sweep_power,
    "fig4": fig4_weight_sweep_freq,
    "fig5": fig5_rho_sweep,
    "fig7": fig7_rho_vs_fl_accuracy,
    "fig8": fig8_joint_vs_single,
    "fig9": fig9_vs_scheme1,
    "scaling": table_allocator_scaling,
    "fleet": fleet_scale,
    "region": region_scale,
    "rounds": rounds_dynamics,
    "serve_latency": serve_latency,
    "obs_overhead": obs_overhead,
    "slo": slo,
    "xla_cost": xla_cost,
    "assoc_mobility": assoc_mobility,
    "sp1_sweep": sp1_sweep_scale,
    "autodiff": autodiff,
    "ablations": ablations,
    "roofline": roofline_table,
}


def main() -> None:
    use_compile_cache()
    args = sys.argv[1:]
    json_path = None
    if "--json" in args:
        i = args.index("--json")
        if i + 1 >= len(args):
            sys.exit("--json requires a path argument")
        json_path = args[i + 1]
        args = args[:i] + args[i + 2:]
    metrics_path = None
    if "--metrics" in args:
        i = args.index("--metrics")
        if i + 1 >= len(args):
            sys.exit("--metrics requires a path argument")
        metrics_path = args[i + 1]
        args = args[:i] + args[i + 2:]
    which = args or list(BENCHES)
    unknown = [n for n in which if n not in BENCHES]
    if unknown:
        sys.exit(f"unknown bench {unknown}; available: {', '.join(BENCHES)}")
    print("name,us_per_call,derived")
    for name in which:
        BENCHES[name]()
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(dict(rows=_ROWS, benches=which), fh, indent=1)
        print(f"# wrote {len(_ROWS)} rows to {json_path}", file=sys.stderr)
    if metrics_path:
        n = obs.write_metrics_jsonl(metrics_path)
        print(f"# wrote {n} metrics to {metrics_path}", file=sys.stderr)


if __name__ == "__main__":
    main()
