"""Closed-loop re-plans: one client re-plans a whole fleet (or a region on
a mesh) back to back. A re-plan ends when `block_until_ready` returns on
its allocation and objective.

Cold traffic cycles through fleets drawn in set-up. Warm traffic walks
the drifted rounds of one fleet forward and back, each re-plan starting
from the previous one's allocation.

A runner is found by its traffic's `kind` (`runners/<kind>.py`) and gives
`run`, which drives the window and returns its `Record`, and `end_to_end`,
which reduces that record to the end-to-end metrics it can report.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import device, generate, program
from harness.record import Record, Tracer, phase


def _problem(f: generate.Fleet, cfg: dict, init=None) -> dict:
    """The problem a re-plan solved, on the host; a warm re-plan's problem
    includes the allocation it continued from."""
    C, N = f.weights.shape[0], int(cfg["devices"])
    out = dict(arrays=jax.device_get(f.arrays), active=np.ones((C, N), bool),
               scalars=f.scalars, weights=f.weights,
               accuracy=cfg["accuracy"], menu=cfg["fl"]["resolutions"])
    if init is not None:
        out["init"] = dict(B=np.asarray(init.bandwidth),
                           p=np.asarray(init.power),
                           warm=np.ones((C,), bool))
    return out


def end_to_end(rec: Record) -> dict:
    """`setup_s`, and `replan_ms`: the window over the re-plans it
    completed."""
    out = {"setup_s": rec.setup_s}
    if rec.replans:
        out["replan_ms"] = 1e3 * rec.window_s / rec.replans
    return out


def run(cell, seed: int, seconds: float, traced: bool, t_process: float,
        devs: list) -> Record:
    cfg, tr = cell.config, cell.traffic
    spec, acc = program.spec(cfg), program.accuracy(cfg)
    menu = cfg["fl"]["resolutions"]
    chips = int(cfg.get("mesh_chips", 1))
    mesh = program.mesh(chips) if chips > 1 else None
    fleets = generate.fleets(cfg, tr, seed)
    systems, weights = [], []
    for f in fleets:
        sys = program.system(f.arrays, {k: jnp.asarray(v) for k, v in
                                        f.scalars.items()}, menu)
        systems.append(program.place(sys, mesh) if mesh is not None else sys)
        weights.append(program.weights(f.weights))
    warm = bool(tr.get("warm"))
    order = generate.visit_order(tr, len(fleets), 1 << 20)
    rng = generate.host_rng(seed, 8)
    keep = int(tr["check_replans"])

    def replan(i, init):
        k = int(order[i])
        t0 = time.perf_counter()
        with phase("enqueue", tracer.on):
            res = program.solve(systems[k], weights[k], spec, acc,
                                init=init, mesh_=mesh)
        t1 = time.perf_counter()
        with phase("block", tracer.on):
            jax.block_until_ready((res.objective, res.allocation))
        return res, t1 - t0

    # set-up: the window's program (and, for warm traffic, the cold solve
    # that starts the walk) compiled and run twice
    tracer = Tracer(traced, float(tr["trace_seconds"]))
    init = None
    if warm:
        res, _ = replan(0, None)
        init = res.allocation
    i = 1 if warm else 0
    for _ in range(2):
        res, _ = replan(i, init)
        init = res.allocation if warm else None
        i += 1
    counter = device.CompileCounter()
    rec = Record(kind="replan", chips=chips, device_kind=devs[0].device_kind)

    enq, ctr, kept = [], [], []
    traced_steps = 0
    # what set-up built lives for the whole run: keep the collector from
    # scanning it again and again inside the window
    gc.collect()
    gc.freeze()
    counter.active = True
    t_open = time.perf_counter()
    rec.setup_s = t_open - t_process
    n = 0
    while True:
        elapsed = time.perf_counter() - t_open
        if elapsed >= seconds:
            break
        tracer.maybe_start(elapsed, seconds)
        start = init
        res, dt = replan(i, init)
        init = res.allocation if warm else None
        enq.append(dt)
        ctr.append(res.counters.data)
        n += 1
        traced_steps += tracer.on
        # reservoir sample of the answers to check, uniform over the window
        j = len(kept) if len(kept) < keep else int(rng.integers(0, n))
        if j < keep:
            entry = (i, int(order[i]), start, program.answer(res))
            if j == len(kept):
                kept.append(entry)
            else:
                kept[j] = entry
        i += 1
    rec.window_s = time.perf_counter() - t_open
    counter.active = False
    rec.trace = tracer.stop()
    rec.traced_steps = traced_steps
    rec.compiles_in_window = counter.count
    rec.memory_peak_bytes = device.memory_peak_bytes(devs)
    rec.replans = rec.attempted = n
    rec.enqueue_s = np.asarray(enq)
    rec.counters = np.asarray(jax.device_get(jnp.stack(ctr)))
    rec.counter_columns = tuple(res.counters.columns)
    for visit, k, start, ans in kept:
        rec.checks.append(((visit if warm else k),
                           _problem(fleets[k], cfg, start),
                           {a: np.asarray(v) for a, v in
                            jax.device_get(ans).items()}))
    return rec
