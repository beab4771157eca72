"""Open-loop serving through the region pipeline.

Requests are sent when due, whether or not earlier ones are answered.
The loop is the caller's event loop of the pipeline's asynchronous
surface: `submit` when a request is due, `poll` to let the batch policy
close and dispatch batches, and a batch's futures are claimed as soon as
its device arrays are ready. A request's latency runs from its due time
to its response being on the host.

A runner is found by its traffic's `kind` (`runners/<kind>.py`): `run`
drives the window, `end_to_end` reduces its `Record`.
"""
from __future__ import annotations

import gc
import time
from collections import deque

import numpy as np

from harness import device, generate, program
from harness.record import Record, Tracer, phase

STAGES = ("queue_wait", "plan", "dispatch", "gather")


def _warm_requests(cfg: dict, seed: int):
    """`cells_per_batch` requests in each bucket the population reaches,
    under cell ids outside it: every shape the window will use."""
    pop, pipe = cfg["population"], cfg["pipeline"]
    lo, hi = int(pop["devices"][0]), int(pop["devices"][1])
    mb, cpb = int(pipe["min_bucket"]), int(pipe["cells_per_batch"])
    sizes, b = [], mb
    while True:
        top = min(b, hi)
        if top >= lo:
            sizes.append(top)
        if b >= hi:
            break
        b *= 2
    d = generate.draw_cells(generate.prng_key(seed, 9), (len(sizes), hi), cfg)
    d = {k: np.asarray(v) for k, v in d.items()}
    out = []
    for j, n in enumerate(sizes):
        scal = {k: float(v[0]) for k, v in
                generate.cell_scalars(cfg, np.asarray([n])).items()}
        for c in range(cpb):
            out.append(program.request(
                -1 - j * cpb - c, {k: d[k][j, :n] for k in d}, scal,
                (0.5, 0.5, 1.0), cfg["fl"]["resolutions"]))
    return out


def _ready(batch) -> bool:
    return all(x.is_ready() for x in (batch.result.objective,
                                      batch.result.allocation.bandwidth))


def end_to_end(rec: Record) -> dict:
    """`setup_s`; `alloc_p95_ms`, the 95th percentile latency over every
    request due in the window (one never answered counts as infinite); and
    `allocs_per_s`, the requests answered inside the window over it."""
    out = {"setup_s": rec.setup_s}
    if rec.attempted:
        out["alloc_p95_ms"] = 1e3 * float(np.percentile(rec.latency_s, 95))
        out["allocs_per_s"] = rec.completed_in_window / rec.window_s
    return out


def run(cell, seed: int, seconds: float, traced: bool, t_process: float,
        devs: list) -> Record:
    cfg, tr = cell.config, cell.traffic
    spec, acc = program.spec(cfg), program.accuracy(cfg)
    menu = cfg["fl"]["resolutions"]
    stream = generate.requests(cfg, tr, seed, seconds)
    reqs = [program.request(r.cell_id, r.arrays, r.scalars, r.weights, menu)
            for r in stream]
    due = np.asarray([r.due for r in stream])
    pipe = program.pipeline(cfg, spec, acc)
    cpb = int(cfg["pipeline"]["cells_per_batch"])
    for _ in range(2):
        for r in _warm_requests(cfg, seed):
            pipe.submit(r)
        pipe.drain()
    marks = {s: len(pipe.clocks.samples(s)) for s in STAGES}
    tracer = Tracer(traced, float(tr["trace_seconds"]))
    counter = device.CompileCounter()
    rec = Record(kind="serve", chips=1, device_kind=devs[0].device_kind)

    K = len(reqs)
    futures = [None] * K
    sent = np.full(K, np.nan)
    done = np.full(K, np.nan)
    index = {}
    lanes = {}          # request -> (its batch plan, its lane)
    in_flight = deque()
    fills = []
    i = 0
    n_due = int(np.searchsorted(due, seconds))
    # what set-up built lives for the whole run: keep the collector from
    # scanning it again and again inside the window
    gc.collect()
    gc.freeze()
    counter.active = True
    t_open = time.perf_counter()
    rec.setup_s = t_open - t_process

    closing = False
    while True:
        now = time.perf_counter() - t_open
        if now >= seconds and not closing:
            # the window closes: send what was due in it, flush the queue
            closing = True
            counter.active = False
            rec.trace = tracer.stop()
            rec.memory_peak_bytes = device.memory_peak_bytes(devs)
        if closing and (i >= n_due and not pipe.pending and not in_flight
                        or now > seconds + 60.0):
            break
        if not closing:
            tracer.maybe_start(now, seconds)
        busy = False
        limit = seconds if closing else now
        with phase("submit", tracer.on):
            while i < K and due[i] < limit:
                futures[i] = pipe.submit(reqs[i])
                index[id(futures[i])] = i
                sent[i] = time.perf_counter() - t_open
                i += 1
                busy = True
        with phase("poll", tracer.on):
            batches = pipe.pump(force=True) if closing else pipe.poll()
        for b in batches:
            in_flight.append(b)
            fills.append(b.plan.n_real / cpb)
            for lane, fut in enumerate(b.pending):
                lanes[index[id(fut)]] = (b.plan, lane)
            busy = True
        # claim a batch once its arrays are ready (or the pipeline's depth
        # bound has already materialized it)
        while in_flight and (closing or in_flight[0].materialized
                             or _ready(in_flight[0])):
            batch = in_flight.popleft()
            with phase("materialize", tracer.on):
                batch.pending[0].result()
            stamp = time.perf_counter() - t_open
            for fut in batch.pending:
                done[index[id(fut)]] = stamp
            busy = True
        if not busy:
            nxt = due[i] - now if i < K else 0.0005
            with phase("idle", tracer.on):
                time.sleep(min(max(nxt, 0.0), 0.0005))
    rec.window_s = float(seconds)
    rec.compiles_in_window = counter.count
    rec.attempted = n_due
    lat = done[:n_due] - due[:n_due]
    rec.failed = int(np.sum(~np.isfinite(lat)))
    rec.latency_s = np.where(np.isfinite(lat), lat, np.inf)
    rec.completed_in_window = int(np.sum(done <= seconds))
    rec.due_s, rec.done_s = due[:n_due], done[:n_due]
    rec.lag_s = sent[:n_due] - due[:n_due]
    rec.stage_s = {s: pipe.clocks.samples(s)[marks[s]:] for s in STAGES}
    rec.batch_fill = np.asarray(fills)
    # a sample of the answered requests, drawn from the seed
    answered = np.flatnonzero(np.isfinite(done[:n_due]))
    rng = generate.host_rng(seed, 10)
    pick = np.sort(rng.choice(answered, size=min(answered.size,
                                                 int(tr["check_requests"])),
                              replace=False))
    if pick.size:
        rec.checks.append(("serve", *_check_batch(
            cfg, [stream[j] for j in pick],
            [program.response_answer(futures[j].result()) for j in pick],
            [lanes[j] for j in pick])))
    return rec


def _check_batch(cfg: dict, reqs: list, answers: list, lanes: list) -> tuple:
    """Requests of mixed pool sizes as one padded problem, and the answers
    padded alike (padding is masked out of every comparison). A request
    the pipeline warm-started carries the start its batch plan used."""
    C, N = len(reqs), max(r.n for r in reqs)
    arrays = {k: np.zeros((C, N), np.float32) for k in program.ARRAYS}
    arrays["gain"][:] = 1.0
    active = np.zeros((C, N), bool)
    ans = {k: np.zeros((C, N), np.float64) for k in ("B", "p", "f", "s")}
    init = dict(B=np.zeros((C, N)), p=np.zeros((C, N)),
                warm=np.zeros((C,), bool))
    for c, (r, a, (plan, lane)) in enumerate(zip(reqs, answers, lanes)):
        for k in program.ARRAYS:
            arrays[k][c, :r.n] = r.arrays[k]
        active[c, :r.n] = True
        for k in ans:
            ans[k][c, :r.n] = np.asarray(a[k])
        start = program.plan_start(plan, lane)
        if start is not None:
            init["warm"][c] = True
            init["B"][c, :r.n] = start["B"][:r.n]
            init["p"][c, :r.n] = start["p"][:r.n]
    scalars = {k: np.asarray([r.scalars[k] for r in reqs], np.float32)
               for k in reqs[0].scalars}
    problem = dict(arrays=arrays, active=active, scalars=scalars,
                   weights=np.stack([r.weights for r in reqs]),
                   accuracy=cfg["accuracy"], menu=cfg["fl"]["resolutions"],
                   init=init)
    return problem, ans
