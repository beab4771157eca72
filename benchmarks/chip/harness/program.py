"""The program under test, as the benchmark drives it.

The only module of the harness that imports `repro`. It turns generated
arrays into the program's `SystemParams`, and calls the program's own
entries: `repro.solve` for a re-plan and `RegionPipeline` for serving.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

import repro
from repro.core.accuracy import LinearAccuracy
from repro.core.types import Allocation, SystemParams, Weights
from repro.region import AllocationRequest, MaxWait, RegionPipeline

ARRAYS = ("gain", "cycles", "samples", "bits")


def spec(cfg: dict) -> repro.SolverSpec:
    s = cfg["solver"]
    return repro.SolverSpec(max_iters=int(s["max_iters"]), tol=float(s["tol"]))


def accuracy(cfg: dict) -> LinearAccuracy:
    (s0, s1), (a0, a1) = cfg["accuracy"]["resolutions"], cfg["accuracy"]["map"]
    return LinearAccuracy(slope=(a1 - a0) / (s1 - s0), s_lo=s0, a_lo=a0)


def system(arrays: dict, scalars: dict, menu) -> SystemParams:
    return SystemParams(**{k: arrays[k] for k in ARRAYS},
                        **dict(scalars),
                        resolutions=tuple(float(m) for m in menu))


def weights(rows) -> list:
    """Per-cell weights as the program's per-cell sequence of `Weights`."""
    return [Weights(float(a), float(b), float(c)) for a, b, c in rows]


def mesh(chips: int):
    from repro.region import region_mesh

    return region_mesh(chips)


def place(sys: SystemParams, mesh_):
    """Shard a stacked system's cell axis over the mesh, as the program's
    region path lays it out."""
    from repro.region.mesh import place_cells

    return place_cells(sys, mesh_)


def solve(sys: SystemParams, w: list, spec_: repro.SolverSpec, acc,
          init: Optional[Allocation] = None, mesh_=None):
    """One re-plan through `repro.solve`; returns its `FleetResult`
    (unmaterialized device arrays)."""
    res = repro.solve(repro.Problem(system=sys, weights=w, acc=acc,
                                    init=init, mesh=mesh_), spec_)
    return res.fleet if mesh_ is not None else res


def answer(fleet) -> dict:
    """The allocation a re-plan produced, as B, p, f, s device arrays."""
    a = fleet.allocation
    return dict(B=a.bandwidth, p=a.power, f=a.freq, s=a.resolution)


def pipeline(cfg: dict, spec_: repro.SolverSpec, acc) -> RegionPipeline:
    p = cfg["pipeline"]
    return RegionPipeline(Weights(0.5, 0.5, 1.0), acc=acc,
                          cells_per_batch=int(p["cells_per_batch"]),
                          min_bucket=int(p["min_bucket"]), spec=spec_,
                          policy=MaxWait(float(p["max_wait_s"])),
                          max_in_flight=int(p["max_in_flight"]))


def request(cell_id, arrays: dict, scalars: dict, w, menu):
    return AllocationRequest(
        cell_id=cell_id,
        sys=system({k: np.asarray(arrays[k], np.float32) for k in ARRAYS},
                   {k: float(v) for k, v in scalars.items()}, menu),
        w=Weights(float(w[0]), float(w[1]), float(w[2])))


def response_answer(resp) -> dict:
    a = resp.allocation
    return dict(B=a.bandwidth, p=a.power, f=a.freq, s=a.resolution)


def plan_start(plan, lane: int):
    """The allocation a batch plan warm-started a lane from (B, p, host
    numpy), or None for a cold lane."""
    if not plan.warm[lane]:
        return None
    a = plan.init_batch
    return dict(B=np.asarray(a.bandwidth)[lane], p=np.asarray(a.power)[lane])
