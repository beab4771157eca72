"""Dispatch layer: launch a planned batch and return it *in flight*.

JAX dispatch is asynchronous: `solve()` on a planned batch enqueues the
compiled computation and returns device arrays immediately — futures, not
values. The old monolith squandered that by calling `np.asarray` on each
chunk's results before assembling the next one, serializing host assembly
behind device compute. This layer keeps the results as device futures
inside an `InFlightBatch`; the completion layer materializes them later
(one blocking gather per batch), so the pipeline can plan/stack/enqueue
batch k+1 on the host while batch k is still computing — double-buffered
batches with `RegionPipeline.max_in_flight` bounding the queue depth.

Host time spent tracing/enqueueing the solve is charged to the
`StageClocks` "dispatch" stage.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, List, Optional

from repro.api import Problem, SolverSpec, solve
from repro.core.accuracy import AccuracyModel, default_accuracy
from repro.core.bcd import FleetResult

from .admission import StageClocks
from .planning import BatchPlan


@dataclasses.dataclass
class InFlightBatch:
    """A dispatched batch whose results are still device futures.

    `result` leaves are unmaterialized device arrays; `pending` holds the
    `PendingResponse` futures bound to the plan's real lanes (aligned by
    index). `seq` is the dispatch order — the completion order the
    synchronous facade reproduces."""
    plan: BatchPlan
    result: FleetResult
    seq: int
    pending: List[Any] = dataclasses.field(default_factory=list)
    materialized: bool = False


class Dispatcher:
    """Run planned batches through the one `solve()` dispatcher.

    The jit-cache key of every dispatch is (spec, topology, bucket) only —
    per-request weights ride along as a traced (C, 3) operand. `mesh=None`
    solves on the default device (fleet vmap); a mesh shards the cell axis
    (`region_mesh`, shard-local early exit unless `spec.lockstep`).
    """

    def __init__(self, spec: SolverSpec,
                 acc: Optional[AccuracyModel] = None, mesh=None,
                 clocks: Optional[StageClocks] = None):
        self.spec = spec
        self.acc = acc if acc is not None else default_accuracy()
        self.mesh = mesh
        self.clocks = clocks if clocks is not None else StageClocks()
        self._seq = 0

    def dispatch(self, plan: BatchPlan) -> InFlightBatch:
        """Enqueue one batch solve; returns without blocking on results."""
        t0 = time.monotonic()
        res = solve(Problem(system=plan.sys_batch, weights=plan.weights,
                            acc=self.acc, init=plan.init_batch,
                            mesh=self.mesh), self.spec)
        fleet = res.fleet if hasattr(res, "fleet") else res
        self.clocks.record("dispatch", time.monotonic() - t0)
        batch = InFlightBatch(plan=plan, result=fleet, seq=self._seq)
        self._seq += 1
        return batch
