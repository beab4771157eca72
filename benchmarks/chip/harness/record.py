"""What a run leaves for the end-to-end metrics, the per-layer readers and
the check of `correct`."""
from __future__ import annotations

import dataclasses
import tempfile
from contextlib import contextmanager
from typing import Dict, List, Optional

import numpy as np

from . import trace as trace_mod


@dataclasses.dataclass
class Record:
    kind: str                       # "replan" or "serve"
    chips: int
    device_kind: str
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    compiles_in_window: int = 0
    memory_peak_bytes: int = 0
    # re-plan cells
    replans: int = 0
    enqueue_s: Optional[np.ndarray] = None
    counters: Optional[np.ndarray] = None      # (replans, C, k)
    counter_columns: tuple = ()
    # serving cells
    latency_s: Optional[np.ndarray] = None     # per request due in window
    completed_in_window: int = 0
    due_s: Optional[np.ndarray] = None
    done_s: Optional[np.ndarray] = None
    lag_s: Optional[np.ndarray] = None
    stage_s: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    batch_fill: Optional[np.ndarray] = None    # real cells / cells_per_batch
    # traced sub-window
    trace: Optional[trace_mod.TraceSummary] = None
    traced_steps: int = 0
    # answers to check: problem dicts and answers (host numpy)
    checks: List[tuple] = dataclasses.field(default_factory=list)


class Tracer:
    """Opens a profiler session over the last part of the window and
    reduces it. The Python tracer is off: its events would outnumber the
    device's. Phases of the harness are marked `bench.*`."""

    def __init__(self, enabled: bool, seconds: float):
        self.enabled = enabled
        self.seconds = seconds
        self.dir = None
        self.on = False
        self._window = None

    def maybe_start(self, elapsed: float, window: float) -> None:
        if not self.enabled or self.on or elapsed < window - self.seconds:
            return
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(trace_mod.WINDOW)
        self._window.__enter__()
        self.on = True

    def stop(self) -> Optional[trace_mod.TraceSummary]:
        if not self.on:
            return None
        import shutil

        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = False
        try:
            return trace_mod.load(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@contextmanager
def phase(name: str, on: bool):
    """A `bench.<name>` host span in the trace (nothing when not tracing)."""
    if not on:
        yield
        return
    import jax

    with jax.profiler.TraceAnnotation(f"bench.{name}"):
        yield
