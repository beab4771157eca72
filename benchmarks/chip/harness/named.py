"""Device and host time by the program's own names.

The program names its layers in a profiler trace two ways:

  * device time: the solver's `jax.named_scope`s (`bcd`, `sp1`, `sp2`)
    are components of the name stack that each XLA op event carries as
    its `tf_op` stat (`jit(_allocate_impl)/bcd/while/body/sp2/...`),
    which `harness/xspace.py` reads;
  * host time: `repro.obs.span`s write `repro.<name>` host events
    whenever a profiler session is collecting (`repro.solve`,
    `repro.solve.prepare`, `.launch`, `.result`, `repro.plan`,
    `repro.dispatch`, `repro.materialize`), beside the harness's own
    `bench.*` phases.

`reduce` turns a trace into `Named`: each scope's self time of device
ops, each program span's self times, and chip 0's idle time by the host
span it fell in. A program that names nothing (an older one) gives empty
tables, and the readers of `layers/` then report nothing.

The harness reduces its trace in `trace.load` and deletes the trace
directory before any reader runs, so `install()` has `trace.load` also
reduce the same file here and hang the result on its summary as
`summary.named`; the summary's own fields are left as `trace.load` made
them. The readers that need it call `install()` as they are loaded,
which `spec.resolve` does before the run.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import sys
import traceback
from typing import Dict, List, Tuple

from . import trace, xspace

PROGRAM = "repro."
HARNESS = "bench."


@dataclasses.dataclass
class Named:
    window_s: float
    chips: int
    # name-stack component -> self seconds of the device ops under it,
    # summed over chips
    scope_s: Dict[str, float]
    # host span -> (occurrences, self seconds summed): its duration less
    # the program and harness spans nested in it
    spans: Dict[str, Tuple[int, float]]
    # chip 0's idle seconds by the innermost host span (program or
    # harness) around them, "host" where none is; longest first
    idle_by_span: List[Tuple[str, float]]
    # chip 0's idle seconds while the host is anywhere inside a span of
    # the name (nested spans included)
    idle_inside: Dict[str, float]


def _components(op: str, cache: Dict[str, frozenset]) -> frozenset:
    """The name-stack components of a `tf_op` (`a/b/c:type`, several
    joined by `;` for a fused op)."""
    got = cache.get(op)
    if got is None:
        got = frozenset(c for path in op.split(";")
                        for c in path.partition(":")[0].split("/") if c)
        cache[op] = got
    return got


def _scope_self(events, into: Dict[str, float], cache) -> None:
    """Add the self time of each op (its duration less what its nested
    ops cover) to every component of its name stack. An op with no
    `tf_op` (a copy or loop XLA inserted) takes the name stack of the op
    it runs inside."""
    stack: list = []          # [end, components, self seconds]

    def pop():
        end, comps, s = stack.pop()
        for c in comps:
            into[c] = into.get(c, 0.0) + s

    for a, b, op in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and a >= stack[-1][0]:
            pop()
        if stack:
            stack[-1][2] -= (min(b, stack[-1][0]) - a) * 1e-9
        comps = _components(op, cache) if op else (
            stack[-1][1] if stack else frozenset())
        stack.append([b, comps, (b - a) * 1e-9])
    while stack:
        pop()


def _idle(busy: List[Tuple[int, int]], w0: int, w1: int):
    out, prev = [], w0
    for a, b in busy + [(w1, w1)]:
        if a > prev:
            out.append((prev, a))
        prev = max(prev, b)
    return out


def _attribute(idle, spans):
    """(idle seconds by innermost span, idle seconds inside each name):
    one sweep over the idle intervals and the host spans' edges."""
    edges = []
    for k, (a, b, name) in enumerate(spans):
        edges.append((a, 1, k))
        edges.append((b, 0, k))
    for a, b in idle:
        edges.append((a, 1, -1))
        edges.append((b, 0, -1))
    edges.sort()
    by_span: Dict[str, float] = {}
    inside: Dict[str, float] = {}
    open_: Dict[int, int] = {}       # span index -> its start
    idle_on, prev = False, None
    for t, opening, k in edges:
        if idle_on and prev is not None and t > prev:
            dt = (t - prev) * 1e-9
            if open_:
                inner = max(open_, key=lambda j: (open_[j], -spans[j][1]))
                label = spans[inner][2]
            else:
                label = "host"
            by_span[label] = by_span.get(label, 0.0) + dt
            for name in {spans[j][2] for j in open_}:
                inside[name] = inside.get(name, 0.0) + dt
        prev = t
        if k < 0:
            idle_on = bool(opening)
        elif opening:
            open_[k] = t
        else:
            open_.pop(k, None)
    return by_span, inside


def reduce(planes: List[xspace.Plane], window: str = trace.WINDOW) -> Named:
    """`Named` from the planes `xspace.read` gives, inside the window the
    host events named `window` mark (as `trace.reduce` takes it)."""
    devices, host = [], []
    for plane in planes:
        if trace._DEVICE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            marks = [(s, s + d, n) for n, s, d, _ in line.events
                     if n.startswith(PROGRAM) or n.startswith(HARNESS)]
            if marks:
                host.append(marks)
    spans = [m for line in host for m in line if m[2] == window]
    if not spans:
        raise ValueError(f"the trace has no {window!r} host event")
    if not devices:
        raise ValueError("the trace has no device plane")
    w0, w1 = min(a for a, _, _ in spans), max(b for _, b, _ in spans)

    def clip(a, b):
        return max(a, w0), min(b, w1)

    scope_s: Dict[str, float] = {}
    cache: Dict[str, frozenset] = {}
    chip0 = min(devices, key=lambda p: int(p.name.rpartition(":")[2]))
    busy0: List[Tuple[int, int]] = []
    for plane in devices:
        for line in plane.lines:
            ops = []
            for _, s, d, op in line.events:
                if s + d <= w0 or s >= w1:
                    continue
                a, b = clip(s, s + d)
                if plane is chip0:
                    busy0.append((a, b))
                if line.name == "XLA Ops":
                    ops.append((a, b, op))
            _scope_self(ops, scope_s, cache)
    span_s: Dict[str, float] = {}
    count: Dict[str, int] = {}
    phases = []
    for line in host:
        marks = [(*clip(a, b), n) for a, b, n in line
                 if n != window and b > w0 and a < w1]
        phases += marks
        trace._self_times(marks, span_s)
        for _, _, name in marks:
            count[name] = count.get(name, 0) + 1
    by_span, inside = _attribute(_idle(trace._union(busy0), w0, w1), phases)
    return Named(window_s=(w1 - w0) * 1e-9, chips=len(devices),
                 scope_s=scope_s,
                 spans={k: (count[k], span_s[k]) for k in count},
                 idle_by_span=sorted(by_span.items(), key=lambda kv: -kv[1]),
                 idle_inside=inside)


def report(named: Named) -> None:
    """The idle-by-span lines a traced run prints to standard error."""
    for name, s in named.idle_by_span:
        print(f"bench: idle on chip 0 in {name}: {s:.6f} s of "
              f"{named.window_s:.6f} s", file=sys.stderr)


def _newest(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime)


def install() -> None:
    """Have `trace.load` also reduce its trace here (idempotent; the
    harness's own `load` stays reachable as `trace.load.base`)."""
    if hasattr(trace.load, "base"):
        return
    base = trace.load

    def load(trace_dir: str) -> trace.TraceSummary:
        summary = base(trace_dir)
        try:
            summary.named = reduce(xspace.read(_newest(trace_dir)))
        except Exception:   # the run's other readings still stand
            traceback.print_exc()
            print("bench: the trace's program names were not read",
                  file=sys.stderr)
        else:
            report(summary.named)
        return summary

    load.base = base
    trace.load = load


def of(run):
    """The `Named` of a traced run, or None."""
    return getattr(run.trace, "named", None)
