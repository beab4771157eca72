"""Reduction of a profiler trace to device busy, idle and kernel time.

Works on what `jax.profiler.ProfileData.from_file` gives for a
`*.xplane.pb`: planes with lines of events (name, start_ns, duration_ns),
all on one clock. On a TPU host:

  * each chip is a plane named `/device:TPU:<i>`, whose lines are
    `XLA Modules` (one event per program run), `XLA Ops` (one per HLO op,
    loops nesting the ops of their body) and `Async XLA Ops`;
  * an op event's name is its HLO text, `%<op> = <shape> <opcode>(...)`;
    a Pallas kernel is a `custom-call` whose op is named after the kernel
    (`%sp1_lambda_sum.39 = f32[64,16,1]{...} custom-call(f32[64,16,1]...`);
  * host threads are lines of `/host:CPU`; the harness marks the measured
    window and its own phases there with `jax.profiler.TraceAnnotation`
    events named `bench.*`.

Busy time is the union of all event intervals of a device plane inside the
window; idle is the window less busy. Kernel time is the sum of the
durations of the kernel's custom-call events.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "bench.window"
_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")
_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
_OP = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?$")


@dataclasses.dataclass
class KernelCall:
    kernel: str
    seconds: float
    out_shape: Tuple[int, ...]
    operand_shapes: List[Tuple[int, ...]]
    device: str


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: Dict[str, float]           # per device plane
    kernels: List[KernelCall]
    op_self_s: Dict[str, float]        # HLO op -> self seconds, all devices
    gaps: List[Tuple[str, float]]      # longest idle gaps: (host phase, s)

    @property
    def chips(self) -> int:
        return len(self.busy_s)

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)

    def kernel_calls(self, kernel: str) -> List[KernelCall]:
        return [k for k in self.kernels if k.kernel == kernel]


def _shape(tok: str) -> Tuple[int, ...]:
    return tuple(int(x) for x in tok.split(",") if x)


def parse_kernel(text: str) -> Optional[Tuple[str, Tuple[int, ...],
                                              List[Tuple[int, ...]]]]:
    """(kernel, output shape, operand shapes) of a custom-call op event,
    or None for any other op."""
    head, sep, rest = text.partition(" = ")
    if not sep or " custom-call(" not in rest:
        return None
    m = _OP.match(head.strip())
    if not m:
        return None
    out_txt, _, args = rest.partition(" custom-call(")
    args = args.split("custom_call_target", 1)[0]
    outs = _SHAPE.findall(out_txt)
    return (m.group(1), _shape(outs[0]) if outs else (),
            [_shape(s) for s in _SHAPE.findall(args)])


def op_name(text: str) -> str:
    return text.partition(" = ")[0].strip().lstrip("%")


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _self_times(events: List[Tuple[int, int, str]], into: Dict[str, float]):
    """Self time of nested events (start, end, name): each event's duration
    less the part its children cover."""
    stack: List[list] = []
    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and a >= stack[-1][1]:
            s = stack.pop()
            into[s[2]] = into.get(s[2], 0.0) + s[3]
        if stack:
            stack[-1][3] -= (min(b, stack[-1][1]) - a) * 1e-9
        stack.append([a, b, name, (b - a) * 1e-9])
    for s in stack:
        into[s[2]] = into.get(s[2], 0.0) + s[3]


def reduce(planes: Iterable, window: str = WINDOW,
           n_gaps: int = 10) -> TraceSummary:
    """Busy, idle, kernel and op self times inside the window marked by the
    host events named `window` (from the first such event's start to the
    last one's end)."""
    planes = list(planes)
    host_marks: List[Tuple[int, int, str]] = []
    devices = []
    for plane in planes:
        if _DEVICE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    a = int(e.start_ns)
                    host_marks.append((a, a + int(e.duration_ns), e.name))
    spans = [(a, b) for a, b, n in host_marks if n == window]
    if not spans:
        raise ValueError(f"the trace has no {window!r} host event")
    w0, w1 = min(a for a, _ in spans), max(b for _, b in spans)
    busy: Dict[str, float] = {}
    kernels: List[KernelCall] = []
    op_self: Dict[str, float] = {}
    first_busy = None
    for plane in devices:
        intervals = []
        for line in plane.lines:
            ops = []
            for e in line.events:
                a = int(e.start_ns)
                b = a + int(e.duration_ns)
                if b <= w0 or a >= w1:
                    continue
                a, b = max(a, w0), min(b, w1)
                intervals.append((a, b))
                if line.name != "XLA Ops":
                    continue
                ops.append((a, b, op_name(e.name)))
                k = parse_kernel(e.name)
                if k is not None:
                    kernels.append(KernelCall(k[0], (b - a) * 1e-9, k[1],
                                              k[2], plane.name))
            _self_times(ops, op_self)
        merged = _union(intervals)
        busy[plane.name] = sum(b - a for a, b in merged) * 1e-9
        if first_busy is None:
            first_busy = merged
    if not devices:
        raise ValueError("the trace has no device plane")
    gaps = []
    prev = w0
    for a, b in (first_busy or []) + [(w1, w1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    phases = [m for m in host_marks if m[2] != window]
    for a, b in gaps[:n_gaps]:
        mid = (a + b) // 2
        cover = [m for m in phases if m[0] <= mid < m[1]]
        label = min(cover, key=lambda m: m[1] - m[0])[2] if cover else "host"
        named.append((label, (b - a) * 1e-9))
    return TraceSummary(window_s=(w1 - w0) * 1e-9, busy_s=busy,
                        kernels=kernels, op_self_s=op_self, gaps=named)


def load(trace_dir: str) -> TraceSummary:
    """Reduce the newest `*.xplane.pb` under `trace_dir`."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    newest = max(files, key=os.path.getmtime)
    return reduce(ProfileData.from_file(newest).planes)
