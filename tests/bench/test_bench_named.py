"""The program's names in a trace: the `*.xplane.pb` reader, the reduction
of device time by named scope and of host time by program span, and the
readers of the per-layer metrics built on them."""
from __future__ import annotations

import dataclasses
import shutil
from pathlib import Path

import pytest

from bench_cells import BENCH

from harness import named, spec, trace, xspace  # noqa: E402
from harness.record import Record  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
OLD = DATA / "sp1_fleet_c2n256.xplane.pb"      # a program without names
KERNEL_OP = ("jit(_allocate_impl)/while/body/jit(_solve_sp1_sweep_impl)/"
             "jit(_sp1_sweep_dispatch)/sp1_lambda_sum/pallas_call:")
EXISTING = ("sp1_kernel_ms.replan", "sp1_lambda_sum_roofline.replan",
            "device_idle_pct.replan", "device_idle_pct.serve")
NEW = ("sp2_device_ms.replan", "sp1_device_ms.replan",
       "solve_prepare_ms.replan", "solve_launch_ms.replan",
       "solve_result_ms.replan", "idle_in_dispatch_pct.serve")


def _reader(metric: str):
    return spec._module(BENCH / "layers" / f"{metric}.py",
                        "test_layer_" + metric.replace(".", "_")).read


# -- the wire format ---------------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(no: int, v) -> bytes:
    """One protobuf field: an int as a varint, a float as a double, text
    or bytes length-delimited."""
    if isinstance(v, int):
        return _varint(no << 3) + _varint(v)
    if isinstance(v, float):
        import struct

        return _varint(no << 3 | 1) + struct.pack("<d", v)
    b = v.encode() if isinstance(v, str) else v
    return _varint(no << 3 | 2) + _varint(len(b)) + b


def _entry(key: int, msg: bytes) -> bytes:
    return _f(1, key) + _f(2, msg)


def test_the_reader_decodes_a_hand_encoded_space(tmp_path):
    """tf_op as a reference into stat metadata and as a string; an event
    with every field zero (so none is written); a double stat skipped."""
    stat_meta = _f(5, _entry(1, _f(1, 1) + _f(2, "tf_op"))) + \
        _f(5, _entry(2, _f(1, 2) + _f(2, "jit(f)/sp2/add:"))) + \
        _f(5, _entry(3, _f(1, 3) + _f(2, "scale")))
    ev_meta = _f(4, _entry(10, _f(1, 10) + _f(2, "%add.1 = f32[] add()") +
                           _f(5, _f(1, 1) + _f(7, 2)) +
                           _f(5, _f(1, 3) + _f(2, 1.5)))) + \
        _f(4, _entry(11, _f(1, 11) + _f(2, "%mul.2") +
                     _f(5, _f(1, 1) + _f(5, "jit(f)/sp1/mul:"))))
    events = _f(4, _f(1, 10) + _f(2, 5000) + _f(3, 2500) +
                _f(4, _f(1, 3) + _f(2, 2.0))) + \
        _f(4, _f(1, 11) + _f(2, 9_999) + _f(3, 1_000)) + _f(4, b"")
    line = _f(2, "XLA Ops") + _f(3, 1000) + events
    plane = _f(2, "/device:TPU:0") + _f(3, line) + ev_meta + stat_meta
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_f(1, plane) + _f(1, _f(2, "/host:CPU")))
    dev, host = xspace.read(str(path))
    assert (dev.name, host.name, host.lines) == ("/device:TPU:0",
                                                 "/host:CPU", [])
    (ops,) = dev.lines
    assert ops.name == "XLA Ops"
    assert ops.events == [
        ("%add.1 = f32[] add()", 1005, 2, "jit(f)/sp2/add:"),
        ("%mul.2", 1009, 1, "jit(f)/sp1/mul:"),
        ("", 1000, 0, "")]


@pytest.mark.parametrize("recorded", ["sp1_fleet_c2n256",
                                      "named_fleet_c2n256"])
def test_the_reader_gives_profiledatas_events(recorded):
    """Every plane, line and event of a recorded chip trace as
    `ProfileData` gives it (name, start, duration), so the ops the new
    metrics read are the events the existing reduction reads."""
    from jax.profiler import ProfileData

    path = str(DATA / f"{recorded}.xplane.pb")
    mine = xspace.read(path)
    theirs = list(ProfileData.from_file(path).planes)
    assert [p.name for p in mine] == [p.name for p in theirs]
    for p, q in zip(mine, theirs):
        lines = list(q.lines)
        assert [ln.name for ln in p.lines] == [ln.name for ln in lines]
        for ln, m in zip(p.lines, lines):
            assert [e[:3] for e in ln.events] == [
                (e.name, e.start_ns, e.duration_ns) for e in m.events]


def test_the_reader_finds_the_kernels_name_stack():
    (dev,) = [p for p in xspace.read(str(OLD)) if p.name == "/device:TPU:0"]
    ops = [e for ln in dev.lines if ln.name == "XLA Ops" for e in ln.events
           if e[0].startswith("%sp1_lambda_sum.39 ")]
    assert ops and {e[3] for e in ops} == {KERNEL_OP}


# -- the reduction -----------------------------------------------------------

P, L = xspace.Plane, xspace.Line


def hand_built():
    """A window [100, 1100) ns on one chip. Device: a program [150, 450)
    whose loop (bcd) holds an SP1 kernel, an SP2 fusion and a copy with no
    name stack of its own; a fused op [600, 900) with sp2 in one of its
    two name stacks. Host: a re-plan's `solve` and its three stages inside
    `bench.enqueue`, a `repro.dispatch`, a `bench.block`, and an event
    the program did not write."""
    dev = P("/device:TPU:0", [
        L("XLA Modules", [("jit_a", 150, 300, "")]),
        L("XLA Ops", [
            ("%while.1", 160, 280, "jit(a)/bcd/while"),
            ("%k.2", 200, 100, "jit(a)/bcd/while/body/sp1/k/pallas_call:"),
            ("%fusion.7", 300, 50, "jit(a)/bcd/while/body/sp2/add:"),
            ("%copy.8", 350, 30, ""),
            ("%fusion.9", 600, 300, "jit(b)/x;jit(b)/sp2/y:")]),
    ])
    host = P("/host:CPU", [L("python", [
        ("bench.window", 100, 1000, ""),
        ("bench.enqueue", 100, 50, ""), ("repro.solve", 105, 43, ""),
        ("repro.solve.prepare", 106, 14, ""),
        ("repro.solve.launch", 120, 20, ""),
        ("PjitFunction(f)", 121, 18, ""),
        ("repro.solve.result", 140, 7, ""),
        ("bench.enqueue", 450, 150, ""), ("repro.dispatch", 460, 130, ""),
        ("bench.block", 600, 400, ""), ("other", 0, 2000, "")])])
    return [host, dev]


def test_reduction_by_name_of_a_hand_built_trace():
    n = named.reduce(hand_built())
    assert n.window_s == pytest.approx(1000e-9) and n.chips == 1
    # the loop's self time 280 - 100 - 50 - 30 = 100; the copy takes the
    # loop's names
    assert n.scope_s["sp1"] == pytest.approx(100e-9)
    assert n.scope_s["sp2"] == pytest.approx(350e-9)
    assert n.scope_s["bcd"] == pytest.approx(280e-9)
    assert n.scope_s["while"] == pytest.approx(280e-9)
    # program spans' self times: children that are program spans only
    want = {"repro.solve": 2e-9, "repro.solve.prepare": 14e-9,
            "repro.solve.launch": 20e-9, "repro.solve.result": 7e-9,
            "repro.dispatch": 130e-9, "bench.enqueue": 27e-9,
            "bench.block": 400e-9}
    assert set(n.spans) == set(want)
    for name, s in want.items():
        count = 2 if name == "bench.enqueue" else 1
        assert n.spans[name] == (count, pytest.approx(s)), name
    # idle [100, 150) + [450, 600) + [900, 1100), by innermost span
    assert dict(n.idle_by_span) == pytest.approx({
        "repro.dispatch": 130e-9, "bench.block": 100e-9, "host": 100e-9,
        "bench.enqueue": 27e-9, "repro.solve.launch": 20e-9,
        "repro.solve.prepare": 14e-9, "repro.solve.result": 7e-9,
        "repro.solve": 2e-9})
    assert sum(s for _, s in n.idle_by_span) == pytest.approx(400e-9)
    assert n.idle_inside["bench.enqueue"] == pytest.approx(200e-9)
    assert n.idle_inside["repro.solve"] == pytest.approx(43e-9)
    assert n.idle_inside["repro.dispatch"] == pytest.approx(130e-9)


def test_reduction_by_name_needs_the_window_and_a_device():
    host, dev = hand_built()
    with pytest.raises(ValueError, match="bench.window"):
        named.reduce([dev])
    with pytest.raises(ValueError, match="device"):
        named.reduce([host])


def test_the_idle_lines_name_every_span(capsys):
    named.report(named.reduce(hand_built()))
    err = capsys.readouterr().err.splitlines()
    assert len(err) == 8 and err[0].startswith(
        "bench: idle on chip 0 in repro.dispatch: 0.000000")


# -- the hook on trace.load and the readers ----------------------------------

def _load(path: Path, tmp: Path, hooked: bool, monkeypatch):
    d = tmp / ("hooked" if hooked else "plain") / "plugins" / "profile"
    d.mkdir(parents=True)
    shutil.copyfile(path, d / path.name)
    base = getattr(trace.load, "base", trace.load)
    monkeypatch.setattr(trace, "load", base)
    if hooked:
        named.install()
        named.install()          # idempotent: one wrapper
        assert trace.load.base is base
    return trace.load(str(tmp / ("hooked" if hooked else "plain")))


def _record(summary, kind: str) -> Record:
    return Record(kind=kind, chips=1, device_kind="TPU v5 lite",
                  trace=summary, traced_steps=2)


@pytest.mark.parametrize("recorded", ["sp1_fleet_c2n256",
                                      "named_fleet_c2n256"])
def test_the_hook_leaves_every_existing_reading_as_it_was(
        recorded, tmp_path, monkeypatch, capsys):
    path = DATA / f"{recorded}.xplane.pb"
    plain = _load(path, tmp_path, False, monkeypatch)
    hooked = _load(path, tmp_path, True, monkeypatch)
    assert not hasattr(plain, "named") and hooked.named.chips == 1
    assert dataclasses.asdict(hooked) == dataclasses.asdict(plain)
    for metric in EXISTING:
        kind = "serve" if metric.endswith(".serve") else "replan"
        read = _reader(metric)
        assert read(_record(hooked, kind)) == read(_record(plain, kind))
        assert read(_record(plain, kind)) is not None
    assert "bench: idle on chip 0 in " in capsys.readouterr().err


def test_a_program_without_names_reports_no_new_metric(tmp_path,
                                                       monkeypatch, capsys):
    """The parent's traced runs: no scope, no program span, so each new
    reader returns nothing, and nothing raises."""
    hooked = _load(OLD, tmp_path, True, monkeypatch)
    assert "sp2" not in hooked.named.scope_s
    assert not any(k.startswith("repro.") for k in hooked.named.spans)
    for metric in NEW:
        kind = "serve" if metric.endswith(".serve") else "replan"
        assert _reader(metric)(_record(hooked, kind)) is None


def test_the_named_trace_reads_every_new_metric(tmp_path, monkeypatch,
                                                capsys):
    """Recorded on a TPU v5e by `record_trace.py`: a re-plan of a 2-cell x
    256-device fleet and one pipeline batch, two solves in all."""
    hooked = _load(DATA / "named_fleet_c2n256.xplane.pb", tmp_path, True,
                   monkeypatch)
    replan, serve = _record(hooked, "replan"), _record(hooked, "serve")
    replan.traced_steps = 2
    got = {m: _reader(m)(serve if m.endswith(".serve") else replan)
           for m in NEW + EXISTING}
    assert all(v is not None and v > 0 for v in got.values()), got
    # SP1's scope holds its kernel; the two scopes fit in the busy time
    assert got["sp1_device_ms.replan"] >= got["sp1_kernel_ms.replan"]
    busy_ms = 1e3 * hooked.busy_mean_s / replan.traced_steps
    assert got["sp1_device_ms.replan"] + got["sp2_device_ms.replan"] \
        <= busy_ms
    # the three stages hold all but 5% of `solve`'s host time
    spans = hooked.named.spans
    stages = sum(got[f"solve_{s}_ms.replan"]
                 for s in ("prepare", "launch", "result"))
    count, own = spans["repro.solve"]
    own *= 1e3 / count
    assert count == 2 and own <= 0.05 * (own + stages)
    assert {"repro.plan", "repro.dispatch", "repro.materialize"} <= set(
        spans)
    assert got["idle_in_dispatch_pct.serve"] <= got["device_idle_pct.serve"]
