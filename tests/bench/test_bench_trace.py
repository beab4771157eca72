"""The yardstick's arithmetic: trace reduction to busy, idle and kernel
time, the SP1 kernel's operations and bytes, and the table of peaks."""
from __future__ import annotations

import collections
import json
from pathlib import Path

import pytest

from bench_cells import BENCH

from harness import roofline, trace  # noqa: E402

E = collections.namedtuple("E", "name start_ns duration_ns")
L = collections.namedtuple("L", "name events")
P = collections.namedtuple("P", "name lines")

KERNEL = ("%sp1_lambda_sum.39 = f32[64,16,1]{2,1,0:T(8,128)S(1)} custom-call("
          "f32[64,16,1]{2,1,0:T(8,128)S(1)} %copy.164, f32[64,1,8]{2,1,0:"
          "T(1,128)S(1)} %pad.128, f32[64,1,2048]{2,1,0:T(1,128)S(1)} "
          "%reshape_multiply_fusion.2, f32[64,1,2048]{2,1,0:T(1,128)S(1)} "
          "%reshape.618), custom_call_target=\"tpu_custom_call\", "
          "frontend_attributes={kernel_metadata={}}")
RECORDED = Path(__file__).resolve().parent / "data" / \
    "sp1_fleet_c2n256.xplane.pb"


def hand_built():
    """A window [100, 1100) ns on one chip: a program [150, 450) whose
    while loop [160, 440) holds the kernel [200, 300) and a fusion
    [300, 350); a second program [600, 900); an async copy [880, 1000).
    The host marks enqueue [100, 150) and [450, 600), block [600, 1000)."""
    dev = P("/device:TPU:0", [
        L("XLA Modules", [E("jit_a", 150, 300), E("jit_a", 600, 300)]),
        L("XLA Ops", [E("%while.1 = (f32[2]) while(...)", 160, 280),
                      E(KERNEL, 200, 100),
                      E("%fusion.7 = f32[64] fusion(...)", 300, 50),
                      E("%fusion.9 = f32[64] fusion(...)", 600, 300)]),
        L("Async XLA Ops", [E("%copy-start = ...", 880, 120)]),
    ])
    host = P("/host:CPU", [L("main", [
        E("bench.window", 100, 1000), E("bench.enqueue", 100, 50),
        E("bench.enqueue", 450, 150), E("bench.block", 600, 400),
        E("other", 0, 2000)])])
    return [host, dev, P("/device:CUSTOM:Megascale Trace", [])]


def test_reduction_of_a_hand_built_trace():
    s = trace.reduce(hand_built())
    assert s.window_s == pytest.approx(1000e-9)
    # busy: [150, 450) + [600, 1000) = 700 ns; idle 300 ns
    assert s.busy_s == {"/device:TPU:0": pytest.approx(700e-9)}
    assert s.chips == 1
    k = s.kernel_calls("sp1_lambda_sum")
    assert len(k) == 1 and k[0].seconds == pytest.approx(100e-9)
    assert k[0].out_shape == (64, 16, 1)
    assert k[0].operand_shapes == [(64, 16, 1), (64, 1, 8), (64, 1, 2048),
                                   (64, 1, 2048)]
    # self times: the loop less its two children
    assert s.op_self_s["while.1"] == pytest.approx(130e-9)
    assert s.op_self_s["fusion.9"] == pytest.approx(300e-9)
    # idle gaps, longest first, named by the host phase around them
    assert [g[0] for g in s.gaps] == ["bench.enqueue", "host",
                                      "bench.enqueue"]
    assert [g[1] for g in s.gaps] == pytest.approx([150e-9, 100e-9, 50e-9])


def test_reduction_needs_the_window_and_a_device():
    host, dev, _ = hand_built()
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce([dev])
    with pytest.raises(ValueError, match="device"):
        trace.reduce([host])


def test_reduction_of_a_recorded_chip_trace():
    """Two re-plans of a 2-cell x 256-device fleet on a TPU v5e."""
    from jax.profiler import ProfileData

    s = trace.reduce(ProfileData.from_file(str(RECORDED)).planes)
    calls = s.kernel_calls("sp1_lambda_sum")
    assert calls and len(calls) % 3 == 0          # 3 sweep rounds per step
    for c in calls:
        assert c.out_shape == (2, 16, 1)
        assert c.operand_shapes[2] == (2, 1, 256)
    busy = s.busy_s["/device:TPU:0"]
    assert 0 < sum(c.seconds for c in calls) < busy <= s.window_s
    assert s.gaps and all(g[0].startswith("bench.") or g[0] == "host"
                          for g in s.gaps)


@pytest.mark.parametrize("out,operands,ops,nbytes", [
    # one cell: M = 16 deadlines, N = 2048 devices
    ((16, 1), [(16, 1), (1, 8), (1, 2048), (1, 2048)],
     16 * 2048 * 198 + 2048 * 3, 4 * (16 + 8 + 2 * 2048 + 16)),
    # a vmapped fleet of 64 such cells
    ((64, 16, 1), [(64, 16, 1), (64, 1, 8), (64, 1, 2048), (64, 1, 2048)],
     64 * (16 * 2048 * 198 + 2048 * 3), 64 * 4 * (16 + 8 + 2 * 2048 + 16)),
])
def test_sp1_operations_and_bytes_from_shapes(out, operands, ops, nbytes):
    assert roofline.sp1_lambda_sum_cost(out, operands) == (ops, nbytes)


def test_peaks_refuse_an_unknown_device_kind():
    peak = roofline.peaks("TPU v5 lite")
    assert peak["flops_per_s"] == 1.97e14
    assert peak["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError, match="TPU v9"):
        roofline.peaks("TPU v9")
    doc = json.loads((BENCH / "peaks.json").read_text())
    assert "TPU v5e" in doc["source"]


def test_roofline_share_stays_below_the_peak():
    """The fleet call's least time is compute-bound and short of the time
    the chip measured (213 us for this call, PERF.md)."""
    ops, nbytes = roofline.sp1_lambda_sum_cost(
        (64, 16, 1), [(64, 16, 1), (64, 1, 8), (64, 1, 2048), (64, 1, 2048)])
    t, bound = roofline.least_time_s(ops, nbytes, roofline.peaks(
        "TPU v5 lite"))
    assert bound == "compute" and t == pytest.approx(ops / 1.97e14)
    assert t < 213e-6
