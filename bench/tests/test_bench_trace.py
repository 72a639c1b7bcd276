"""The reduction from a device trace to busy time, shares and the
breakdown: interval arithmetic on a hand-made trace, and every reduction
on small traces recorded on a TPU v5e."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.harness import trace as tr  # noqa: E402
from bench.harness.trace import Event, Trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
KERNEL = ('%k.1 = f32[512,512,128]{2,1,0} custom-call(f32[516,516,128]{2,1,0} %p), '
          'custom_call_target="tpu_custom_call"')
PAD = '%fusion.6 = f32[516,516,128]{2,1,0} fusion(f32[512,512,128]{2,1,0} %x), kind=kLoop'
WHILE = '%while = (s32[]) while((s32[]) %t), body=%b'


def _made():
    """Window 0-100 ns: a while around a kernel (10-50) and a pad (50-80)."""
    dev = [Event(WHILE, 10, 90), Event(KERNEL, 10, 50), Event(PAD, 50, 80)]
    host = [Event("bench.window", 0, 100), Event("bench.chunk", 5, 95)]
    return Trace(devices={"/device:TPU:0": dev}, host=host)


def test_bench_trace_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert tr.minus([(0, 10), (20, 30)], [(2, 4), (8, 22)]) == [(0, 2), (4, 8), (22, 30)]
    assert tr.length([(0, 2), (4, 8)]) == 6


def test_bench_trace_busy_idle_and_kernel_shares():
    t = _made()
    assert tr.window_s(t) == pytest.approx(100e-9)
    assert tr.busy_s(t) == pytest.approx(70e-9)  # the while counts once
    assert tr.idle_share(t) == pytest.approx(30.0)
    assert tr.share_outside(t, lambda i: i.opcode == "custom-call") == pytest.approx(
        100 * 30 / 70)
    r = tr.kernel_roofline(t, 819e9)
    moved = 4 * 512 * 512 * 128 + 4 * 516 * 516 * 128
    assert r["launches"] == 1 and r["bytes"] == moved
    assert r["share"] == pytest.approx(100 * moved / 819e9 / 40e-9)
    assert r["cells"] == 512 * 512 * 128


def test_bench_trace_breakdown_names_ops_and_gaps():
    t = _made()
    ops = tr.top_ops(t)
    assert [o[0] for o in ops] == ["custom-call k.1", "fusion fusion.6"]
    assert ops[0][1] == pytest.approx(40e-9)
    gaps = tr.idle_gaps(t)
    assert [g[0] for g in gaps] == ["bench.chunk", "bench.chunk"]
    assert gaps[0][1] == pytest.approx(20e-9) and gaps[1][1] == pytest.approx(10e-9)


def test_bench_trace_round_trips_through_json():
    t = _made()
    assert Trace.from_json(t.to_json()) == t


def _recorded(name):
    return Trace.read(DATA / f"{name}.trace.json")


def test_bench_trace_recorded_explicit():
    """Two 64-step chunks of heat3d.explicit (k=2) on one v5e."""
    t = _recorded("heat3d.explicit")
    r = tr.kernel_roofline(t, 819e9)
    assert r["launches"] == 69
    assert r["bytes"] == 69 * (4 * 512 * 512 * 128 + 8 + 4 * 516 * 516 * 128)
    assert 0 < r["share"] <= 100 and r["share"] == pytest.approx(62.3874, abs=1e-3)
    assert tr.idle_share(t) == pytest.approx(2.5225, abs=1e-3)
    outside = tr.share_outside(t, lambda i: i.opcode == "custom-call")
    assert outside == pytest.approx(46.1698, abs=1e-3)
    assert tr.top_ops(t)[0][0] == "custom-call closed_call.4"
    assert {g[0] for g in tr.idle_gaps(t)} == {"bench.chunk"}


def test_bench_trace_recorded_solve_and_served():
    """A tenth of a second of btcs.cg and 0.4 s of heat3d.served on one v5e."""
    from bench.harness import hlo

    cg = _recorded("btcs.cg")
    r = tr.kernel_roofline(cg, 819e9)
    assert 0 < r["share"] <= 100 and r["launches"] == 38
    vec = tr.share_outside(cg, hlo.is_stencil_kernel)
    assert vec == pytest.approx(82.8422, abs=1e-3)
    served = _recorded("heat3d.served")
    assert tr.idle_share(served) == pytest.approx(55.8585, abs=1e-3)
    assert 0 < tr.kernel_roofline(served, 819e9)["share"] <= 100
    assert tr.idle_gaps(served)[0][0] == "bench.request"
