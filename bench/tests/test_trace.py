"""Trace reduction: interval arithmetic by hand, and a trace recorded on
a TPU v5 lite (`record_trace.py`) reduced to numbers computed by hand
from its raw events."""
from pathlib import Path

import pytest

from bench import trace as T

SMALL = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def test_union_merges_overlaps_and_touching():
    assert T.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_subtract():
    a = [(0, 10), (20, 30)]
    b = [(2, 4), (8, 22), (25, 26)]
    assert T.subtract(a, b) == [(0, 2), (4, 8), (22, 25), (26, 30)]
    assert T.subtract(a, []) == a
    assert T.subtract(a, [(-5, 50)]) == []


@pytest.mark.parametrize("hlo,name", [
    ("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p)", "fusion"),
    ("%rotor_slice_step.1 = (f32[8,432,432]) custom-call(...)",
     "rotor_slice_step"),
    ("%all-reduce-start.3 = f32[] all-reduce-start(%x)", "all-reduce-start"),
    ("%while = (s32[]) while(%t)", "while"),
])
def test_op_name(hlo, name):
    assert T.op_name(hlo) == name


def synthetic():
    # chip 0: compute 0-40 and 60-100 ns, an all-reduce 30-70 (exposed
    # 40-60 = 20 ns); chip 1: compute 0-50, collective-permute 50-90
    # (all exposed, 40 ns: 35 in flight, 5 in its done op).  Host: a call
    # span 0-100 with a `prepare` event 40-60, so chip 0's one gap (40-60)
    # is `prepare`.
    d0 = T.DeviceTrace(0, [("fusion", 0, 40), ("all-reduce", 30, 70),
                           ("fusion", 60, 100)],
                       [("jit_step", 0, 100)],
                       [("copy-start", 0, 90)])
    d1 = T.DeviceTrace(1, [("fusion", 0, 50),
                           ("collective-permute-done", 85, 90)],
                       [("jit_step", 0, 95), ("jit_step", 95, 99)],
                       [("collective-permute-start", 50, 85)])
    host = [("bench.window", 0, 100), ("bench.call", 0, 100),
            ("prepare", 40, 60)]
    return T.Reduced([d0, d1], host, 0, 100)


def test_synthetic_busy_idle_launches():
    r = synthetic()
    assert r.window_s == pytest.approx(100e-9)
    # chip 0 busy 100 (ops cover it all); chip 1 busy 55, the transfer
    # in flight (50-85) not an op: mean 77.5 ns
    assert r.busy_s() == pytest.approx(77.5e-9)
    assert r.launches() == 1.5
    assert r.op_seconds()["fusion"] == pytest.approx((80 + 50) / 2 * 1e-9)


def test_synthetic_collective_exposure():
    r = synthetic()
    coll, exposed = r.collective_s()
    assert coll == pytest.approx((40 + 40) / 2 * 1e-9)
    assert exposed == pytest.approx((20 + 40) / 2 * 1e-9)


def test_synthetic_idle_gap_named_by_host_event():
    r = synthetic()
    r.devices[0].ops = [("fusion", 0, 40), ("fusion", 60, 100)]
    assert r.idle_gaps() == [("prepare", pytest.approx(20e-9))]


@pytest.fixture(scope="module")
def small():
    return T.reduce_trace(str(SMALL))


def test_recorded_window_and_skew(small):
    # bench.window spans 48,629,700 - 70,785,290 ns on the host; chip 0's
    # first program starts at 47,334,951 ns, 1,294,749 ns before it, so
    # every device event moves 1,294,749 ns later
    assert (small.lo, small.hi) == (48_629_700, 70_785_290)
    assert small.window_s == pytest.approx(22_155_590e-9)
    assert small.devices[0].modules[0][1] == 48_629_700
    assert small.launches() == 3


def test_recorded_busy_and_idle(small):
    # three runs of the program; per run the ops copy-start, copy-done and
    # the fusion (13 + 2 + 3,122 ns; 13 + 2 + 3,117 ns; and the third run's
    # copy-start and copy-done touch: 1,733 + 3,120 ns) = 11,122 ns busy
    assert small.busy_s() == pytest.approx(11_122e-9)
    idle = 1 - small.busy_s() / small.window_s
    assert idle == pytest.approx(1 - 11_122 / 22_155_590)


def test_recorded_op_time_by_name(small):
    ops = small.op_seconds()
    assert ops["convolution_tanh_fusion"] == pytest.approx(
        (3_122 + 3_117 + 3_120) * 1e-9)
    assert ops["copy-done"] == pytest.approx((2 + 2 + 1_720) * 1e-9)
    assert ops["copy-start"] == pytest.approx(3 * 13e-9)
    assert small.top_ops(1) == [("convolution_tanh_fusion",
                                 pytest.approx(9_359e-9))]


def test_recorded_idle_gap_is_the_host_sleep(small):
    name, secs = small.idle_gaps(1)[0]
    assert name == "$time sleep"           # inside the `host_sleep` span
    assert secs > 0.02                     # the 20 ms sleep
    assert small.collective_s() == (0.0, 0.0)
