"""The reduction from a profiler trace to busy time, kernel time, exposed
collectives and idle gaps: on made-up intervals, and on a small trace
recorded on a TPU v5e by ``bench/tools/record_probe_trace.py``."""

from __future__ import annotations

import pytest

from conftest import DATA

from benchlib import xplane
from benchlib.xplane import Op, Trace

PROBE = DATA / "probe.xplane.pb"


def test_interval_arithmetic():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert xplane.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert xplane.length([(0, 3), (5, 8)]) == 6


def made_up() -> Trace:
    # chip 0: compute 0-40, all-reduce 30-60 (20 ns exposed), compute 80-100
    # chip 1: compute 0-50, collective-permute 50-70 (20 ns exposed)
    return Trace(devices={
        0: [Op("fusion.1", 0, 40), Op("all-reduce.3", 30, 60),
            Op("fusion.2", 80, 100)],
        1: [Op("fusion.1", 0, 50), Op("collective-permute-done.1", 50, 70)],
    }, host=[Op("step", 0, 100), Op("fetch", 60, 80)])


def test_busy_collectives_and_kernels_on_made_up_chips():
    tr = made_up()
    assert xplane.busy_s(tr) == pytest.approx((80 + 70) / 2 / 1e9)
    assert xplane.exposed_collective_s(tr) == pytest.approx(20 / 1e9)
    assert xplane.op_seconds(tr, r"fusion\.1") == pytest.approx(90 / 1e9)
    assert xplane.op_count(tr, "fusion") == 3
    assert xplane.top_ops(tr, 1) == [["fusion.1", 90 / 1e9]]


def test_idle_gaps_go_to_the_innermost_host_span():
    tr = made_up()
    gaps = dict((k, v) for k, v in xplane.idle_gaps(tr, 0, 100))
    # chip 0 idles 60-80 (inside "fetch"); chip 1 idles 70-100, whose middle
    # (85) lies only inside "step"
    assert gaps["fetch"] == pytest.approx(20 / 2 / 1e9)
    assert gaps["step"] == pytest.approx(30 / 2 / 1e9)


@pytest.fixture(scope="module")
def probe():
    if not PROBE.is_file():
        pytest.fail(f"missing recorded trace {PROBE}")
    return xplane.load(str(PROBE), host_names={"probe", "probe_sleep"})


def test_recorded_trace_busy_and_idle(probe):
    assert probe.n_devices == 1
    t0, t1 = xplane.extent(probe)
    busy = xplane.busy_s(probe)
    assert 0 < busy < (t1 - t0) / 1e9
    # the host slept 3 x 30 ms with nothing queued: that is the longest idle
    gaps = xplane.idle_gaps(probe, t0, t1)
    assert gaps[0][0] == "probe_sleep"
    assert 0.085 < gaps[0][1] < 0.2


def test_recorded_trace_kernel_time(probe):
    assert xplane.op_count(probe, "probe_kernel") == 3
    assert 0 < xplane.op_seconds(probe, "probe_kernel") < xplane.busy_s(probe)
    assert xplane.exposed_collective_s(probe) == 0.0
