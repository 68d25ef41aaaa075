"""The reduction from a profiler trace to busy/idle time, the top device
ops and the idle gaps named by the host's activity: on interval arithmetic,
and on a small trace recorded on a TPU v5e (data/trace_v5e.xplane.pb, made
by make_trace_data.py)."""
from pathlib import Path

import pytest

from bench import trace_reduce as TR

DATA = Path(__file__).resolve().parent / "data" / "trace_v5e.xplane.pb"


def test_union_clip_gaps():
    u = TR.union([(5, 7), (0, 2), (1, 3), (6, 9), (9, 9)])
    assert u == [(0, 3), (5, 9)]
    assert TR.clip(u, 1, 6) == [(1, 3), (5, 6)]
    assert TR.gaps(u, -1, 12) == [(-1, 0), (3, 5), (9, 12)]


def test_reduce_synthetic():
    devices = {"/device:TPU:0": [("fusion", 10, 20), ("fusion", 15, 30), ("dot", 60, 70)]}
    host = [("main", "bench.window", 0, 100),
            ("main", "bench.submit", 30, 40),
            ("pack", "numpy work", 35, 58),
            ("main", "bench.wait", 70, 100)]
    r = TR.reduce(devices, host, extra_host=[("pipeline.pack", 72, 99)])
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["idle_share"] == pytest.approx(0.7)
    assert r["device_ops"] == [["fusion", pytest.approx(25e-9)], ["dot", pytest.approx(10e-9)]]
    names = [n for n, _s in r["idle_gaps"]]
    secs = [s for _n, s in r["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)
    # 30..60: the numpy work overlaps 23 of it; 70..100: the wait overlaps
    # all 30 and wins over the pack span's 27; 0..10: only the window
    assert names == ["numpy work", "bench.wait", "host: nothing traced"]


def test_no_device_ops_reads_all_idle():
    r = TR.reduce({}, [("main", "bench.window", 0, 10)])
    assert r["devices"] == 0 and r["busy_s"] == 0.0


def test_recorded_v5e_trace():
    devices, host = TR.read_planes(str(DATA))
    assert list(devices) == ["/device:TPU:0"]
    r = TR.reduce(devices, host)
    assert 0.1 < r["window_s"] < 2.0
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0.5 < r["idle_share"] < 1.0
    assert r["device_ops"] and all(t > 0 for _n, t in r["device_ops"])
    # named by jitted program (fingerprint dropped) and HLO op
    assert {n for n, _t in r["device_ops"]} >= {"jit__lambda:%fusion",
                                                 "jit__lambda:%tanh_reduce_fusion"}
    # the host pauses between the jitted calls are the longest gaps
    assert r["idle_gaps"][0][0] == "host.pause"
    assert r["idle_gaps"][0][1] == pytest.approx(0.06, rel=0.3)
