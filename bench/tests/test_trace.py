"""The trace reduction, on hand-made planes and on a small trace recorded
on a TPU v5e (``data/``)."""
import glob
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def planes(ops, spans, devices=1):
    host = ("/host:CPU", [("python3", [(n, float(s), float(d))
                                       for n, s, d in spans])])
    dev = [(f"/device:TPU:{i}", [("XLA Modules", [("jit_loop", 0.0, 1e9)]),
                                 ("XLA Ops", [(n, float(s), float(d))
                                              for n, s, d in ops])])
           for i in range(devices)]
    return [host, *dev]


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    ops = [("gather", 100, 50),      # 100-150
           ("fusion", 120, 60),      # 120-180: overlaps
           ("gather", 300, 100),     # 300-400
           ("copy", 950, 100)]       # 950-1050: clipped at 1000
    spans = [("bench.window", 0, 1000), ("bench.root", 90, 400),
             ("bench.step", 500, 200)]
    r = trace.reduce_planes(planes(ops, spans))
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx((80 + 100 + 50) * 1e-9)
    assert r["device_ops"][0] == ["gather", pytest.approx(150e-9)]
    gaps = dict(r["idle_gaps"])
    # idle: 0-100, 180-300, 400-950.  The root span covers 90-490, the
    # step span 500-700; the rest of the idle time is outside both
    assert gaps["root"] == pytest.approx((10 + 120 + 90) * 1e-9)
    assert gaps["step"] == pytest.approx(200e-9)
    assert gaps["none"] == pytest.approx((90 + 10 + 250) * 1e-9)


def test_busy_is_averaged_over_devices():
    ops = [("a", 0, 500)]
    spans = [("bench.window", 0, 1000)]
    r = trace.reduce_planes(planes(ops, spans, devices=2), devices=2)
    assert r["busy_s"] == pytest.approx(500e-9)
    with pytest.raises(ValueError):
        trace.reduce_planes(planes(ops, spans, devices=2), devices=4)


def test_a_trace_without_device_ops_or_window_is_refused():
    with pytest.raises(ValueError):
        trace.reduce_planes(planes([], [("bench.window", 0, 10)]))
    with pytest.raises(ValueError):
        trace.reduce_planes(planes([("a", 0, 5)], []))


RECORDED = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_chip_trace(path):
    r = trace.reduce(path, devices=1)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"] and all(t > 0 for _, t in r["device_ops"])
    assert sum(t for _, t in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)


def test_recorded_bfs_trace_reads_as_before():
    """Scale-10 BFS, three roots, recorded on a TPU v5e: the numbers this
    reduction gave when the file was added."""
    r = trace.reduce(os.path.join(DATA, "bfs_s10_v5e.xplane.pb"), devices=1)
    assert r["window_s"] == pytest.approx(0.016709699, rel=1e-6)
    assert r["busy_s"] == pytest.approx(0.002762273, rel=1e-6)
    assert r["device_ops"][0] == ["while.33", pytest.approx(0.002739086)]
    assert dict(r["idle_gaps"])["root"] == pytest.approx(0.013900026)
