"""Metric arithmetic on hand-made records."""
import types

import pytest

from bench import run as harness
from bench.spans import Spans


def reader(name):
    return harness.load_module("metrics", name).read


def analytics_run(trace=None):
    runs = [  # roots end at 2, 5 and 9 s; the window asked for 8 s
        {"root": 1, "start_s": 0.0, "end_s": 2.0, "iters": 4,
         "edges_traversed": 200, "reached_edges": 100},
        {"root": 2, "start_s": 2.0, "end_s": 5.0, "iters": 6,
         "edges_traversed": 300, "reached_edges": 300},
        {"root": 3, "start_s": 5.0, "end_s": 9.0, "iters": 10,
         "edges_traversed": 500, "reached_edges": 600},
    ]
    return types.SimpleNamespace(
        driver="analytics", records={"program": "sssp", "runs": runs,
                                     "elapsed_s": 9.0, "num_vertices": 50,
                                     "translate": [{"preprocess_s": 1.5,
                                                    "aot_s": 0.25},
                                                   {"preprocess_s": 0.5,
                                                    "aot_s": 0.25}]},
        spans=Spans(), setup_s=12.0, seconds=8.0, memory_peak_bytes=3 << 30,
        trace=trace, peaks={"hbm_bytes_per_s": 1e3}, params={})


def test_mteps_counts_whole_roots_over_the_time_to_the_last():
    # (100 + 300 + 600) / 2 tuples over 9 s, not over the 8 s asked for
    assert reader("mteps")(analytics_run()) == pytest.approx(500 / 9 / 1e6)


def test_analytics_layer_metrics():
    run = analytics_run(trace={"busy_s": 4.0, "window_s": 10.0})
    assert reader("edge_work_ratio")(run) == pytest.approx(1000 / 1000)
    assert reader("superstep_ms")(run) == pytest.approx(4.0 / 20 * 1e3)
    assert reader("idle_share.analytics")(run) == pytest.approx(60.0)
    assert reader("layout_build_s")(run) == pytest.approx(2.0)
    assert reader("aot_s")(run) == pytest.approx(0.5)
    assert reader("peak_hbm_gib")(run) == pytest.approx(3.0)
    assert reader("setup_s")(run) == 12.0
    # least bytes: 8 per reached edge + 8 per vertex, per root
    least = 8 * 1000 + 3 * 8 * 50
    assert reader("sssp_roofline")(run) == pytest.approx(
        least / 1e3 / 4.0 * 100)
    run.records["program"] = "bfs"
    assert reader("sssp_roofline")(run) is None


def test_layer_metrics_without_a_trace_read_nothing():
    run = analytics_run()
    for name in ("superstep_ms", "idle_share.analytics", "sssp_roofline"):
        assert reader(name)(run) is None
