"""Device time by stage and idle time by span (``bench/stages.py``), on
hand-made planes and on the traces recorded on a TPU v5e (``data/``)."""
import glob
import os

import pytest

from bench import stages, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))
SCOPED = os.path.join(DATA, "bfs_s10_scoped_v5e.xplane.pb")
LOOP = "jit(loop)/while/body"


def planes(ops, spans, modules=()):
    """One device with ``(name, start, duration, scope)`` ops and
    ``(name, start, duration)`` run-loop modules; host ``spans``."""
    host = ("/host:CPU", [("python", [(n, float(s), float(d), None)
                                      for n, s, d in spans])])
    dev = ("/device:TPU:0",
           [("XLA Modules", [(n, float(s), float(d), None)
                             for n, s, d in modules]),
            ("XLA Ops", [(n, float(s), float(d), sc)
                         for n, s, d, sc in ops])])
    return [host, dev]


def test_stage_is_the_innermost_repro_scope():
    assert stages.stage_of(
        f"{LOOP}/repro.push/jit(push_superstep)/repro.push.tier0/"
        "repro.push.tier0.gather/gather:") == "repro.push.tier0.gather"
    assert stages.stage_of(f"{LOOP}/repro.init:") == "repro.init"
    assert stages.stage_of(f"{LOOP}/cond/branch_2_fun/gather:") == "none"
    assert stages.stage_of(None) == "none"


def test_self_time_under_nested_while_and_cond():
    ops = [("while", 0, 1000, "jit(loop)/while"),                 # 0-1000
           ("cond", 10, 900, f"{LOOP}/cond"),                     # 10-910
           ("fusion.2", 20, 600,
            f"{LOOP}/repro.push.fallback.sweep/gather:"),         # 20-620
           ("fusion.9", 650, 200,
            f"{LOOP}/repro.push.fallback.apply/select:"),         # 650-850
           ("fusion.3", 920, 50, f"{LOOP}/repro.loop.stats/add:")]
    spans = [("bench.window", 0, 1000)]
    r = stages.reduce_planes(planes(ops, spans))
    got = dict(r["device_stages"])
    assert got["repro.push.fallback.sweep"] == pytest.approx(600e-9)
    assert got["repro.push.fallback.apply"] == pytest.approx(200e-9)
    assert got["repro.loop.stats"] == pytest.approx(50e-9)
    # the containers' own time: while 1000 - 900 - 50, cond 900 - 800
    assert got["none"] == pytest.approx((50 + 100) * 1e-9)
    assert sum(got.values()) == pytest.approx(r["busy_s"])
    assert r["staged_share"] == pytest.approx(850 / 1000)
    assert stages.stage_seconds(r, "repro.push.fallback") == \
        pytest.approx(800e-9)


def test_self_time_is_clipped_to_the_window():
    ops = [("while", 0, 1000, "jit(loop)/while"),
           ("fusion", 100, 400, f"{LOOP}/repro.pull/gather:"),     # 100-500
           ("fusion", 600, 300, f"{LOOP}/repro.pull/gather:")]     # 600-900
    r = stages.reduce_planes(planes(ops, [("bench.window", 300, 400)]))
    got = dict(r["device_stages"])
    assert got["repro.pull"] == pytest.approx((200 + 100) * 1e-9)
    assert got["none"] == pytest.approx(100e-9)
    assert sum(got.values()) == pytest.approx(r["busy_s"])


def test_idle_is_labelled_by_repro_spans_inside_bench_spans():
    ops = [("while", 200, 500, "jit(loop)/while")]               # 200-700
    spans = [("bench.window", 0, 1000), ("bench.root", 50, 900),  # 50-950
             ("repro.run", 100, 800),                             # 100-900
             ("repro.run.dispatch", 100, 150),                    # 100-250
             ("repro.run.wait", 250, 650)]                        # 250-900
    modules = [("jit_loop(123)", 200, 500)]
    r = stages.reduce_planes(planes(ops, spans, modules))
    gaps = dict(r["idle_gaps"])
    # idle 0-200 and 700-1000
    assert gaps["none"] == pytest.approx((50 + 50) * 1e-9)
    assert gaps["root"] == pytest.approx((50 + 50) * 1e-9)
    assert gaps["repro.run.dispatch"] == pytest.approx(100e-9)
    assert gaps["repro.run.wait"] == pytest.approx(200e-9)
    assert "repro.run" not in gaps
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["loop_runs"] == {"executions": 1, "inside_run": 1,
                              "skew_s": 0.0}


def test_a_loop_outside_its_run_span_is_skew():
    ops = [("while", 200, 500, "jit(loop)/while")]
    spans = [("bench.window", 0, 1000), ("repro.run", 100, 550)]
    r = stages.reduce_planes(planes(ops, spans, [("jit_loop(1)", 200, 500)]))
    assert r["loop_runs"]["inside_run"] == 0
    assert r["loop_runs"]["skew_s"] == pytest.approx(50e-9)


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_wire_reader_reads_what_profile_data_reads(path):
    mine = {p: [(ln, [ev[:3] for ev in evs]) for ln, evs in lines]
            for p, lines in stages.load_planes(path)}
    for name, lines in trace.load_planes(path):
        if name.startswith(("/device:", "/host:CPU")):
            assert mine[name] == lines, name


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_stages_sum_to_busy(path):
    r = stages.reduce(path, devices=1)
    base = trace.reduce(path, devices=1)
    assert (r["window_s"], r["busy_s"]) == (base["window_s"], base["busy_s"])
    assert sum(t for _, t in r["device_stages"]) == pytest.approx(
        r["busy_s"], rel=1e-9)
    assert sum(t for _, t in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)


def test_unscoped_trace_has_no_stages():
    """The trace recorded before the program named its stages: all its
    device time is ``none``, and no run span holds its loops."""
    r = stages.reduce(os.path.join(DATA, "bfs_s10_v5e.xplane.pb"), devices=1)
    assert [n for n, _ in r["device_stages"]] == ["none"]
    assert r["loop_runs"]["executions"] == 3
    assert r["loop_runs"]["inside_run"] == 0


def test_scoped_trace_reads_the_program_stages():
    """Scale-10 BFS, two roots, recorded on a TPU v5e with the program's
    stage names and spans: the readings when the file was added."""
    r = stages.reduce(SCOPED, devices=1)
    got = dict(r["device_stages"])
    assert r["busy_s"] == pytest.approx(0.001777587, rel=1e-6)
    assert max(got, key=got.get) == "repro.push.fallback.sweep"
    assert got["repro.push.fallback.sweep"] == pytest.approx(0.000975794,
                                                             rel=1e-6)
    assert got["repro.push.tier0.compact"] == pytest.approx(0.000286654,
                                                            rel=1e-6)
    assert stages.stage_seconds(r, "repro.push.tier") == pytest.approx(
        0.000673751, rel=1e-6)
    assert r["staged_share"] == pytest.approx(0.951485, rel=1e-5)
    gaps = dict(r["idle_gaps"])
    assert gaps["repro.run.wait"] == pytest.approx(0.00563357, rel=1e-6)
    assert gaps["repro.run.dispatch"] == pytest.approx(0.002843607, rel=1e-6)
    assert gaps["none"] == pytest.approx(0.00020053, rel=1e-6)
    assert r["loop_runs"] == {"executions": 2, "inside_run": 2,
                              "skew_s": 0.0}


def test_heaviest_fusions_are_named_in_the_scoped_trace():
    """The fusions the unscoped trace could only call ``fusion.2`` and
    ``fusion.8``: the fallback's sweep and the tier-0 compaction."""
    ops = {trace.op_name(n): stages.stage_of(scope)
           for p, lines in stages.load_planes(SCOPED)
           if p.startswith("/device:") for ln, evs in lines
           if ln == trace.OPS_LINE for n, _, _, scope in evs}
    assert ops["fusion.2"] == "repro.push.fallback.sweep"
    assert ops["fusion.8"] == "repro.push.tier0.compact"
