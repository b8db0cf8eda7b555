"""Host spans and device stage names (``repro.core.tracing``): the run
loop's stages in the lowered program, the translator's breakdown read from
its spans, and the run's spans in a profiler trace."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dsl, tracing
from repro.core import graph as G
from repro.core.scheduler import DirectionPolicy, ScheduleConfig
from repro.core.translator import translate

LOOP_STAGES = (
    "repro.loop.direction", "repro.loop.stats", "repro.pull", "repro.push",
    *(f"repro.push.tier{i}{part}" for i in (0, 1)
      for part in ("", ".compact", ".gather", ".reduce", ".apply")),
    "repro.push.fallback", "repro.push.fallback.gather",
    "repro.push.fallback.reduce", "repro.push.fallback.apply")
PROGRAMS = {"bfs": lambda: dsl.bfs_program(2**30), "sssp": dsl.sssp_program}
AUTO = ScheduleConfig(direction=DirectionPolicy(mode="auto"))


def graph(seed=3, v=300, e=2400):
    """A small symmetric weighted R-MAT graph (a fresh one per call, so
    its layouts are built anew)."""
    src, dst = G.rmat_edges(v, e, seed=seed)
    w = np.random.default_rng(seed).random(len(src)).astype(np.float32)
    return G.from_edge_list(np.concatenate([src, dst]),
                            np.concatenate([dst, src]), num_vertices=v,
                            weights=np.concatenate([w, w]))


def stage_names(text):
    return set(re.findall(r"repro\.[\w.]+", text))


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_lowered_run_loop_names_every_stage(name):
    prog = translate(PROGRAMS[name](), graph(), AUTO)
    assert prog.report.push_layout == "fwd_ell"
    values, active = prog.init_state(roots=0)
    text = prog.run_loop.lower(prog.layouts, values,
                               active).as_text(debug_info=True)
    assert set(LOOP_STAGES) <= stage_names(text)
    init = jax.jit(lambda: prog.init_state(roots=0)).lower()
    assert "repro.init" in stage_names(init.as_text(debug_info=True))


def test_a_stage_adds_nothing_to_the_compiled_program():
    def f(x, named):
        if named:
            with tracing.stage("loop.stats"):
                return jnp.cumsum(x * 2) + 1
        return jnp.cumsum(x * 2) + 1

    def hlo(named):
        # the instructions, without their metadata and source locations
        text = jax.jit(f, static_argnums=1).lower(
            jnp.ones(64), named).compile().as_text()
        return re.sub(r",? metadata=\{[^}]*\}", "",
                      text.split("\nFileNames")[0])

    assert hlo(True) == hlo(False)


def test_spans_nest_and_keep_their_self_time():
    with tracing.collect() as ended:
        with tracing.span("outer") as outer:
            with tracing.span("outer.a") as a:
                pass
            with tracing.span("outer.b") as b:
                with tracing.span("outer.b.c") as c:
                    pass
    assert [s.name for s in ended] == ["repro.outer.a", "repro.outer.b.c",
                                       "repro.outer.b", "repro.outer"]
    assert outer.child_s == a.seconds + b.seconds
    assert b.self_s == b.seconds - c.seconds
    assert all(s.self_s >= 0 for s in ended)


def test_translate_breakdown_is_read_from_its_spans():
    g = graph(seed=5)
    with tracing.collect() as ended:
        prog = translate(dsl.sssp_program(), g, AUTO)
    by_name = {}
    for s in ended:
        by_name.setdefault(s.name, []).append(s)
    tb = prog.report.translate_breakdown
    layouts = [s for s in ended if s.name.startswith("repro.layout.")]
    assert {s.name for s in layouts} >= {
        "repro.layout.reverse", "repro.layout.reverse_bucketed",
        "repro.layout.pull_plan", "repro.layout.forward_ell"}
    assert tb["preprocess_s"] == pytest.approx(
        sum(s.self_s for s in layouts), rel=1e-9)
    (aot,), (passes,), (emit,), (whole,) = (
        by_name[f"repro.translate{k}"] for k in (".aot", ".passes", ".emit",
                                                 ""))
    assert tb["aot_s"] == aot.seconds
    assert tb["passes_s"] == passes.seconds
    assert tb["emit_s"] == emit.self_s
    assert tb["total_s"] == whole.seconds
    assert whole.seconds >= aot.seconds + passes.seconds + emit.seconds
    assert tb["preprocess_cached"] is False


def test_ingest_spans_and_a_reverse_is_not_an_ingest():
    src, dst = G.rmat_edges(64, 256, seed=1)
    with tracing.collect() as ended:
        g = G.from_edge_list(src, dst, num_vertices=64)
    assert [s.name for s in ended] == ["repro.ingest.csr",
                                       "repro.ingest.place", "repro.ingest"]
    with tracing.collect() as ended:
        r = G.reverse(g)
    assert ended == []
    assert r.num_edges == g.num_edges


def test_run_spans_nest_in_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    prog = translate(dsl.bfs_program(2**30), graph(seed=7), AUTO)
    prog.run(roots=0)               # compiled outside the trace
    with jax.profiler.trace(str(tmp_path)):
        for root in (1, 2):
            prog.run(roots=root)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro.run"):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    runs = sorted(spans["repro.run"])
    dispatch = sorted(spans["repro.run.dispatch"])
    wait = sorted(spans["repro.run.wait"])
    assert len(runs) == len(dispatch) == len(wait) == 2
    for (r0, r1), (d0, d1), (w0, w1) in zip(runs, dispatch, wait):
        assert r0 <= d0 <= d1 <= w0 <= w1 <= r1
