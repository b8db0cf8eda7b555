"""The light-weight translator: module matching, schedules, reports."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dsl
from repro.core import graph as G
from repro.core.comm import CommManager
from repro.core.scheduler import ScheduleConfig, plan, plan_for_devices
from repro.core.translator import classify_gather, translate


@pytest.fixture(scope="module")
def g():
    src, dst = G.rmat_edges(150, 1200, seed=11)
    return G.from_edge_list(src, dst, num_vertices=150)


def test_classify_gather_matches_menu():
    assert classify_gather(lambda v, w, d: v + 1, jnp.int32) == "plus_one"
    assert classify_gather(lambda v, w, d: v + w, jnp.float32) == "add_w"
    assert classify_gather(lambda v, w, d: v * w, jnp.float32) == "mul_w"
    assert classify_gather(lambda v, w, d: v, jnp.float32) == "copy"
    assert classify_gather(
        lambda v, w, d: v / jnp.maximum(d, 1).astype(v.dtype),
        jnp.float32) == "div_deg"
    # unknown gather → general path (None)
    assert classify_gather(lambda v, w, d: jnp.sin(v) * w, jnp.float32) is None


def test_unknown_gather_falls_back_to_sparse(g):
    prog = dsl.VertexProgram(
        name="custom", gather=lambda v, w, d: jnp.sin(v) * w,
        reduce="add", apply=lambda old, s: s, init_value=1.0,
        frontier="all", mask_inactive=False, max_iters=1)
    c = translate(prog, g, ScheduleConfig(backend="dense"))
    assert c.report.backend == "sparse_xla"
    assert c.report.gather_module is None
    vals, _ = c.run()
    assert np.isfinite(np.asarray(vals)).all()


def test_backend_selection_heuristic():
    cfg = ScheduleConfig(backend="auto")
    p = plan(cfg, num_vertices=100, num_edges=1000)   # avg degree 10
    assert p.backend == "dense"
    p = plan(cfg, num_vertices=1000, num_edges=1500)  # avg degree 1.5
    assert p.backend == "sparse"


def test_pipeline_chunking(g):
    for pipelines in (1, 4, 8):
        prog = translate(dsl.bfs_program(), g,
                         ScheduleConfig(pipelines=pipelines,
                                        backend="sparse"))
        assert prog.report.pipelines <= pipelines
        levels, _ = prog.run(roots=0)
        if pipelines == 1:
            base = np.asarray(levels)
        else:
            np.testing.assert_array_equal(np.asarray(levels), base)


def test_translation_report_fields(g):
    comm = CommManager()
    prog = translate(dsl.bfs_program(), g, ScheduleConfig(), comm)
    r = prog.report
    assert r.translate_time_s > 0
    assert r.est_flops_per_superstep == 2.0 * g.num_edges
    assert r.backend in ("dense_xla", "dense_pallas", "sparse_xla")
    # paper's claim: translation finishes in seconds, not minutes
    assert r.translate_time_s < 60


def test_elastic_replanning():
    cfg = ScheduleConfig(pes=8)
    p = plan_for_devices(cfg, num_devices=1, num_vertices=10, num_edges=50)
    assert p.mesh is None  # degraded to single device, no failure


def test_comm_manager_stats(g):
    comm = CommManager()
    placed = comm.transport(g)
    assert comm.stats.host_to_device_bytes > 0
    back = comm.fetch(placed.vertex_values)
    assert comm.stats.device_to_host_bytes > 0
    q, s = comm.quantize_messages(jnp.asarray([0.5, -1.0, 2.0]))
    deq = comm.dequantize_messages(q, s)
    np.testing.assert_allclose(np.asarray(deq), [0.5, -1.0, 2.0], atol=0.02)
    assert comm.estimate_collective_bytes(1000, jnp.float32, pes=4) > 0
    assert comm.estimate_collective_bytes(1000, jnp.float32, pes=1) == 0


def test_multi_pe_equivalence():
    """PE-partitioned supersteps (shard_map + pmin) ≡ single device —
    the paper's PE-scheduling knob, with disjoint edge partitions.
    Runs in-process on the conftest's forced host devices; the 4-PE
    bfs+pagerank version runs in the slow suite."""
    import jax
    import numpy as np
    from repro.core import algorithms as alg
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    src, dst = G.rmat_edges(300, 3000, seed=7)
    g2 = G.from_edge_list(src, dst, num_vertices=300)
    l1, _, _ = alg.bfs(g2, root=0, pes=1, backend="sparse")
    l2, _, rep = alg.bfs(g2, root=0, pes=2, backend="sparse")
    assert rep.pes == 2
    assert (np.asarray(l1) == np.asarray(l2)).all()


@pytest.mark.slow
def test_multi_pe_equivalence_full(subproc):
    """4-PE bfs + pagerank equivalence (heavier compiles; slow suite)."""
    out = subproc("""
import numpy as np
from repro.core import graph as G, algorithms as alg
src, dst = G.rmat_edges(300, 3000, seed=7)
g = G.from_edge_list(src, dst, num_vertices=300)
l1, _, _ = alg.bfs(g, root=0, pes=1, backend="sparse")
l4, _, rep = alg.bfs(g, root=0, pes=4, backend="sparse")
assert rep.pes == 4
assert (np.asarray(l1) == np.asarray(l4)).all()
r1, _, _ = alg.pagerank(g, iters=10, pes=1, backend="sparse")
r4, _, _ = alg.pagerank(g, iters=10, pes=4, backend="sparse")
np.testing.assert_allclose(np.asarray(r1), np.asarray(r4), rtol=1e-4)
print("MULTI_PE_OK")
""", devices=8, timeout=560)
    assert "MULTI_PE_OK" in out


def test_superstep_idempotent_when_converged(g):
    prog = translate(dsl.bfs_program(), g, ScheduleConfig())
    values, iters = prog.run(roots=0)
    # run one more superstep from the converged state: nothing changes
    v2, active = prog.superstep(values, jnp.zeros(g.num_vertices, bool))
    np.testing.assert_array_equal(np.asarray(values), np.asarray(v2))
    assert not bool(np.asarray(active).any())


def _nested_consts(jaxpr) -> list:
    """Every constant closed over anywhere in a jaxpr, sub-jaxprs included."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    out = []

    def visit(x):
        if isinstance(x, ClosedJaxpr):
            out.extend(x.consts)
            visit(x.jaxpr)
        elif isinstance(x, Jaxpr):
            for eqn in x.eqns:
                for p in eqn.params.values():
                    for q in (p if isinstance(p, (tuple, list)) else (p,)):
                        visit(q)
    visit(jaxpr)
    return out


@pytest.mark.parametrize("case", ["bfs-auto", "bfs-bitmap", "sssp-sparse",
                                  "pagerank", "bfs-pes2", "sssp-sparse-pes2"])
def test_staged_programs_take_layouts_as_arguments(g, case):
    """No staged function closes over a graph layout: the run loop, the
    serving plane's slice/admit/init programs all see the graph only
    through their ``layouts`` argument (a closed-over array would lower
    to an HLO constant the size of the graph)."""
    import jax
    from repro.core import algorithms as alg
    from repro.core.scheduler import DirectionPolicy
    if case.endswith("pes2") and len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    program = {"bfs": dsl.bfs_program(alg.INT_MAX),
               "sssp": dsl.sssp_program(),
               "pagerank": dsl.pagerank_program()}[case.split("-")[0]]
    cfg = ScheduleConfig(
        direction=DirectionPolicy(mode="auto"),
        pull_sweep="bitmap" if "bitmap" in case else "auto",
        backend="sparse" if "sparse" in case else "auto",
        pes=2 if case.endswith("pes2") else 1)
    prog = translate(program, g, cfg, aot_compile=False)
    layout_leaves = jax.tree.leaves(prog.layouts)
    assert min(a.size for a in layout_leaves) >= 1
    rooted = program.frontier == "changed"
    v, a = prog.init_state(roots=0 if rooted else None)
    state = prog.batch_idle(2)
    staged = [jax.make_jaxpr(prog.run_loop)(prog.layouts, v, a),
              jax.make_jaxpr(prog._slice_fn())(prog.layouts, state,
                                               jnp.int32(3))]
    for j in staged:
        assert all(np.size(c) <= 1 for c in _nested_consts(j))
    if cfg.pes > 1:
        sharded = prog.layouts["fwd_pe" if prog.report.exchange_plane
                               == "push" else "coo"]
        leaf = jax.tree.leaves(sharded)[0]
        assert len(leaf.sharding.device_set) == 2
