"""Multi-PE direction optimization: the sharded forward-ELL push engine,
the cross-PE exchange plane (full-precision and int8-quantized), plan/staged
chunk-geometry consistency, elastic re-planning, and degenerate graphs.

Runs in-process on the conftest's forced host devices
(``--xla_force_host_platform_device_count=8``) — no subprocess round trips,
so the whole matrix stays inside the fast tier-1 suite.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms as alg
from repro.core import dsl
from repro.core import graph as G
from repro.core.comm import CommManager
from repro.core.scheduler import (DirectionPolicy, ScheduleConfig, plan,
                                  plan_for_devices)
from repro.core.translator import translate
from repro.errors import DeviceCountError

needs2 = pytest.mark.skipif(len(jax.devices()) < 2,
                            reason="needs >= 2 devices")
needs4 = pytest.mark.skipif(len(jax.devices()) < 4,
                            reason="needs >= 4 devices")


@pytest.fixture(scope="module")
def g():
    src, dst = G.rmat_edges(400, 4000, seed=11)      # avg degree 10 → dense
    w = np.random.default_rng(11).uniform(0.5, 2, len(src)).astype(np.float32)
    return G.from_edge_list(src, dst, num_vertices=400, weights=w)


def _cfg(pes, mode="auto", **kw):
    return ScheduleConfig(pes=pes, direction=DirectionPolicy(mode=mode), **kw)


# ---------------------------------------------------------------------------
# 1. the per-PE forward-ELL interval partition
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pes", [1, 2, 3, 4, 7])
def test_sharded_forward_ell_partition(g, pes):
    """Intervals are contiguous, vertex-aligned, degree-balanced, and
    round-trip to the original forward ELL rows."""
    fe = G.forward_ell(g)
    sfe = G.shard_forward_ell(fe, pes)
    assert sum(sfe.rows_per_pe) == fe.num_rows
    assert sum(sfe.edges_per_pe) == g.num_edges
    rs = np.concatenate([np.asarray(sfe.row_src)[p, :n]
                         for p, n in enumerate(sfe.rows_per_pe)])
    ds = np.concatenate([np.asarray(sfe.dst)[p, :n]
                         for p, n in enumerate(sfe.rows_per_pe)])
    np.testing.assert_array_equal(rs, np.asarray(fe.row_src)[:fe.num_rows])
    np.testing.assert_array_equal(ds, np.asarray(fe.dst)[:fe.num_rows])
    # vertex-aligned cuts: a vertex's rows never straddle two PEs
    row_src = np.asarray(sfe.row_src)
    for p in range(pes - 1):
        n = sfe.rows_per_pe[p]
        if n and sfe.rows_per_pe[p + 1]:
            assert row_src[p, n - 1] != row_src[p + 1, 0]
    # degree balance: no PE owns more than 2x its fair edge share (+ the
    # largest single vertex, which is indivisible under vertex alignment)
    if pes > 1:
        fair = g.num_edges / pes
        hub = int(np.asarray(g.out_degrees).max())
        assert max(sfe.edges_per_pe) <= 2 * fair + hub


def test_shard_forward_ell_edgeless():
    g0 = G.from_edge_list(np.array([], np.int32), np.array([], np.int32),
                          num_vertices=5)
    sfe = G.shard_forward_ell(G.forward_ell(g0), 3)
    assert sfe.rows_per_pe == (0, 0, 0)
    assert sfe.edges_per_pe == (0, 0, 0)


# ---------------------------------------------------------------------------
# 2. sharded push ≡ single PE, bit-exact (the tentpole acceptance)
# ---------------------------------------------------------------------------


@needs4
@pytest.mark.parametrize("pes", [2, 4])
@pytest.mark.parametrize("mode", ["push", "auto"])
@pytest.mark.parametrize("template", ["bfs", "sssp", "wcc"])
def test_push_bit_exact_across_pes(g, template, mode, pes):
    """pes ∈ {2, 4} push/auto ≡ pes=1, with real push supersteps executed
    under the sharded engine (the single-PE legality pin is gone)."""
    program = dsl.PROGRAM_TEMPLATES[template]()
    roots = 0 if template != "wcc" else None
    base_prog = translate(program, g, _cfg(1, mode))
    base, base_iters = base_prog.run(roots=roots)
    prog = translate(program, g, _cfg(pes, mode))
    vals, iters = prog.run(roots=roots)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(vals))
    assert int(iters) == int(base_iters)
    rep = prog.report
    assert rep.pes == pes
    assert rep.directions == ("pull", "push")
    assert rep.exchange_plane == "push"
    assert rep.push_pe_rows is not None and len(rep.push_pe_rows) == pes
    s = rep.run_stats
    assert s["push_supersteps"] >= 1          # push actually engaged
    assert s["pes"] == pes
    assert len(s["push_live_rows_per_pe"]) == pes
    if s["push_compacted_supersteps"]:
        assert sum(s["push_live_rows_per_pe"]) > 0
        assert s["exchange_supersteps"] == s["push_compacted_supersteps"]


@needs2
def test_all_templates_translate_and_match_under_multi_pe(g):
    """Every DSL template runs under a pes=2 plan and matches pes=1 —
    sharded (push plane / sparse pull plane) or replicated (dense pull)."""
    for name, factory in dsl.PROGRAM_TEMPLATES.items():
        program = factory()
        roots = 0 if program.frontier == "changed" else None
        base, i1 = translate(program, g, _cfg(1)).run(roots=roots)
        prog = translate(program, g, _cfg(2))
        vals, i2 = prog.run(roots=roots)
        assert int(i1) == int(i2), name
        np.testing.assert_array_equal(np.asarray(base), np.asarray(vals),
                                      err_msg=name)


@needs2
def test_multi_pe_legality_notes(g):
    """Per-PE legality facts are recorded: legal programs note the PE
    count, non-fixpoint applies pin with the multi-PE reason."""
    c = translate(dsl.bfs_program(alg.INT_MAX), g, _cfg(2),
                  dump_passes=True)
    assert "push legal across pes=2" in c.report.pass_report
    # overwrite-style apply: fixpoint probe fails → single-PE would take
    # coo_chunks, multi-PE pins to pull with the data-path reason
    prog = dsl.VertexProgram(
        name="overwrite", gather=lambda v, w, d: v + 1, reduce="min",
        apply=lambda old, s: s, init_value=2**30, value_dtype=jnp.int32)
    c2 = translate(prog, g, _cfg(2), dump_passes=True)
    assert c2.report.directions == ("pull",)
    assert "identity-fixpoint" in c2.report.pass_report
    c3 = translate(dsl.bfs_program(alg.INT_MAX), g,
                   _cfg(2, backend="sparse"), dump_passes=True)
    assert c3.report.directions == ("pull",)
    assert "sparse plan shards the pull plane" in c3.report.pass_report


# ---------------------------------------------------------------------------
# 3. plan owns the chunk/PE arithmetic (the headline bugfix)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pes", [1, 2, 3])
def test_plan_geometry_matches_staged_arrays(g, pes):
    """`SchedulePlan` chunk geometry is exactly what the sparse module
    stages — including the non-divisible pipelines/pes pair (pes=3), which
    previously diverged silently via a translator-side re-round."""
    if len(jax.devices()) < pes:
        pytest.skip("needs more devices")
    cfg = ScheduleConfig(pipelines=8, pes=pes, backend="sparse")
    p = plan(cfg, num_vertices=g.num_vertices, num_edges=g.num_edges)
    c = translate(dsl.bfs_program(alg.INT_MAX), g, cfg, dump_passes=True)
    assert c.report.staged_chunks == (p.num_chunks, p.chunk_size)
    assert c.report.pipelines == p.num_chunks
    assert p.num_chunks % max(p.pes, 1) == 0
    assert p.num_chunks * p.chunk_size >= g.num_edges
    assert f"pipelines={p.num_chunks} chunk_size={p.chunk_size}" \
        in p.describe()
    assert p.describe() in c.report.pass_report   # pass dump agrees too


def test_plan_edgeless_graph_geometry():
    """num_edges=0 keeps a well-formed (1-chunk, 1-slot) geometry."""
    p = plan(ScheduleConfig(), num_vertices=10, num_edges=0)
    assert p.num_chunks >= 1 and p.chunk_size >= 1


@pytest.mark.parametrize("pes", [1, 2])
def test_edgeless_graph_end_to_end(pes):
    """translate()+run() on a 0-edge graph: bfs reaches only the root,
    wcc keeps every vertex its own component."""
    if len(jax.devices()) < pes:
        pytest.skip("needs more devices")
    g0 = G.from_edge_list(np.array([], np.int32), np.array([], np.int32),
                          num_vertices=6)
    lv, it = translate(dsl.bfs_program(alg.INT_MAX), g0,
                       _cfg(pes)).run(roots=2)
    want = np.full(6, alg.INT_MAX)
    want[2] = 0
    np.testing.assert_array_equal(np.asarray(lv), want)
    assert int(it) == 1
    labels, _ = translate(dsl.wcc_program(), g0, _cfg(pes)).run()
    np.testing.assert_array_equal(np.asarray(labels), np.arange(6))


# ---------------------------------------------------------------------------
# 4. elastic re-planning under a degraded device pool
# ---------------------------------------------------------------------------


@needs2
def test_elastic_degrade_replan_multi_pe(g):
    """plan_for_devices clamps to the surviving pool; an over-asked
    ScheduleConfig is refused by translate, and the re-planned schedule
    runs on the pool and still matches."""
    cfg = ScheduleConfig(pes=8)
    p = plan_for_devices(cfg, num_devices=3, num_vertices=g.num_vertices,
                         num_edges=g.num_edges)
    assert p.pes == min(3, len(jax.devices()))
    assert p.num_chunks % p.pes == 0
    # asking for more PEs than devices exist: translate refuses with the
    # typed error; the explicit re-plan onto the pool runs bit-exact
    with pytest.raises(DeviceCountError):
        translate(dsl.bfs_program(alg.INT_MAX), g, _cfg(16))
    base, _ = translate(dsl.bfs_program(alg.INT_MAX), g, _cfg(1)).run(roots=0)
    replanned = plan_for_devices(_cfg(16), num_devices=16,
                                 num_vertices=g.num_vertices,
                                 num_edges=g.num_edges)
    prog = translate(dsl.bfs_program(alg.INT_MAX), g, replanned.config)
    vals, _ = prog.run(roots=0)
    assert prog.report.pes == len(jax.devices())
    np.testing.assert_array_equal(np.asarray(base), np.asarray(vals))


# ---------------------------------------------------------------------------
# 5. the quantized exchange (int8 wire format) and its escape hatch
# ---------------------------------------------------------------------------


@needs4
def test_quantized_exchange_pagerank_tolerance(g):
    """int8-quantized combine: the *per-combine* error bound
    (pes * scale, pinned exactly in test_quantized_psum_unit_bound)
    compounds graph-dependently across iterations, so this pins the
    damped heuristic d/(1-d) * pes * scale on this acceptance graph as
    a regression tripwire (docs/architecture.md documents why no
    a-priori end-to-end bound exists); unquantized float-add exchange
    matches within reassociation noise only (psum reorders partials)."""
    iters, damping = 10, 0.85
    r1, _, _ = alg.pagerank(g, iters=iters, damping=damping, pes=1,
                            backend="sparse")
    r4, _, rep4 = alg.pagerank(g, iters=iters, damping=damping, pes=4,
                               backend="sparse")
    assert rep4.exchange_plane == "pull" and not rep4.exchange_quantized
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r4), rtol=1e-5)

    cq = translate(dsl.pagerank_program(damping, iters), g,
                   ScheduleConfig(pes=4, backend="sparse",
                                  message_dtype="int8"))
    rq, _ = cq.run()
    assert cq.report.exchange_quantized
    pes = cq.report.pes
    scale = np.abs(np.asarray(r1)).max() / 127.0
    bound = damping / (1 - damping) * pes * scale + 1e-6
    err = np.abs(np.asarray(rq) - np.asarray(r1)).max()
    assert err <= bound, (err, bound)


@needs2
def test_quantization_escape_hatch_keeps_min_reduce_exact(g):
    """message_dtype='int8' must not touch min/max or integer reduces —
    bfs over the sparse sharded pull plane stays bit-exact."""
    base, _, _ = alg.bfs(g, root=0, pes=1, backend="sparse")
    c = translate(dsl.bfs_program(alg.INT_MAX), g,
                  ScheduleConfig(pes=2, backend="sparse",
                                 message_dtype="int8"))
    lv, _ = c.run(roots=0)
    assert not c.report.exchange_quantized
    np.testing.assert_array_equal(np.asarray(base), np.asarray(lv))


@needs4
def test_quantized_psum_unit_bound():
    """CommManager.quantized_psum: |result − exact| <= pes * scale
    (pes·scale/2 from quantizing the partials + pes·scale/2 from
    re-quantizing the chunk sums in the reduce-scatter phase)."""
    from repro.core._jax_compat import make_mesh, shard_map_unchecked
    from jax.sharding import PartitionSpec as P
    pes = 4
    mesh = make_mesh((pes,), ("pe",), devices=jax.devices()[:pes])
    # 67 elements: also exercises the pad-to-multiple-of-pes chunking
    x = jnp.asarray(np.random.default_rng(0).normal(size=(pes, 67)),
                    jnp.float32)
    out = shard_map_unchecked(
        lambda s: CommManager.quantized_psum(s[0], "pe", pes=pes),
        mesh=mesh, in_specs=(P("pe"),), out_specs=P())(x)
    exact = np.asarray(x).sum(axis=0)
    scale = np.abs(np.asarray(x)).max() / 127.0
    assert np.abs(np.asarray(out) - exact).max() <= pes * scale + 1e-6


@needs2
def test_run_batch_multi_pe_matches_sequential(g):
    """vmapped batched runs work over the sharded push engine too
    (shard_map_unchecked: the vma checker cannot type a vmapped
    shard_map)."""
    prog = translate(dsl.bfs_program(alg.INT_MAX), g, _cfg(2))
    roots = [0, 7, 31]
    bv, bi = prog.run_batch(roots)
    for k, root in enumerate(roots):
        sv, si = prog.run(roots=root)
        np.testing.assert_array_equal(np.asarray(bv[k]), np.asarray(sv))
        assert int(bi[k]) == int(si)


# ---------------------------------------------------------------------------
# 6. exchange stats recorded from the run loop (not just estimated)
# ---------------------------------------------------------------------------


@needs2
def test_exchange_stats_recorded_from_run_loop(g):
    """comm.stats accumulates the *executed* exchange traffic per run."""
    comm = CommManager()
    prog = translate(dsl.bfs_program(alg.INT_MAX), g, _cfg(2), comm)
    assert comm.stats.collective_bytes_total == 0       # nothing ran yet
    prog.run(roots=0)
    s = prog.last_run_stats
    assert s["exchange_supersteps"] == s["push_compacted_supersteps"]
    assert s["exchange_bytes"] == \
        s["exchange_supersteps"] * prog.report.est_collective_bytes
    assert comm.stats.collective_supersteps == s["exchange_supersteps"]
    assert comm.stats.collective_bytes_total == s["exchange_bytes"]
    prog.run(roots=0)                                   # totals accumulate
    assert comm.stats.collective_bytes_total == 2 * s["exchange_bytes"]
    rep = comm.report()
    assert rep["collective_bytes_total"] == 2 * s["exchange_bytes"]
    assert rep["collective_supersteps"] == 2 * s["exchange_supersteps"]
