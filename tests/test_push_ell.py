"""Frontier-compacted forward-ELL push engine: layout construction,
cumsum compaction, kernel ≡ dense-scatter oracle, the preprocessing cache,
and the translate-time breakdown / staging cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms as alg
from repro.core import dsl
from repro.core import graph as G
from repro.core import preprocess as pre
from repro.core.scheduler import (DirectionPolicy, ScheduleConfig,
                                  push_capacity_tiers)
from repro.core.translator import translate
from repro.kernels import ops as kops
from repro.kernels import push_ell as pk
from repro.kernels.ref import (GATHER_OPS, REDUCE_OPS, _identity, gather_msg,
                               push_scatter_reduce_ref)

PAD = jnp.iinfo(jnp.int32).max


def _graph(V=50, E=300, seed=0, weights=True):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, V, E).astype(np.int32)
    dst = rng.integers(0, V, E).astype(np.int32)
    w = rng.uniform(0.5, 2, E).astype(np.float32) if weights else None
    return G.from_edge_list(src, dst, num_vertices=V, weights=w)


def _kernel_vs_ref(g, active, *, gather="copy", reduce="min", width=4,
                   capacity=None, dtype=np.float32, use_pallas=False):
    fe = G.forward_ell(g, width=width)
    src, dst, wgt = G.to_coo(g)
    deg = jnp.asarray(np.asarray(g.out_degrees), jnp.int32)
    rng = np.random.default_rng(1)
    if np.issubdtype(dtype, np.integer):
        vals = jnp.asarray(rng.integers(0, 50, g.num_vertices), dtype)
    else:
        vals = jnp.asarray(rng.uniform(0, 5, g.num_vertices), dtype)
    active = jnp.asarray(active)
    cap = capacity if capacity is not None else max(fe.num_rows, 1)
    got_red, got_t = kops.push_ell_reduce(
        fe.row_src, fe.dst, fe.weights, vals, deg, active,
        num_rows=fe.num_rows, capacity=cap, gather=gather, reduce=reduce,
        use_pallas=use_pallas)
    want_red, want_t = push_scatter_reduce_ref(
        jnp.asarray(src), jnp.asarray(dst), jnp.asarray(wgt), vals, deg,
        active, gather=gather, reduce=reduce)
    np.testing.assert_allclose(np.asarray(got_red), np.asarray(want_red),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_t), np.asarray(want_t))


# ---------------------------------------------------------------------------
# 1. forward-ELL layout construction
# ---------------------------------------------------------------------------


def test_forward_ell_roundtrip():
    """Every edge appears exactly once; padding slots are PAD."""
    g = _graph(V=30, E=200, seed=3)
    fe = G.forward_ell(g, width=4)
    src, dst, wgt = G.to_coo(g)
    want = sorted(zip(src.tolist(), dst.tolist(), wgt.tolist()))
    got = []
    rs = np.asarray(fe.row_src)
    ed = np.asarray(fe.dst)
    ew = np.asarray(fe.weights)
    for r in range(fe.num_rows):
        for j in range(fe.width):
            if ed[r, j] != int(PAD):
                got.append((int(rs[r]), int(ed[r, j]), float(ew[r, j])))
    assert sorted(got) == want
    # rows_per_vertex consistent with out-degrees at this width
    deg = np.asarray(g.out_degrees)
    np.testing.assert_array_equal(np.asarray(fe.rows_per_vertex),
                                  -(-deg // 4))


def test_forward_ell_hub_spans_rows():
    """A hub with out-degree > width owns several consecutive rows."""
    src = np.zeros(10, np.int32)                 # vertex 0: degree 10
    dst = np.arange(1, 11, dtype=np.int32)
    g = G.from_edge_list(src, dst, num_vertices=12)
    fe = G.forward_ell(g, width=4)
    rows_of_0 = np.nonzero(np.asarray(fe.row_src) == 0)[0]
    assert len(rows_of_0) == 3                   # ceil(10/4)
    assert int(fe.rows_per_vertex[0]) == 3
    # all 10 destinations present, 2 PAD slots in the last row
    ed = np.asarray(fe.dst)[rows_of_0]
    assert (ed != int(PAD)).sum() == 10


def test_forward_ell_empty_graph():
    g = G.from_edge_list(np.asarray([], np.int32), np.asarray([], np.int32),
                         num_vertices=5)
    fe = G.forward_ell(g, width=8)
    assert fe.num_rows == 0
    assert fe.dst.shape == (1, 8)                # dummy row, all PAD
    assert (np.asarray(fe.dst) == int(PAD)).all()


# ---------------------------------------------------------------------------
# 2. cumsum compaction
# ---------------------------------------------------------------------------


def test_compact_rows_basic():
    live = jnp.asarray([False, True, False, True, True, False])
    sel, ok = pk.compact_rows(live, 6, 4)
    np.testing.assert_array_equal(np.asarray(sel[:3]), [1, 3, 4])
    np.testing.assert_array_equal(np.asarray(ok), [True, True, True, False])


def test_compact_rows_empty_and_full():
    sel, ok = pk.compact_rows(jnp.zeros(5, bool), 5, 3)
    assert not np.asarray(ok).any()
    sel, ok = pk.compact_rows(jnp.ones(5, bool), 5, 5)
    np.testing.assert_array_equal(np.asarray(sel), np.arange(5))
    assert np.asarray(ok).all()


def test_compact_rows_overflow_drops_tail():
    """Capacity below the live count keeps the first `capacity` rows —
    the runtime tier guard must prevent this ever mattering."""
    sel, ok = pk.compact_rows(jnp.ones(6, bool), 6, 3)
    np.testing.assert_array_equal(np.asarray(sel), [0, 1, 2])
    assert np.asarray(ok).all()


# ---------------------------------------------------------------------------
# 3. kernel ≡ dense push-scatter oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("gather", GATHER_OPS)
@pytest.mark.parametrize("reduce", REDUCE_OPS)
def test_push_ell_matches_ref(gather, reduce):
    g = _graph(V=60, E=400, seed=5)
    act = np.random.default_rng(2).random(60) < 0.3
    dtype = np.int32 if (gather, reduce) == ("plus_one", "min") else np.float32
    _kernel_vs_ref(g, act, gather=gather, reduce=reduce, dtype=dtype)


def test_push_ell_empty_frontier():
    g = _graph(V=40, E=200, seed=6)
    _kernel_vs_ref(g, np.zeros(40, bool), gather="copy", reduce="min")


def test_push_ell_all_active_frontier():
    g = _graph(V=40, E=200, seed=7)
    _kernel_vs_ref(g, np.ones(40, bool), gather="add_w", reduce="max")


def test_push_ell_hub_spanning_rows():
    """An active hub whose rows exceed one width must scatter all edges."""
    src = np.concatenate([np.zeros(20, np.int32),
                          np.asarray([1, 2, 3], np.int32)])
    dst = np.concatenate([np.arange(1, 21, dtype=np.int32),
                          np.asarray([5, 6, 7], np.int32)])
    g = G.from_edge_list(src, dst, num_vertices=25)
    act = np.zeros(25, bool)
    act[0] = True                                  # only the hub is active
    _kernel_vs_ref(g, act, gather="plus_one", reduce="min", width=4,
                   dtype=np.int32)


def test_push_ell_pad_slot_safety():
    """PAD slots must not contribute — even with adversarial values."""
    src = np.asarray([0, 0, 0, 2], np.int32)       # degree 3 -> 1 PAD at w=4
    dst = np.asarray([1, 3, 4, 1], np.int32)
    g = G.from_edge_list(src, dst, num_vertices=5)
    fe = G.forward_ell(g, width=4)
    vals = jnp.asarray([-7.0, 1.0, -2.0, 3.0, 4.0])
    deg = jnp.asarray(np.asarray(g.out_degrees), jnp.int32)
    act = jnp.asarray([True, False, True, False, False])
    red, touched = kops.push_ell_reduce(
        fe.row_src, fe.dst, fe.weights, vals, deg, act,
        num_rows=fe.num_rows, capacity=4, gather="copy", reduce="max")
    # vertex 0 and 2 are the sources; dst 1 gets max(-7, -2), dsts 3/4 get -7
    np.testing.assert_allclose(np.asarray(red)[[1, 3, 4]], [-2.0, -7.0, -7.0])
    assert not np.asarray(touched)[[0, 2]].any()   # untouched stay untouched


def test_push_ell_capacity_tier_exact_fit():
    """capacity == live rows (the tightest legal tier) is still exact."""
    g = _graph(V=30, E=150, seed=9)
    fe = G.forward_ell(g, width=4)
    act = np.zeros(30, bool)
    act[[3, 7, 11]] = True
    r_f = int(np.asarray(fe.rows_per_vertex)[act].sum())
    _kernel_vs_ref(g, act, gather="mul_w", reduce="add", capacity=r_f)


def test_push_ell_pallas_interpret_matches_xla():
    """The Pallas message-block variant (interpret mode) ≡ the XLA form."""
    g = _graph(V=20, E=80, seed=11)
    act = np.random.default_rng(3).random(20) < 0.5
    _kernel_vs_ref(g, act, gather="add_w", reduce="min", use_pallas=True)


def test_push_capacity_tiers_shape():
    small, large = push_capacity_tiers(80_000)
    assert small < large
    assert small & (small - 1) == 0 and large & (large - 1) == 0
    assert push_capacity_tiers(0) == (256, 512)


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_push_ell_property(dtype):
    """Hypothesis sweep: push_ell ≡ push_scatter_reduce_ref on random
    graphs, frontiers, widths, and reduce ops (skips without hypothesis)."""
    hypothesis = pytest.importorskip(
        "hypothesis", reason="property tests need the hypothesis package")
    from hypothesis import given, settings, strategies as st

    @st.composite
    def cases(draw):
        v = draw(st.integers(2, 24))
        e = draw(st.integers(0, 60))
        seed = draw(st.integers(0, 2**16))
        width = draw(st.sampled_from([1, 2, 4, 8]))
        frac = draw(st.floats(0.0, 1.0))
        reduce = draw(st.sampled_from(REDUCE_OPS))
        gather = draw(st.sampled_from(
            ["copy", "plus_one", "add_w", "mul_w"]))
        return v, e, seed, width, frac, reduce, gather

    @given(cases())
    @settings(max_examples=25, deadline=None)
    def check(case):
        v, e, seed, width, frac, reduce, gather = case
        rng = np.random.default_rng(seed)
        g = G.from_edge_list(rng.integers(0, v, e).astype(np.int32),
                             rng.integers(0, v, e).astype(np.int32),
                             num_vertices=v)
        act = rng.random(v) < frac
        _kernel_vs_ref(g, act, gather=gather, reduce=reduce, width=width,
                       dtype=dtype)

    check()


# ---------------------------------------------------------------------------
# 3b. full push (the wide-frontier branch) ≡ dense pull sweep ≡ oracle
# ---------------------------------------------------------------------------

FULL_V = 48
# out-degree (7i mod 19) for vertex i: 429 edges in R = 75 width-8 rows
FULL_DEG = (np.arange(FULL_V) * 7) % 19
# chunk sizes against R = 75: 3 whole chunks, 3 chunks with the last one
# clamped onto 21 rows the second already took, one chunk past R
FULL_CHUNKS = {"multiple": 25, "ragged": 32, "one_chunk": 128}
# (reduce, form) -> (menu gather, value dtype): the table form needs a
# weight-free gather, the masked form one the reduce identity absorbs
# through (inf + w, -inf + w, 0 * w); the classic form takes any gather
FULL_CASES = {
    ("min", "table"): ("plus_one", np.float32),
    ("min", "masked"): ("add_w", np.float32),
    ("min", "classic"): ("add_w", np.float32),
    ("max", "table"): ("plus_one", np.float32),
    ("max", "masked"): ("add_w", np.float32),
    ("max", "classic"): ("add_w", np.float32),
    ("add", "table"): ("plus_one", np.int32),
    ("add", "masked"): ("mul_w", np.int32),
    ("add", "classic"): ("add_w", np.int32),
}
_FULL_FNS: dict = {}


def _full_graph(layout):
    if layout == "edgeless":
        none = np.zeros(0, np.int32)
        return G.from_edge_list(none, none, num_vertices=FULL_V)
    rng = np.random.default_rng(41)
    src = np.repeat(np.arange(FULL_V, dtype=np.int32), FULL_DEG)
    dst = rng.integers(0, FULL_V, len(src)).astype(np.int32)
    w = rng.uniform(0.5, 3, len(src)).astype(np.float32)
    return G.from_edge_list(src, dst, num_vertices=FULL_V, weights=w)


def _dense_pull_sweep(g, gather, reduce, dtype):
    """The pull plane's dense sweep (the push plane's wide-frontier branch
    before the full push) as a jitted ``(values, active) -> red``."""
    from repro.core import translator as tr
    from repro.core.ir import FusedGatherReduceOp, lower_program
    from repro.core.passes import PassContext, default_pipeline
    from repro.core.scheduler import plan

    apply = {"min": jnp.minimum, "max": jnp.maximum, "add": jnp.add}[reduce]
    program = dsl.VertexProgram(
        name=f"sweep_{gather}_{reduce}",
        gather=lambda v, w, d: gather_msg(gather, v, w, d), reduce=reduce,
        apply=apply, init_value=0, value_dtype=dtype)
    cfg = ScheduleConfig()
    ctx = PassContext(schedule=cfg, plan=plan(
        cfg, num_vertices=g.num_vertices, num_edges=g.num_edges),
        use_pallas=False, num_vertices=g.num_vertices,
        num_edges=g.num_edges)
    ir, _ = default_pipeline().run(lower_program(program), ctx)
    fused = ir.find(FusedGatherReduceOp)
    if fused is None:
        fused = ir.find(tr.FusedSuperstepOp).fused
    pplan = pre.layouts_for(g).pull_plan(cfg.pull_block_slots)
    sweep, _ = tr._emit_dense_pull_reduce(ir, fused, pplan, cfg, False)
    layouts = {"pull": tr._pull_device_arrays(pplan),
               "deg": jnp.asarray(np.asarray(g.out_degrees), jnp.int32)}
    return jax.jit(lambda values, active: sweep(layouts, values, active)[0])


def _full_fns(layout, reduce, form):
    key = (layout, reduce, form)
    if key not in _FULL_FNS:
        gather, dtype = FULL_CASES[reduce, form]
        g = _full_graph(layout)
        fe = G.forward_ell(g, width=8)
        deg = jnp.asarray(np.asarray(g.out_degrees), jnp.int32)
        chunk = FULL_CHUNKS.get(layout, 16)
        ident = _identity(reduce, jnp.dtype(dtype))

        def full(values, active):
            return pk.full_push_reduce(
                fe.row_src, fe.dst.T, fe.weights.T, values, deg, active,
                num_rows=fe.num_rows, chunk=chunk, form=form,
                gather_fn=lambda v, w, d: gather_msg(gather, v, w, d),
                reduce=reduce, identity=ident, num_vertices=g.num_vertices,
                dtype=jnp.dtype(dtype), gather_module=gather)

        src, dst, wgt = (jnp.asarray(a) for a in G.to_coo(g))
        _FULL_FNS[key] = (
            fe, dtype, jax.jit(full),
            _dense_pull_sweep(g, gather, reduce, dtype),
            lambda values, active: push_scatter_reduce_ref(
                src, dst, wgt, values, deg, active, gather=gather,
                reduce=reduce)[0])
    return _FULL_FNS[key]


def _frontier(kind):
    if kind == "empty":
        return np.zeros(FULL_V, bool)
    if kind == "one":
        one = np.zeros(FULL_V, bool)
        one[int(np.argmax(FULL_DEG))] = True       # a hub: 3 rows
        return one
    if kind == "half":
        return np.random.default_rng(43).random(FULL_V) < 0.5
    return np.ones(FULL_V, bool)


@pytest.mark.parametrize("frontier", ["empty", "one", "half", "all"])
@pytest.mark.parametrize("layout",
                         [*FULL_CHUNKS, "edgeless"])
@pytest.mark.parametrize("form", pk.MESSAGE_FORMS)
@pytest.mark.parametrize("reduce", ["min", "max", "add"])
def test_full_push_matches_dense_sweep_and_ref(reduce, form, layout,
                                               frontier):
    """The full push over every forward-ELL row equals the dense pull
    sweep and the push-scatter oracle bit for bit (min, max, integer
    add), whether R is a whole number of chunks, a ragged one (the
    clamped last chunk must not count rows twice under add), below one
    chunk, or 0."""
    fe, dtype, full, sweep, ref = _full_fns(layout, reduce, form)
    if layout == "edgeless":
        assert fe.num_rows == 0
    else:
        assert fe.num_rows == 75
        chunk = FULL_CHUNKS[layout]
        assert {"multiple": fe.num_rows % chunk == 0,
                "ragged": 0 < fe.num_rows % chunk < fe.num_rows,
                "one_chunk": fe.num_rows < chunk}[layout]
    rng = np.random.default_rng(44)
    if dtype == np.int32:
        values = jnp.asarray(rng.integers(-20, 50, FULL_V), jnp.int32)
    else:
        values = jnp.asarray(rng.uniform(-5, 5, FULL_V), jnp.float32)
    active = jnp.asarray(_frontier(frontier))
    got = np.asarray(full(values, active))
    np.testing.assert_array_equal(got, np.asarray(sweep(values, active)))
    np.testing.assert_array_equal(got, np.asarray(ref(values, active)))
    if frontier == "empty" or layout == "edgeless":
        assert (got == np.asarray(_identity(reduce, jnp.dtype(dtype)),
                                  dtype)).all()


# ---------------------------------------------------------------------------
# 4. preprocessing cache + translate-time breakdown + staging cache
# ---------------------------------------------------------------------------


def test_layout_cache_hits_same_graph():
    pre.layout_cache_clear()
    g = _graph(V=40, E=200, seed=13)
    lay1 = pre.layouts_for(g)
    lay2 = pre.layouts_for(g)
    assert lay1 is lay2
    assert pre.layout_cache_info()["hits"] == 1
    # structural identity, not wrapper identity: with_values still hits
    assert pre.layouts_for(g.with_values(jnp.zeros(40))) is lay1
    # a different graph misses
    assert pre.layouts_for(_graph(V=40, E=200, seed=14)) is not lay1


def test_layout_cache_builds_once():
    pre.layout_cache_clear()
    g = _graph(V=40, E=200, seed=15)
    lay = pre.layouts_for(g)
    b1 = lay.reverse_bucketed()
    f1 = lay.forward_ell(8)
    assert lay.reverse_bucketed() is b1
    assert lay.forward_ell(8) is f1
    assert lay.forward_ell(4) is not f1          # width-keyed
    assert set(lay.build_times_s) >= {"reverse", "reverse_bucketed",
                                      "forward_ell_w8", "forward_ell_w4"}


def test_translate_breakdown_and_staging_cache():
    g = _graph(V=60, E=500, seed=16)
    prog = dsl.bfs_program(alg.INT_MAX)
    cfg = ScheduleConfig()
    c1 = translate(prog, g, cfg)
    bd1 = c1.report.translate_breakdown
    assert bd1 is not None and not bd1["staging_cached"]
    assert bd1["total_s"] >= bd1["passes_s"]
    # repeat translate of identical (program, graph, schedule): staged
    c2 = translate(prog, g, cfg)
    bd2 = c2.report.translate_breakdown
    assert bd2["staging_cached"]
    assert bd2["preprocess_s"] == 0.0
    assert c2._superstep is c1._superstep        # same jitted executable
    # and results stay identical
    v1, i1 = c1.run(roots=0)
    v2, i2 = c2.run(roots=0)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    assert int(i1) == int(i2)
    # a different schedule re-stages
    c3 = translate(prog, g, ScheduleConfig(pipelines=4))
    assert not c3.report.translate_breakdown["staging_cached"]


def test_staging_cache_survives_layout_eviction():
    """The staging cache keys on the graph's structure arrays, so pushing
    the graph out of the layout LRU must not orphan its staged supersteps."""
    from repro.core import translator as tr
    pre.layout_cache_clear()
    tr.staging_cache_clear()
    g = _graph(V=40, E=300, seed=31)
    prog = dsl.bfs_program(alg.INT_MAX)
    cfg = ScheduleConfig()
    translate(prog, g, cfg)
    for s in range(9):                       # evict g from the layout LRU
        pre.layouts_for(_graph(V=20, E=60, seed=100 + s))
    c = translate(prog, g, cfg)
    assert c.report.translate_breakdown["staging_cached"]


def test_translate_unhashable_program_skips_cache():
    g = _graph(V=20, E=80, seed=17)
    prog = dsl.VertexProgram(
        name="arr_init", gather=lambda v, w, d: v, reduce="min",
        apply=jnp.minimum, init_value=jnp.full((20,), 9.0))
    c = translate(prog, g, ScheduleConfig())      # must not raise
    assert not c.report.translate_breakdown["staging_cached"]


# ---------------------------------------------------------------------------
# 5. engine-level: tier guard, fallback accounting, sparse layout kept
# ---------------------------------------------------------------------------


def _levels_ref(g, root):
    """BFS levels by a host frontier loop (-1 where unreached)."""
    off, dst = np.asarray(g.edge_offsets), np.asarray(g.edges_dst)
    level = np.full(g.num_vertices, -1)
    level[root], frontier, d = 0, [root], 0
    while frontier:
        d += 1
        nxt = np.unique(np.concatenate(
            [dst[off[v]:off[v + 1]] for v in frontier]))
        nxt = nxt[level[nxt] < 0]
        level[nxt] = d
        frontier = list(nxt)
    return level


def _dist_ref(g, root):
    """Dijkstra in float32 arithmetic: each path's left-to-right float32
    sum, minimised — the fixpoint the min-plus supersteps reach."""
    import heapq
    off, dst = np.asarray(g.edge_offsets), np.asarray(g.edges_dst)
    w = np.asarray(g.edge_weights, np.float32)
    dist = np.full(g.num_vertices, np.inf, np.float32)
    dist[root] = 0
    heap = [(np.float32(0), root)]
    while heap:
        dv, v = heapq.heappop(heap)
        if dv > dist[v]:
            continue
        for e in range(off[v], off[v + 1]):
            nd = dv + w[e]
            if nd < dist[dst[e]]:
                dist[dst[e]] = nd
                heapq.heappush(heap, (nd, int(dst[e])))
    return dist


@pytest.fixture(scope="module")
def wide_graph():
    """A symmetric weighted R-MAT graph with ~4.5k forward-ELL rows, so
    a BFS level or an all-active start exceeds the large tier (R/16)."""
    src, dst = G.rmat_edges(4096, 16384, seed=21)
    w = np.random.default_rng(21).uniform(0.5, 2, len(src)).astype(np.float32)
    return G.from_edge_list(np.concatenate([src, dst]),
                            np.concatenate([dst, src]), num_vertices=4096,
                            weights=np.concatenate([w, w]))


@pytest.mark.parametrize("name", ["wcc", "bfs", "sssp"])
def test_push_run_uses_fallback_beyond_tiers(name, wide_graph):
    """A frontier wider than the largest tier must take the full-push
    fallback (recorded in run_stats) and stay bit-exact: against the
    pull-pinned run, and for bfs / sssp against host references."""
    g = wide_graph
    assert G.forward_ell(g, width=8).num_rows >= 4096
    program = {"wcc": dsl.wcc_program, "sssp": dsl.sssp_program,
               "bfs": lambda: dsl.bfs_program(alg.INT_MAX)}[name]()
    root = None if name == "wcc" else int(np.argmax(g.out_degrees))
    c = translate(program, g,
                  ScheduleConfig(direction=DirectionPolicy(mode="push")))
    assert c.report.push_layout == "fwd_ell"
    got, _ = c.run(roots=root)                     # wcc: all-active start
    stats = c.last_run_stats
    assert stats["push_fallback_supersteps"] >= 1
    assert stats["push_compacted_supersteps"] \
        + stats["push_fallback_supersteps"] == stats["push_supersteps"]
    c_pull = translate(program, g,
                       ScheduleConfig(direction=DirectionPolicy(mode="pull")))
    want, _ = c_pull.run(roots=root)
    got = np.asarray(got)
    np.testing.assert_array_equal(got, np.asarray(want))
    if name == "bfs":
        level = _levels_ref(g, root)
        np.testing.assert_array_equal(
            got, np.where(level < 0, alg.INT_MAX, level))
    elif name == "sssp":
        np.testing.assert_array_equal(got, _dist_ref(g, root))


def test_sparse_backend_keeps_coo_chunks_layout():
    """The sparse backend has no forward ELL: push uses the legacy
    chunk-streamed scatter, and stays bit-exact."""
    src, dst = G.rmat_edges(200, 400, seed=22)     # avg degree 2 -> sparse
    g = G.from_edge_list(src, dst, num_vertices=200)
    c = translate(dsl.bfs_program(alg.INT_MAX), g,
                  ScheduleConfig(direction=DirectionPolicy(mode="auto")))
    assert c.report.backend == "sparse_xla"
    assert c.report.push_layout == "coo_chunks"
    assert c.report.push_tiers is None
    lv, it = c.run(roots=0)
    c_pull = translate(dsl.bfs_program(alg.INT_MAX), g,
                       ScheduleConfig(direction=DirectionPolicy(mode="pull")))
    want, it2 = c_pull.run(roots=0)
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(want))
    assert int(it) == int(it2)


def test_apply_fixpoint_probe_downgrades_layout():
    """A non-fixpoint apply (overwrite) keeps push legal but must get the
    touched-mask coo_chunks layout, not the compacted engine."""
    g = _graph(V=30, E=300, seed=23)               # avg degree 10 -> dense
    prog = dsl.VertexProgram(
        name="overwrite", gather=lambda v, w, d: v, reduce="min",
        apply=lambda old, s: s, init_value=0.0, frontier="changed")
    # apply(x, identity=inf) = inf != x -> not a fixpoint, still push-legal
    c = translate(prog, g, ScheduleConfig(), dump_passes=True)
    assert c.report.directions == ("pull", "push")
    assert c.report.push_layout == "coo_chunks"
    assert c.report.push_tiers is None
    assert "not an identity fixpoint" in c.report.pass_report
