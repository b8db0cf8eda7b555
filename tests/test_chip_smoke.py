"""``chip_smoke.py`` at a small scale on the CPU, through its own functions.

The script itself refuses to run without a TPU; these tests drive its
phases (graph from the seed, engine BFS/SSSP, the serving plane) at R-MAT
scale 10 and check that its NumPy references agree with the engine, and
that ``main()`` refuses the CPU before doing any work.
"""
import importlib.util
import pathlib

import jax
import numpy as np
import pytest

from repro.core import graph as G

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCALE = 10


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def setup(smoke):
    V, src, dst, w = smoke.build_edges(SCALE, seed=0)
    g = G.from_edge_list(src, dst, num_vertices=V, weights=w)
    roots = smoke.pick_roots(src, V, 4, seed=0)
    return g, roots, smoke.Reference(V, src, dst, w), (src, dst, w)


def test_graph_and_roots_come_from_the_seed(smoke, setup):
    g, roots, _, (src, dst, w) = setup
    assert g.num_vertices == 1 << SCALE
    assert g.num_edges == smoke.EDGEFACTOR << SCALE
    assert w.dtype == np.float32 and 0.0 <= w.min() and w.max() < 1.0
    deg = np.bincount(src, minlength=g.num_vertices)
    assert len(set(roots)) == 4 and all(deg[r] > 0 for r in roots)
    V2, src2, dst2, w2 = smoke.build_edges(SCALE, seed=0)
    assert np.array_equal(src, src2) and np.array_equal(w, w2)
    assert smoke.pick_roots(src, g.num_vertices, 4, seed=0) == roots


def test_references_agree_with_the_engine(smoke, setup):
    g, roots, ref, _ = setup
    lines = []
    results = smoke.engine_phase(g, roots, 1, lines.append)
    queries = smoke.server_phase(g, roots, lines.append)
    assert len(results) == 2 * len(roots) and len(queries) == 8
    assert smoke.check(results, queries, ref, lines.append), "\n".join(lines)
    assert not any("MISMATCH" in s for s in lines)


def test_references_catch_a_wrong_answer(smoke, setup):
    g, roots, ref, _ = setup
    levels = ref.bfs(roots[0])
    hit = np.flatnonzero((levels > 0) & (levels < smoke.UNREACHED))[0]
    bad = levels.copy()
    bad[hit] += 1
    assert smoke.levels_match(levels, levels)[0]
    assert not smoke.levels_match(bad, levels)[0]
    dist = ref.sssp(roots[0])
    off = dist.copy()
    off[hit] = np.nextafter(off[hit], np.float32(np.inf)) * 1.001
    assert smoke.dists_match(dist, dist)[0]
    assert not smoke.dists_match(off, dist)[0]


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs >= 4 devices")
def test_four_pe_phase_spans_four_devices(smoke, setup):
    g, roots, ref, _ = setup
    lines = []
    results = smoke.engine_phase(g, roots[:2], 4, lines.append)
    assert any("sharded over" in s for s in lines)
    assert smoke.check(results, [], ref, lines.append), "\n".join(lines)


def test_main_refuses_without_a_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main(["--scale", "4"]) != 0
    out, err = capsys.readouterr()
    assert "TPU" in err and out == ""
