"""The references against chip_smoke.py's NumPy references, and the
controls against the references, at a small scale."""
import numpy as np
import pytest

from bench import reference as R
from bench.gen import kronecker as K

UNREACHED = 2**30


@pytest.fixture(scope="module")
def edges():
    return K.generate(77, scale=10, edgefactor=16, structure_seed=1)


@pytest.fixture(scope="module")
def smoke_ref(edges):
    import chip_smoke

    return chip_smoke.Reference(edges.num_vertices, edges.src, edges.dst,
                                edges.weights)


@pytest.fixture(scope="module")
def roots(edges):
    # a hub, two search keys, and a vertex of degree 0 (only itself reached)
    return [int(np.argmax(edges.degree)),
            *K.pick_roots(edges, 2).tolist(), K.idle_vertex(edges)]


def test_bfs_matches_chip_smoke(edges, smoke_ref, roots):
    import chip_smoke

    for r in roots:
        want = smoke_ref.bfs(r).astype(np.int64)
        want[want == chip_smoke.UNREACHED] = R.UNREACHED_LEVEL
        np.testing.assert_array_equal(R.bfs_levels(edges, r), want)


def test_sssp_matches_chip_smoke(edges, smoke_ref, roots):
    for r in roots:
        want = smoke_ref.sssp(r)
        for got in (R.sssp(edges, r), R.sssp_rounded(edges, r)):
            assert R.reach_diff(got, want) == 0
            assert R.dist_gap(got, want) < 1e-6


def test_comparisons():
    want = np.asarray([0, 1, 2, R.UNREACHED_LEVEL])
    assert R.level_diff(np.asarray([0, 1, 2, UNREACHED]), want, UNREACHED) == 0
    assert R.level_diff(np.asarray([0, 1, 3, 3]), want, UNREACHED) == 2
    d = np.asarray([0.0, 0.5, 2.0, np.inf])
    assert R.reach_diff(d, d) == 0 and R.dist_gap(d, d) == 0.0
    assert R.reach_diff(np.asarray([0.0, 0.5, np.inf, 1.0]), d) == 2
    assert R.dist_gap(np.asarray([0.0, 0.5001, 2.002, np.inf]), d) == \
        pytest.approx(1e-3)


def test_controls_fail_the_limits(edges, roots):
    """The BFS control stops one level early and the SSSP control works in
    bfloat16; both read far past the cells' limits."""
    import json
    import os

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "workloads", "graph500-s21.sssp.json")) as f:
        limit = json.load(f)["limits"]["sssp_gap"]
    for r in roots[:3]:
        levels = R.bfs_levels(edges, r)
        short = R.bfs_levels(edges, r, max_depth=R.depth_of(levels) - 1)
        short = np.where(short < 0, UNREACHED, short)
        assert R.level_diff(short, levels, UNREACHED) > 0
        gap = R.dist_gap(R.sssp_rounded(edges, r, R.round_bf16),
                         R.sssp(edges, r))
        assert gap > 3 * limit
