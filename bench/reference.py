"""Plain BFS and SSSP references, and the comparisons that decide `correct`.

Nothing here imports the system under test.  The references read the
benchmark's own edge list (:class:`bench.gen.kronecker.EdgeList` or any
object with ``num_vertices``, ``offsets``, ``dst`` and ``weights``):

* :func:`bfs_levels` — level-synchronous frontier BFS in NumPy (hop
  counts, exact);
* :func:`sssp` — Dijkstra from SciPy, in float64;
* :func:`sssp_rounded` — Bellman-Ford over the frontier with every
  tentative distance rounded by a given function: the same reference in
  another precision, which the controls use (bfloat16 for the float32 the
  configurations state).

Unreached vertices read ``-1`` (levels) and ``inf`` (distances).
"""
from __future__ import annotations

import numpy as np

UNREACHED_LEVEL = -1


def _expand(offsets: np.ndarray, frontier: np.ndarray):
    """Edge indices out of ``frontier``, and the source of each."""
    lo = offsets[frontier]
    n = offsets[frontier + 1] - lo
    total = int(n.sum())
    start = np.repeat(lo - np.cumsum(n) + n, n)
    return start + np.arange(total, dtype=np.int64), np.repeat(frontier, n)


def bfs_levels(edges, root: int, *, max_depth: int | None = None
               ) -> np.ndarray:
    """Hop count from ``root`` to every vertex; ``-1`` where unreached.

    ``max_depth`` stops the search after that many levels (the BFS
    control: a search that ends early).
    """
    level = np.full(edges.num_vertices, UNREACHED_LEVEL, np.int64)
    level[root] = 0
    frontier = np.asarray([root], np.int64)
    depth = 0
    while frontier.size and (max_depth is None or depth < max_depth):
        depth += 1
        idx, _ = _expand(edges.offsets, frontier)
        nbrs = edges.dst[idx]
        nbrs = nbrs[level[nbrs] == UNREACHED_LEVEL]
        level[nbrs] = depth
        frontier = np.flatnonzero(level == depth) if nbrs.size else nbrs
    return level


def depth_of(levels: np.ndarray) -> int:
    """Number of BFS levels below the root."""
    return int(levels.max())


def sssp(edges, root: int) -> np.ndarray:
    """Shortest-path distances from ``root`` (float64, SciPy Dijkstra)."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import dijkstra

    V = edges.num_vertices
    m = sp.csr_matrix((edges.weights.astype(np.float64), edges.dst,
                       edges.offsets), shape=(V, V))
    return dijkstra(m, indices=int(root))


def round_bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 values to bfloat16 and back."""
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def sssp_rounded(edges, root: int, rounding=None) -> np.ndarray:
    """Synchronous Bellman-Ford over the changed frontier, float32 storage,
    every weight and every sum passed through ``rounding`` (identity when
    None)."""
    rnd = rounding or (lambda x: x)
    w = rnd(edges.weights.astype(np.float32))
    dist = np.full(edges.num_vertices, np.inf, np.float32)
    dist[root] = 0.0
    frontier = np.asarray([root], np.int64)
    while frontier.size:
        idx, u = _expand(edges.offsets, frontier)
        cand = rnd(dist[u] + w[idx])
        best = np.full_like(dist, np.inf)
        np.minimum.at(best, edges.dst[idx], cand)
        better = best < dist
        dist = np.where(better, best, dist)
        frontier = np.flatnonzero(better)
    return dist


# ---------------------------------------------------------------------------
# Comparisons.  Each returns numbers that a limit is set on.
# ---------------------------------------------------------------------------


def level_diff(got: np.ndarray, want: np.ndarray, unreached: int) -> int:
    """Vertices whose BFS level differs; ``unreached`` is the program's
    sentinel, mapped to the reference's ``-1``."""
    got = np.asarray(got).astype(np.int64)
    got = np.where(got == unreached, UNREACHED_LEVEL, got)
    return int(np.count_nonzero(got != want))


def reach_diff(got: np.ndarray, want: np.ndarray) -> int:
    """Vertices reached on one side and not on the other."""
    return int(np.count_nonzero(np.isinf(np.asarray(got)) != np.isinf(want)))


def dist_gap(got, want) -> float:
    """Widest gap ``|got - want| / max(want, 1)`` over vertices both sides
    reach (0 when none)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    both = np.isfinite(got) & np.isfinite(want)
    if not both.any():
        return 0.0
    return float(np.max(np.abs(got[both] - want[both])
                        / np.maximum(want[both], 1.0)))
