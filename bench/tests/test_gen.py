import numpy as np
import pytest

from bench.gen import kronecker as K

SCALE, EF = 9, 16


def gen(seed, structure_seed=1):
    return K.generate(seed, scale=SCALE, edgefactor=EF,
                      structure_seed=structure_seed)


@pytest.fixture(scope="module")
def edges():
    return gen(2**33 + 11)


def test_same_seed_same_graph(edges):
    again = gen(2**33 + 11)
    for a in ("src", "dst", "weights", "offsets"):
        np.testing.assert_array_equal(getattr(edges, a), getattr(again, a))


def test_seeds_differ_in_their_high_bits(edges):
    low = gen(11)
    assert not np.array_equal(low.dst, edges.dst)
    assert not np.array_equal(low.labels, edges.labels)


def _named_edges(e):
    """The weighted edges as (structure source, structure target, weight),
    sorted: the graph with the run's names taken off."""
    back = np.empty_like(e.labels)
    back[e.labels] = np.arange(e.num_vertices)
    s, d = back[e.src], back[e.dst]
    order = np.lexsort((e.weights, d, s))
    return s[order], d[order], e.weights[order]


def test_seeds_relabel_one_weighted_structure(edges):
    """Another seed gives the same weighted graph with other vertex names.
    Another structure seed does not."""
    other = gen(12)
    np.testing.assert_array_equal(np.sort(other.labels),
                                  np.arange(other.num_vertices))
    assert not np.array_equal(other.degree, edges.degree)
    for a, b in zip(_named_edges(other), _named_edges(edges)):
        np.testing.assert_array_equal(a, b)
    third = gen(12, structure_seed=2)
    assert not np.array_equal(np.sort(third.degree), np.sort(edges.degree))


def test_counts_symmetry_order_and_weights(edges):
    V = 1 << SCALE
    assert edges.num_vertices == V
    assert edges.num_edges == 2 * EF * V
    assert edges.num_tuples == EF * V
    assert np.all(np.diff(edges.src) >= 0)
    assert edges.src.min() >= 0 and edges.src.max() < V
    assert edges.dst.min() >= 0 and edges.dst.max() < V
    assert edges.weights.dtype == np.float32
    assert edges.weights.min() >= 0.0 and edges.weights.max() < 1.0
    fwd = np.lexsort((edges.weights, edges.dst, edges.src))
    rev = np.lexsort((edges.weights, edges.src, edges.dst))
    np.testing.assert_array_equal(edges.src[fwd], edges.dst[rev])
    np.testing.assert_array_equal(edges.dst[fwd], edges.src[rev])
    np.testing.assert_array_equal(edges.weights[fwd], edges.weights[rev])
    np.testing.assert_array_equal(np.diff(edges.offsets), edges.degree)
    assert edges.degree.sum() == edges.num_edges


def test_relabel_is_a_bijection():
    import jax
    import jax.numpy as jnp

    for scale in (5, 9, 12):
        mul, add = jax.random.bits(jax.random.key(scale),
                                   (2, K.RELABEL_ROUNDS), jnp.uint32)
        out = np.asarray(K._relabel(jnp.arange(1 << scale), mul | 1, add,
                                    scale))
        np.testing.assert_array_equal(np.sort(out), np.arange(1 << scale))
        assert not np.array_equal(out, np.arange(1 << scale))


def test_kronecker_skew(edges):
    # A = .57 puts most tuples among few vertices: the top 1% of vertices
    # by degree hold far more than 1% of the edges
    top = np.sort(edges.degree)[::-1][: edges.num_vertices // 100]
    assert top.sum() > 0.1 * edges.num_edges


def test_roots_are_the_same_vertices_under_every_seed(edges):
    r = K.pick_roots(edges, 16)
    assert len(set(r.tolist())) == 16
    assert np.all(edges.degree[r] > 0)
    other = gen(12)
    r2 = K.pick_roots(other, 16)
    assert not np.array_equal(r, r2)
    # the same structure vertices, in the same order, under other names
    back = {int(v): i for i, v in enumerate(edges.labels)}
    back2 = {int(v): i for i, v in enumerate(other.labels)}
    assert [back[v] for v in r.tolist()] == [back2[v] for v in r2.tolist()]
    np.testing.assert_array_equal(other.degree[r2], edges.degree[r])
    third = gen(12, structure_seed=2)
    assert not np.array_equal(np.sort(K.pick_roots(third, 16)), np.sort(r))
    assert edges.degree[K.idle_vertex(edges)] == edges.degree.min()
