"""Graph500 Kronecker graphs, generated on the device from the seed.

The generator follows the Graph500 specification's reference code
(``kronecker_generator.m``): ``edgefactor * 2**scale`` edge tuples, each
drawn bit by bit with initiator probabilities A/B/C (D = 1 - A - B - C),
then a seeded relabelling of the vertices.

The tuples, their weights and the search keys come from the
configuration's ``structure_seed``; the run's seed picks only the
relabelling.  So every seed gets the same weighted graph and the same
roots up to the names of its vertices: the same work per root, and the
same layout shapes, so that the programs the translator compiles for one
seed serve every other from the compile cache.  The relabelling is a keyed
bijection of ``[0, 2**scale)`` (rounds of an odd multiply, an add and an
xor-shift, each invertible modulo ``2**scale``) rather than a uniform
random permutation: it scatters the hubs as well, and needs no sort,
whose compile for the TPU takes half a minute.  SSSP weights are U[0, 1) per
tuple.  The tuples are symmetrized (each ``(u, v, w)`` also gives
``(v, u, w)``), so an undirected graph of ``m`` tuples has ``2m`` directed
edges, and sorted by source on the device, in one jitted call.  The
specification's shuffle of the tuple order is left out: the tuples are
drawn independently, and the sort by source fixes the order anyway.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

GRAPH500_ABC = (0.57, 0.19, 0.19)


def key_for(seed: int, stream: int):
    """A ``jax.random`` key from a seed of up to 64 bits and a stream id.

    ``jax.random.key`` keeps only the low 32 bits of a Python int, so the
    high half is folded in explicitly: seeds that differ anywhere differ
    in their keys.
    """
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


RELABEL_ROUNDS = 4


def _relabel(x, mul, add, scale: int):
    """A keyed bijection of ``[0, 2**scale)``: ``RELABEL_ROUNDS`` rounds of
    ``x * mul + add`` (``mul`` odd) and ``x ^ (x >> ceil(scale / 2))``, all
    modulo ``2**scale``."""
    mask = jnp.uint32((1 << scale) - 1)
    shift = (scale + 1) // 2
    x = x.astype(jnp.uint32)
    for r in range(RELABEL_ROUNDS):
        x = (x * mul[r] + add[r]) & mask
        x = x ^ (x >> shift)
    return x.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("scale", "edgefactor", "abc"))
def _kronecker(k_bits, k_w, k_perm, *, scale: int, edgefactor: int,
               abc: tuple):
    a, b, c = abc
    m = edgefactor << scale
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab

    def bit(ib, ij):
        i, j = ij
        ki, kj = jax.random.split(jax.random.fold_in(k_bits, ib))
        ii = jax.random.uniform(ki, (m,)) > ab
        thresh = jnp.where(ii, c_norm, a_norm)
        jj = jax.random.uniform(kj, (m,)) > thresh
        return (i + (ii.astype(jnp.int32) << ib),
                j + (jj.astype(jnp.int32) << ib))

    zero = jnp.zeros((m,), jnp.int32)
    i, j = jax.lax.fori_loop(0, scale, bit, (zero, zero))
    mul, add = jax.random.bits(k_perm, (2, RELABEL_ROUNDS), jnp.uint32)
    i, j, labels = (_relabel(x, mul | 1, add, scale)
                    for x in (i, j, jnp.arange(1 << scale)))
    w = jax.random.uniform(k_w, (m,), jnp.float32)
    src = jnp.concatenate([i, j])
    dst = jnp.concatenate([j, i])
    wts = jnp.concatenate([w, w])
    return (jax.lax.sort((src, dst, wts), num_keys=1, is_stable=True),
            labels)


@dataclasses.dataclass
class EdgeList:
    """A symmetrized graph, sorted by source, on the host.

    ``offsets`` is the CSR row pointer over ``src`` (int64), ``degree`` the
    out-degree of every vertex (equal to its undirected degree, self-loops
    counted twice).  ``labels[v]`` is the name that vertex ``v`` of the
    structure got in this run, and ``structure_seed`` the seed the
    structure came from.
    """

    num_vertices: int
    src: np.ndarray
    dst: np.ndarray
    weights: np.ndarray
    offsets: np.ndarray
    degree: np.ndarray
    labels: np.ndarray
    structure_seed: int

    @property
    def num_edges(self) -> int:
        return len(self.src)

    @property
    def num_tuples(self) -> int:
        return len(self.src) // 2


def generate(seed: int, *, scale: int, edgefactor: int, structure_seed: int,
             abc: tuple = GRAPH500_ABC) -> EdgeList:
    """Generate on the device, then bring the sorted arrays to the host."""
    out = _kronecker(key_for(structure_seed, 0), key_for(structure_seed, 2),
                     key_for(seed, 1), scale=scale, edgefactor=edgefactor,
                     abc=tuple(float(x) for x in abc))
    (src, dst, w), labels = jax.device_get(out)
    del out
    V = 1 << scale
    degree = np.bincount(src, minlength=V).astype(np.int64)
    offsets = np.zeros(V + 1, np.int64)
    np.cumsum(degree, out=offsets[1:])
    return EdgeList(V, src, dst, w, offsets, degree,
                    labels.astype(np.int64), int(structure_seed))


def from_config(config: dict, seed: int) -> EdgeList:
    """The graph a configuration file describes, for this seed."""
    return generate(seed, scale=config["scale"],
                    edgefactor=config["edgefactor"],
                    structure_seed=config["structure_seed"],
                    abc=tuple(config["abc"]))


def pick_roots(edges: EdgeList, k: int) -> np.ndarray:
    """``k`` distinct vertices of nonzero degree (Graph500's search keys),
    drawn from the structure seed among the structure's vertices and named
    as in this run: every seed searches from the same vertices, in the same
    order."""
    candidates = np.flatnonzero(edges.degree[edges.labels])
    rng = np.random.default_rng([edges.structure_seed, 2])
    picked = rng.choice(candidates, size=min(k, candidates.size),
                        replace=False)
    return edges.labels[picked]


def idle_vertex(edges: EdgeList) -> int:
    """A vertex with the fewest edges: the cheapest root to warm up on."""
    return int(np.argmin(edges.degree))
