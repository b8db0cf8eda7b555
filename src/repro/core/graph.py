"""Graph data structures (the paper's "Graph data" layer, Fig. 3).

The paper stores graphs as three CSR arrays (Vertices, Edge_offset, Edges).
We keep that representation as the canonical on-host/in-HBM format and add a
TPU-native *degree-bucketed ELLPACK* (``BucketedGraph``) used by the
translator's dense edge-processing modules: TPU vector units want dense,
128-lane-aligned access, so irregular adjacency is re-blocked into fixed
width buckets (padding with a sentinel vertex), trading a bounded number of
padding FLOPs for perfectly regular memory streams — the VMEM analogue of the
paper's BRAM vertex caching + pipeline streaming.

All structures are registered pytrees so they flow through jit/shard_map.
The layout builders (``bucketize``, ``forward_ell``, ``shard_forward_ell``,
``pull_bitmap_plan``) return host (numpy) arrays: only the translator
places what a program needs on the device, once per translation.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..errors import GraphValidationError
from . import tracing

# Sentinel for padded edge slots (points at a dummy vertex appended at n).
PAD = jnp.iinfo(jnp.int32).max

# Packed-bitmap word width: the frontier ships as uint32 words, 32 vertices
# per word (V/8 bytes on the wire vs V bytes for an int8 mask).
BITMAP_BITS = 32


def _field(**kw):
    return dataclasses.field(**kw)


# ---------------------------------------------------------------------------
# Packed bitmap frontier (uint32 words) + vectorized helpers
# ---------------------------------------------------------------------------


def bitmap_num_words(n: int) -> int:
    """Words needed for an ``n``-bit bitmap (at least one, shapes stay real)."""
    return max(-(-n // BITMAP_BITS), 1)


def pack_bits(mask: jax.Array) -> jax.Array:
    """Pack a boolean mask into uint32 words (bit ``i`` of word ``w`` is
    ``mask[32w + i]``).

    The frontier's wire/summary format: V/32 words instead of V bytes.
    Elementwise shift+sum, so packing costs O(n) vector work (~2.5 ns/el on
    XLA:CPU) — cheaper than the O(n) serial cumsum it replaces in stream
    compaction (see :func:`bitmap_select`).
    """
    n = mask.shape[0]
    pad = (-n) % BITMAP_BITS
    if n == 0:
        return jnp.zeros((1,), jnp.uint32)
    b = jnp.pad(mask.astype(jnp.uint32), (0, pad)).reshape(-1, BITMAP_BITS)
    return (b << jnp.arange(BITMAP_BITS, dtype=jnp.uint32)[None, :]).sum(
        axis=1, dtype=jnp.uint32)


def unpack_bits(words: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`pack_bits`: uint32 words → ``(n,)`` bool mask."""
    bits = (words[:, None] >> jnp.arange(BITMAP_BITS, dtype=jnp.uint32)[None, :]) & 1
    return bits.reshape(-1)[:n].astype(bool)


def popcount_words(words: jax.Array) -> jax.Array:
    """Per-word set-bit counts (int32) — the bitmap's occupancy summary."""
    return jax.lax.population_count(words).astype(jnp.int32)


def select_bits(words: jax.Array, ranks: jax.Array) -> jax.Array:
    """Position of the ``ranks[i]``-th set bit inside ``words[i]`` (vectorized).

    Five-round binary search on popcounts of the low half of the remaining
    window — O(1) per element, no data-dependent loops, so it vmaps and
    shards like any elementwise op.  Callers must guarantee
    ``ranks[i] < popcount(words[i])``; out-of-range ranks return 31 + junk
    and must be masked by the caller (``bitmap_select`` does).
    """
    pos = jnp.zeros_like(ranks, dtype=jnp.uint32)
    k = ranks.astype(jnp.uint32)
    w = words.astype(jnp.uint32)
    for width in (16, 8, 4, 2, 1):
        m = jnp.uint32((1 << width) - 1)
        low = jax.lax.population_count((w >> pos) & m).astype(jnp.uint32)
        go_high = k >= low
        k = jnp.where(go_high, k - low, k)
        pos = jnp.where(go_high, pos + jnp.uint32(width), pos)
    return pos.astype(jnp.int32)


def bitmap_select(words: jax.Array, capacity: int,
                  num_items: int | None = None
                  ) -> tuple[jax.Array, jax.Array]:
    """Indices of the first ``capacity`` set bits, in ascending order.

    The bitmap-native form of stream compaction: the classic cumsum form
    (``kernels.push_ell.compact_rows``'s original body) pays a serial O(n)
    cumsum (~8 ns/el on XLA:CPU); here the cumsum runs over n/32
    *word popcounts* and the in-word position comes from
    :func:`select_bits`, so compaction of an n-bit frontier costs
    O(n/32 + capacity) after the O(n) elementwise pack.

    Returns ``(idx (capacity,) int32, ok (capacity,) bool)`` — ``idx`` is 0
    where ``ok`` is False (slots past the set-bit count, or past
    ``num_items`` when given).  Bit-for-bit the same selection as the
    cumsum+searchsorted idiom.
    """
    nw = words.shape[0]
    counts = popcount_words(words)
    cum = jnp.cumsum(counts)                       # (nw,) inclusive
    slots = jnp.arange(1, capacity + 1, dtype=jnp.int32)
    wi = jnp.searchsorted(cum, slots).astype(jnp.int32)
    ok = wi < nw
    wis = jnp.where(ok, wi, 0)
    prev = jnp.where(wis > 0, cum[jnp.maximum(wis - 1, 0)], 0)
    rank = (slots - 1) - prev
    bit = select_bits(words[wis], jnp.where(ok, rank, 0))
    idx = wis * BITMAP_BITS + bit
    if num_items is not None:
        ok = ok & (idx < num_items)
    return jnp.where(ok, idx, 0), ok


def interval_live_counts(words: jax.Array, cuts: jax.Array) -> jax.Array:
    """Per-interval set-bit counts of a packed bitmap (device-side).

    ``cuts`` is a ``(P+1,)`` int32 array of vertex cut points
    (``0 = cuts[0] <= ... <= cuts[P] <= 32*len(words)``); the result is the
    ``(P,)`` count of set bits in each ``[cuts[i], cuts[i+1])`` interval —
    the cross-partition frontier summary of the out-of-core engine: one
    popcount cumsum over the words plus a masked partial popcount at each
    (possibly mid-word) cut, so partitions with no live source vertices
    are identified in O(V/32 + P) without unpacking the frontier.
    """
    nw = words.shape[0]
    cum = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                           jnp.cumsum(popcount_words(words))])

    def below(n):
        # set bits among bit positions [0, n)
        w = n // BITMAP_BITS
        rem = (n - w * BITMAP_BITS).astype(jnp.uint32)
        wsafe = jnp.minimum(w, nw - 1)
        mask = jnp.where(rem > 0,
                         (jnp.uint32(1) << rem) - jnp.uint32(1),
                         jnp.uint32(0))
        partial = jax.lax.population_count(words[wsafe] & mask)
        return cum[jnp.minimum(w, nw)] \
            + jnp.where(w < nw, partial, 0).astype(jnp.int32)

    b = jax.vmap(below)(cuts.astype(jnp.int32))
    return b[1:] - b[:-1]


def edge_interval_cuts(out_degrees: np.ndarray, parts: int) -> np.ndarray:
    """Edge-balanced contiguous vertex cut points (host-side numpy).

    Returns a ``(parts+1,)`` int64 array ``cuts`` with ``cuts[0] == 0``,
    ``cuts[-1] == V``, monotone non-decreasing, where interval ``i`` owns
    the out-edges of vertices ``[cuts[i], cuts[i+1])``.  Cut points are
    chosen on the cumulative out-degree (searchsorted at equal fractions
    of E) — the same degree-balanced idiom as :func:`shard_forward_ell`'s
    per-PE row intervals, but on raw vertices so any layout (forward ELL,
    reversed ELL, COO) can be built per interval.  Intervals may be empty
    on extreme skew (a hub owning more than E/parts edges).
    """
    if parts < 1:
        raise ValueError(f"parts must be >= 1, got {parts}")
    deg = np.asarray(out_degrees, np.int64)
    v = len(deg)
    cum = np.zeros(v + 1, np.int64)
    np.cumsum(deg, out=cum[1:])
    targets = cum[-1] * np.arange(1, parts, dtype=np.float64) / parts
    inner = np.searchsorted(cum[1:], targets, side="left") + 1
    cuts = np.concatenate([[0], np.clip(inner, 0, v), [v]])
    return np.maximum.accumulate(cuts).astype(np.int64)


def partition_coo(g: "Graph", lo: int, hi: int
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One source-vertex interval's edges as host COO ``(src, dst, wgt)``.

    The resident-graph slice behind the partitioned engine's lazy layout
    builds: CSR groups edges by source, so interval ``[lo, hi)`` is the
    contiguous edge range ``offsets[lo]:offsets[hi]`` — no scan over E.
    Vertex ids stay global (the per-partition partial tables are
    full-width and combine across partitions).
    """
    offsets = np.asarray(g.edge_offsets).astype(np.int64)
    e0, e1 = int(offsets[lo]), int(offsets[hi])
    deg = offsets[lo + 1:hi + 1] - offsets[lo:hi]
    src = np.repeat(np.arange(lo, hi, dtype=np.int32), deg)
    dst = np.asarray(g.edges_dst)[e0:e1]
    wgt = np.asarray(g.edge_weights)[e0:e1]
    return src, dst.astype(np.int32), wgt


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Graph:
    """CSR graph. ``edge_offsets[v]:edge_offsets[v+1]`` index ``edges_dst``.

    ``vertex_values`` is the algorithm-owned per-vertex state (paper:
    "Vertices" array). ``edge_weights`` may be float weights or all-ones.
    A dummy vertex row is *not* materialized; ``num_vertices`` is static.
    """

    vertex_values: jax.Array        # (V,) algorithm state
    edge_offsets: jax.Array         # (V+1,) int32
    edges_dst: jax.Array            # (E,) int32 destination vertex ids
    edge_weights: jax.Array         # (E,) weights
    num_vertices: int = _field(metadata=dict(static=True))
    num_edges: int = _field(metadata=dict(static=True))

    @property
    def out_degrees(self) -> jax.Array:
        return self.edge_offsets[1:] - self.edge_offsets[:-1]

    def with_values(self, values: jax.Array) -> "Graph":
        return dataclasses.replace(self, vertex_values=values)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BucketedGraph:
    """Degree-bucketed ELLPACK edge blocks (TPU-native dense layout).

    Vertices are grouped into buckets by out-degree; bucket ``b`` stores its
    vertices' adjacency as a dense ``(rows_b, width_b)`` matrix (padded with
    ``PAD``). ``width_b`` is a power of two × 8 so edge blocks tile VMEM
    cleanly. ``src_ids`` maps bucket rows back to vertex ids.
    """

    src_ids: tuple        # tuple of (rows_b,) int32 arrays
    dst: tuple            # tuple of (rows_b, width_b) int32 arrays (PAD-padded)
    weights: tuple        # tuple of (rows_b, width_b) weight arrays
    num_vertices: int = _field(metadata=dict(static=True))
    num_edges: int = _field(metadata=dict(static=True))

    @property
    def num_buckets(self) -> int:
        return len(self.src_ids)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ForwardELL:
    """Fixed-width forward (out-edge) ELL rows, the push engine's layout.

    The dual of :class:`BucketedGraph`: rows are grouped by *source* vertex
    so the frontier-compaction step can select live rows with a single
    ``active[row_src]`` gather.  A vertex with out-degree ``d`` owns
    ``ceil(d / width)`` consecutive rows (hubs span several rows), padded
    with ``PAD`` destinations.  ``width`` is kept small (default 8) because
    the compacted scatter pays per *slot*: padding overhead on power-law
    graphs is ~1.1-1.3x at width 8 vs ~1.7x at 16.

    ``rows_per_vertex`` lets the runtime direction policy compute the live
    row count ``r_f = sum(rows_per_vertex[frontier])`` in O(V) — the guard
    that picks a compaction capacity tier (or the full push) per
    superstep.  When the graph has no edges the arrays keep one all-PAD
    dummy row so every shape stays non-empty; ``num_rows`` is the logical
    row count (0) and compaction treats the dummy as invalid.
    """

    row_src: jax.Array           # (max(R,1),) int32 owner vertex per row
    dst: jax.Array               # (max(R,1), width) int32, PAD-padded
    weights: jax.Array           # (max(R,1), width) edge weights
    rows_per_vertex: jax.Array   # (V,) int32 ceil(out_deg / width)
    num_rows: int = _field(metadata=dict(static=True))       # logical R
    width: int = _field(metadata=dict(static=True))
    num_vertices: int = _field(metadata=dict(static=True))
    num_edges: int = _field(metadata=dict(static=True))


def forward_ell(g: Graph, *, width: int = 8) -> ForwardELL:
    """Build the push engine's forward ELL from CSR (host-side, vectorized).

    Unlike :func:`bucketize` (a python loop over vertices, used for the
    pull side's degree buckets) this is pure-numpy array arithmetic: the
    slot→edge map is ``offsets[row_src] + (row - row_offset[row_src]) *
    width + lane``, so a 500k-edge graph lays out in ~50 ms.
    """
    if width < 1:
        raise ValueError(f"width must be >= 1, got {width}")
    offsets = np.asarray(g.edge_offsets).astype(np.int64)
    dst = np.asarray(g.edges_dst)
    wts = np.asarray(g.edge_weights)
    deg = offsets[1:] - offsets[:-1]
    rows_per_v = -(-deg // width)                        # ceil
    r = int(rows_per_v.sum())
    row_off = np.zeros(g.num_vertices + 1, np.int64)
    np.cumsum(rows_per_v, out=row_off[1:])
    row_src = np.repeat(np.arange(g.num_vertices, dtype=np.int32), rows_per_v)
    if r == 0:                                           # edgeless: dummy row
        return ForwardELL(
            row_src=np.zeros((1,), np.int32),
            dst=np.full((1, width), int(PAD), np.int32),
            weights=np.zeros((1, width), wts.dtype),
            rows_per_vertex=np.zeros((g.num_vertices,), np.int32),
            num_rows=0, width=width,
            num_vertices=g.num_vertices, num_edges=g.num_edges)
    base = offsets[row_src] + (np.arange(r) - row_off[row_src]) * width
    idx = base[:, None] + np.arange(width)[None, :]
    valid = idx < offsets[row_src + 1][:, None]
    safe = np.where(valid, idx, 0)
    ell_dst = np.where(valid, dst[safe].astype(np.int64), int(PAD))
    ell_wgt = np.where(valid, wts[safe], 0)
    return ForwardELL(
        row_src=np.asarray(row_src),
        dst=np.asarray(ell_dst.astype(np.int32)),
        weights=np.asarray(ell_wgt.astype(wts.dtype)),
        rows_per_vertex=np.asarray(rows_per_v.astype(np.int32)),
        num_rows=r, width=width,
        num_vertices=g.num_vertices, num_edges=g.num_edges)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedForwardELL:
    """Per-PE partition of a :class:`ForwardELL`: contiguous row intervals.

    The multi-PE push engine's layout (paper §V-C2: replicated PEs own
    edge partitions).  The forward ELL's rows are split into ``pes``
    contiguous intervals, cut at *vertex* boundaries (a vertex's rows never
    straddle PEs, so ``active[row_src]`` stays a plain gather per PE) and
    balanced by *edge* count, not row count — a degree-balanced split, so a
    hub-heavy prefix doesn't serialize one PE.  Every interval is padded to
    the longest one (``rows_per_pe_max``) because ``shard_map`` needs one
    static per-PE shape; ``row_valid`` masks the padding.

    Row ids stay **PE-local**: each PE compacts over its own ``(Rp,)``
    interval and indexes only its own slice of the stacked arrays, while
    destination ids remain global — the per-PE partial vertex tables are
    disjoint by construction (intervals partition the edge set), which is
    exactly the property that makes the reduce-matched collective
    (psum/pmin/pmax) an exact combine.
    """

    row_src: jax.Array           # (pes, Rp) int32 owner vertex (global id)
    dst: jax.Array               # (pes, Rp, width) int32, PAD-padded
    weights: jax.Array           # (pes, Rp, width) edge weights
    row_valid: jax.Array         # (pes, Rp) bool: real row vs interval pad
    rows_per_pe: tuple = _field(metadata=dict(static=True))   # logical rows
    edges_per_pe: tuple = _field(metadata=dict(static=True))  # balance stats
    rows_per_pe_max: int = _field(metadata=dict(static=True))  # Rp
    pes: int = _field(metadata=dict(static=True))
    width: int = _field(metadata=dict(static=True))
    num_vertices: int = _field(metadata=dict(static=True))
    num_edges: int = _field(metadata=dict(static=True))


def shard_forward_ell(fe: ForwardELL, pes: int) -> ShardedForwardELL:
    """Split a forward ELL into ``pes`` degree-balanced row intervals.

    Host-side numpy, like every layout builder.  Cut points are chosen on
    the cumulative *edge* count (searchsorted at equal fractions of E),
    then snapped to vertex boundaries; intervals may be empty on extreme
    skew (a PE owning zero rows simply contributes the identity table).
    """
    if pes < 1:
        raise ValueError(f"pes must be >= 1, got {pes}")
    rows_per_v = np.asarray(fe.rows_per_vertex, np.int64)
    row_off = np.zeros(fe.num_vertices + 1, np.int64)
    np.cumsum(rows_per_v, out=row_off[1:])
    # valid (non-PAD) slots per row = that row's edge count
    row_edges = np.asarray(fe.dst != PAD).sum(axis=1).astype(np.int64)
    if fe.num_rows == 0:
        row_edges = np.zeros(1, np.int64)
    # per-vertex edge counts via the row→owner map (rows are grouped by
    # vertex in storage order, so cuts on vertices are cuts on rows)
    vertex_edges = np.bincount(
        np.asarray(fe.row_src)[:fe.num_rows],
        weights=row_edges[:fe.num_rows],
        minlength=fe.num_vertices).astype(np.int64)
    cum_edges_v = np.zeros(fe.num_vertices + 1, np.int64)
    np.cumsum(vertex_edges, out=cum_edges_v[1:])
    targets = fe.num_edges * (np.arange(1, pes, dtype=np.float64)) / pes
    cut_vertices = np.searchsorted(cum_edges_v[1:], targets, side="left") + 1
    cuts = np.concatenate([[0], row_off[np.clip(cut_vertices, 0,
                                                fe.num_vertices)],
                           [max(fe.num_rows, 0)]]).astype(np.int64)
    cuts = np.maximum.accumulate(cuts)
    starts, ends = cuts[:-1], cuts[1:]
    rows_per_pe = tuple(int(e - s) for s, e in zip(starts, ends))
    rp = max(max(rows_per_pe), 1)
    row_src = np.zeros((pes, rp), np.int32)
    dst = np.full((pes, rp, fe.width), int(PAD), np.int32)
    wgt = np.zeros((pes, rp, fe.width), np.asarray(fe.weights).dtype)
    valid = np.zeros((pes, rp), bool)
    fe_src, fe_dst, fe_wgt = (np.asarray(fe.row_src), np.asarray(fe.dst),
                              np.asarray(fe.weights))
    edges_per_pe = []
    for p, (s, e) in enumerate(zip(starts, ends)):
        n = int(e - s)
        if n:
            row_src[p, :n] = fe_src[s:e]
            dst[p, :n] = fe_dst[s:e]
            wgt[p, :n] = fe_wgt[s:e]
            valid[p, :n] = True
        edges_per_pe.append(int(row_edges[s:e].sum()))
    return ShardedForwardELL(
        row_src=np.asarray(row_src), dst=np.asarray(dst),
        weights=np.asarray(wgt), row_valid=np.asarray(valid),
        rows_per_pe=rows_per_pe, edges_per_pe=tuple(edges_per_pe),
        rows_per_pe_max=rp, pes=pes, width=fe.width,
        num_vertices=fe.num_vertices, num_edges=fe.num_edges)


def validate_graph(g: Graph, *, reduce: str | None = None) -> None:
    """Structural + integrity checks; raises :class:`GraphValidationError`.

    Checks (all host-side, a few O(V)/O(E) passes):

    * ``edge_offsets`` has length V+1, starts at 0, is monotone
      non-decreasing, and ends at ``num_edges``;
    * ``edges_dst`` has length E and every destination is in ``[0, V)``;
    * ``edge_weights`` has length E and every weight is finite;
    * weight-domain per reduce: ``reduce='min'`` (shortest-path semantics)
      additionally requires non-negative weights — Dijkstra/Beamer-style
      frontier reasoning is unsound under negative edges.

    Opt-in (the ``validate=`` knob on :func:`from_edge_list` and
    ``translate``): the checks cost a few linear passes, which matters at
    the 20M-edge streaming scale.
    """
    v, e = g.num_vertices, g.num_edges
    off = np.asarray(g.edge_offsets)
    if off.shape != (v + 1,):
        raise GraphValidationError(
            f"edge_offsets has shape {off.shape}, expected ({v + 1},)")
    if v >= 0 and (off.size == 0 or off[0] != 0):
        raise GraphValidationError("edge_offsets must start at 0")
    if np.any(np.diff(off) < 0):
        bad = int(np.nonzero(np.diff(off) < 0)[0][0])
        raise GraphValidationError(
            f"edge_offsets not monotone at vertex {bad}: "
            f"{int(off[bad])} > {int(off[bad + 1])}")
    if int(off[-1]) != e:
        raise GraphValidationError(
            f"edge_offsets ends at {int(off[-1])}, expected num_edges={e}")
    dst = np.asarray(g.edges_dst)
    if dst.shape != (e,):
        raise GraphValidationError(
            f"edges_dst has shape {dst.shape}, expected ({e},)")
    if e and (int(dst.min()) < 0 or int(dst.max()) >= v):
        bad = int(np.nonzero((dst < 0) | (dst >= v))[0][0])
        raise GraphValidationError(
            f"edges_dst[{bad}] = {int(dst[bad])} out of range [0, {v})")
    wgt = np.asarray(g.edge_weights)
    if wgt.shape != (e,):
        raise GraphValidationError(
            f"edge_weights has shape {wgt.shape}, expected ({e},)")
    if e and not np.all(np.isfinite(wgt)):
        bad = int(np.nonzero(~np.isfinite(wgt))[0][0])
        raise GraphValidationError(
            f"edge_weights[{bad}] = {wgt[bad]} is not finite")
    if reduce == "min" and e and float(wgt.min()) < 0:
        bad = int(np.nonzero(wgt < 0)[0][0])
        raise GraphValidationError(
            f"reduce='min' (distance semantics) requires non-negative "
            f"weights; edge_weights[{bad}] = {wgt[bad]}")


def from_edge_list(
    src: np.ndarray,
    dst: np.ndarray,
    *,
    num_vertices: int | None = None,
    weights: np.ndarray | None = None,
    vertex_values: np.ndarray | None = None,
    sort: bool = True,
    validate: bool = False,
) -> Graph:
    """Build a CSR :class:`Graph` from COO edge lists (host-side).

    ``validate=True`` additionally rejects out-of-range sources up front
    and runs :func:`validate_graph` on the result — an out-of-range ``src``
    would otherwise crash obscurely inside the bincount/cumsum below.
    Runs in the host span ``repro.ingest``: ``repro.ingest.csr`` (the host
    CSR build) then ``repro.ingest.place`` (the copy to the device).
    """
    with tracing.span("ingest"):
        with tracing.span("ingest.csr"):
            csr = _csr(src, dst, num_vertices, weights, vertex_values, sort,
                       validate)
        with tracing.span("ingest.place"):
            g = _place_csr(*csr)
        if validate:
            validate_graph(g)
    return g


def _csr(src, dst, num_vertices, weights, vertex_values, sort, validate):
    """Host CSR arrays of a COO edge list: ``(vertex_values, offsets, dst,
    weights, num_vertices)``."""
    src = np.asarray(src, np.int32)
    dst = np.asarray(dst, np.int32)
    if num_vertices is None:
        num_vertices = int(max(src.max(initial=-1), dst.max(initial=-1)) + 1)
    if validate and len(src) and (
            int(src.min()) < 0 or int(src.max()) >= num_vertices):
        bad = int(np.nonzero((src < 0) | (src >= num_vertices))[0][0])
        raise GraphValidationError(
            f"src[{bad}] = {int(src[bad])} out of range "
            f"[0, {num_vertices})")
    if weights is None:
        weights = np.ones(len(src), np.float32)
    if sort:
        order = np.argsort(src, kind="stable")
        src, dst, weights = src[order], dst[order], weights[order]
    counts = np.bincount(src, minlength=num_vertices)
    offsets = np.zeros(num_vertices + 1, np.int64)
    np.cumsum(counts, out=offsets[1:])
    if vertex_values is None:
        vertex_values = np.zeros(num_vertices, np.float32)
    return vertex_values, offsets, dst, weights, num_vertices


def _place_csr(vertex_values, offsets, dst, weights, num_vertices) -> Graph:
    return Graph(
        vertex_values=jnp.asarray(vertex_values),
        edge_offsets=jnp.asarray(offsets, jnp.int32),
        edges_dst=jnp.asarray(dst, jnp.int32),
        edge_weights=jnp.asarray(weights),
        num_vertices=num_vertices,
        num_edges=len(dst),
    )


def to_coo(g: Graph) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """CSR → COO (host-side)."""
    offsets = np.asarray(g.edge_offsets)
    degrees = offsets[1:] - offsets[:-1]
    src = np.repeat(np.arange(g.num_vertices, dtype=np.int32), degrees)
    return src, np.asarray(g.edges_dst), np.asarray(g.edge_weights)


def reverse(g: Graph) -> Graph:
    """Transpose the graph (CSR ↔ CSC): out-edges become in-edges."""
    src, dst, w = to_coo(g)
    # not from_edge_list: a layout build, not an ingest (no ingest spans)
    return _place_csr(*_csr(dst, src, g.num_vertices, w,
                            np.asarray(g.vertex_values), True, False))


def bucketize(
    g: Graph,
    *,
    min_width: int = 8,
    max_width: int = 1024,
) -> BucketedGraph:
    """Re-block CSR into degree buckets of power-of-two ELL width.

    Padding overhead is < 2× edges per bucket by construction (width is the
    next pow2 ≥ degree), and in practice ~1.3× on power-law graphs.
    """
    offsets = np.asarray(g.edge_offsets)
    dst = np.asarray(g.edges_dst)
    wts = np.asarray(g.edge_weights)
    degrees = offsets[1:] - offsets[:-1]

    widths = []
    w = min_width
    while w <= max_width:
        widths.append(w)
        w *= 2
    # Assign every vertex with degree>0 to the smallest width ≥ degree
    # (overflow vertices: split across multiple rows of max_width).
    rows_per_bucket: dict[int, list[tuple[int, np.ndarray, np.ndarray]]] = {
        w: [] for w in widths
    }
    for v in np.nonzero(degrees)[0]:
        d = int(degrees[v])
        lo, hi = offsets[v], offsets[v + 1]
        vd, vw = dst[lo:hi], wts[lo:hi]
        if d <= max_width:
            bw = next(w for w in widths if w >= d)
            rows_per_bucket[bw].append((int(v), vd, vw))
        else:  # split high-degree hub across several max-width rows
            for s in range(0, d, max_width):
                rows_per_bucket[max_width].append(
                    (int(v), vd[s:s + max_width], vw[s:s + max_width]))

    src_ids, dsts, weights = [], [], []
    for w in widths:
        rows = rows_per_bucket[w]
        if not rows:
            continue
        n = len(rows)
        sid = np.empty(n, np.int32)
        dm = np.full((n, w), int(PAD), np.int64)
        wm = np.zeros((n, w), wts.dtype)
        for i, (v, vd, vw) in enumerate(rows):
            sid[i] = v
            dm[i, : len(vd)] = vd
            wm[i, : len(vw)] = vw
        src_ids.append(np.asarray(sid))
        dsts.append(np.asarray(dm.astype(np.int32)))
        weights.append(np.asarray(wm))
    return BucketedGraph(
        src_ids=tuple(src_ids),
        dst=tuple(dsts),
        weights=tuple(weights),
        num_vertices=g.num_vertices,
        num_edges=g.num_edges,
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PullBitmapPlan:
    """Static layout + combine metadata for the bitmap-frontier pull plane.

    Derived once per graph from the reversed :class:`BucketedGraph` (cached
    in :class:`repro.core.preprocess.GraphLayouts`); everything here is
    frontier-independent — the per-superstep "any-active" summaries are
    computed at run time against these static structures.

    Three pieces:

    * **Flat width-8 view** — every bucket row re-flattens into ``W/8``
      width-8 *sub-rows* (``flat_dst``/``flat_wgt``, ``owner8`` maps each
      sub-row to its vertex), stored stripe-major within the bucket:
      sub-row ``k`` of row ``r`` sits at ``k·R_b + r``.  The dense sweep then
      runs as ONE uniform gather + row-reduce over ``(R8, 8)`` instead of
      eight per-bucket kernels of different widths — measured ~1.2 ns/slot
      vs ~5.8 ns/slot for the per-bucket form on XLA:CPU (narrow uniform
      rows vectorize; tiny buckets stop paying per-op dispatch).
    * **Scatter-free combine cascade** — sub-row reductions fold back to
      bucket rows by static per-bucket ``reshape(W_b/8, R_b)`` reductions
      (``bucket_shapes``/``bucket_sub_offsets``), then to vertices through
      ``row_map`` (every vertex with in-edges owns exactly one bucket row;
      split hubs add a tiny ``dup_rows`` scatter).  This replaces the old
      per-bucket ``at[sid].min/add/max`` scatter (~70 ns/row — the old
      dense sweep's hidden dominant cost) with pure reshapes + one gather.
    * **Skippable blocks** — the flat sub-rows group into uniform blocks
      of ``block_rows`` (``8·block_rows`` edge slots each).  Per-block
      liveness is one gather of the touched table over ``owner8`` +
      reshape + any (exact).  ``block_word_lo/hi`` bound each block's
      owner ids in frontier-bitmap words (each block's min and max
      owner), so the conservative range-popcount test
      (``pull_bitmap.block_range_live``) never skips a live block; the
      emitter uses the exact gather form and keeps the ranges as the
      cheap pre-filter shape and as a layout invariant tests pin.
      ``block_edges`` records real (non-PAD) slots per block so skipped
      vs swept blocks convert to exact edge counts for ``run_stats``.
    """

    flat_dst: jax.Array       # (R8p, 8) int32 destinations, PAD-padded
    flat_dst_safe: jax.Array  # (R8p, 8) int32, PAD baked to V (table index)
    flat_wgt: jax.Array       # (R8p, 8) edge weights
    owner8: jax.Array         # (R8p,) int32 owner vertex per sub-row (V: pad)
    row_map: jax.Array        # (V,) int32 → first concat row id (R_cat: none)
    dup_rows: jax.Array       # (max(D,1),) int32 concat rows beyond the first
    dup_vertices: jax.Array   # (max(D,1),) int32 owner vertex per dup row
    block_edges: jax.Array    # (nb,) int32 real edge slots per block
    block_word_lo: jax.Array  # (nb,) int32 first owner bitmap word
    block_word_hi: jax.Array  # (nb,) int32 exclusive last owner bitmap word
    bucket_shapes: tuple = _field(metadata=dict(static=True))  # (R_b, W_b/8)
    bucket_sub_offsets: tuple = _field(metadata=dict(static=True))
    block_rows: int = _field(metadata=dict(static=True))  # sub-rows / block
    num_blocks: int = _field(metadata=dict(static=True))
    num_subrows: int = _field(metadata=dict(static=True))      # R8p (padded)
    num_rows_total: int = _field(metadata=dict(static=True))   # Σ R_b
    num_dup: int = _field(metadata=dict(static=True))
    num_vertices: int = _field(metadata=dict(static=True))


def pull_bitmap_plan(bucket: BucketedGraph, *,
                     block_slots: int = 64) -> PullBitmapPlan:
    """Build the bitmap pull plane's static metadata (host-side numpy).

    ``block_slots`` is the edge-slot volume per skippable block
    (``block_rows = block_slots/8`` flat sub-rows); uniform across the
    whole flat view, so a dead block elides the same memory traffic
    everywhere and liveness stays a single reshape+any.  Small blocks
    capture scattered frontiers (the measured win on power-law graphs:
    block liveness tracks *row* liveness only below ~8 rows/block);
    every bucket width must be a multiple of 8 (bucketize guarantees it).
    """
    if block_slots < 8 or block_slots % 8:
        raise ValueError(f"block_slots must be a positive multiple of 8, "
                         f"got {block_slots}")
    V = bucket.num_vertices
    rows_per_bucket = [int(s.shape[0]) for s in bucket.src_ids]
    offsets = np.zeros(len(rows_per_bucket) + 1, np.int64)
    np.cumsum(rows_per_bucket, out=offsets[1:])
    r_cat = int(offsets[-1])

    row_map = np.full(V, r_cat, np.int64)
    dup_rows, dup_vertices = [], []
    flat_dst, flat_wgt, owner8 = [], [], []
    bucket_shapes, bucket_sub_offsets = [], []
    sub_off = 0
    wdtype = np.float32
    for b, (sid, dst, wgt) in enumerate(zip(bucket.src_ids, bucket.dst,
                                            bucket.weights)):
        sid = np.asarray(sid)
        dst = np.asarray(dst)
        wgt = np.asarray(wgt)
        wdtype = wgt.dtype
        rows_b, width = dst.shape
        if width % 8:
            raise ValueError(f"bucket width {width} is not a multiple of 8")
        # first row per owner: a vertex lives in exactly one bucket, and a
        # split hub's extra rows are consecutive (bucketize appends them
        # in order), so `first` is a simple neighbor compare
        first = np.ones(rows_b, bool)
        first[1:] = sid[1:] != sid[:-1]
        rid = offsets[b] + np.arange(rows_b)
        row_map[sid[first]] = rid[first]
        dup_rows.extend(rid[~first].tolist())
        dup_vertices.extend(sid[~first].tolist())

        f_b = width // 8
        # stripe-major within the bucket: sub-row k of every row is
        # contiguous (flat index k·rows_b + r), so the per-row fold is a
        # reduction over the leading axis of a (f_b, rows_b) view — a
        # (rows_b, f_b) fold would tile f_b to 128 lanes on a TPU
        flat_dst.append(dst.reshape(rows_b, f_b, 8).transpose(1, 0, 2)
                        .reshape(rows_b * f_b, 8))
        flat_wgt.append(wgt.reshape(rows_b, f_b, 8).transpose(1, 0, 2)
                        .reshape(rows_b * f_b, 8))
        owner8.append(np.tile(sid, f_b))
        bucket_shapes.append((rows_b, f_b))
        bucket_sub_offsets.append(sub_off)
        sub_off += rows_b * f_b

    br = block_slots // 8
    r8 = sub_off
    r8p = max(-(-r8 // br), 1) * br             # pad to whole blocks
    pad = r8p - r8
    fd = np.concatenate(flat_dst) if flat_dst else \
        np.zeros((0, 8), np.int64)
    fw = np.concatenate(flat_wgt) if flat_wgt else np.zeros((0, 8), wdtype)
    ow = np.concatenate(owner8) if owner8 else np.zeros((0,), np.int64)
    fd = np.concatenate([fd, np.full((pad, 8), int(PAD), fd.dtype)])
    fw = np.concatenate([fw, np.zeros((pad, 8), fw.dtype)])
    ow = np.concatenate([ow, np.full(pad, V, ow.dtype)]).astype(np.int64)

    nb = r8p // br
    edges = (fd != int(PAD)).sum(axis=1).reshape(nb, br).sum(axis=1)
    ow_2d = ow.reshape(nb, br)
    # word bounds over real sub-rows only (promote before masking — numpy
    # 2 silently wraps an out-of-range python sentinel into the dtype)
    real = ow_2d < V
    lo_ids = np.where(real, ow_2d, np.int64(V)).min(axis=1)
    hi_ids = np.where(real, ow_2d, np.int64(-1)).max(axis=1)

    ndup = len(dup_rows)
    return PullBitmapPlan(
        flat_dst=np.asarray(fd.astype(np.int32)),
        # PAD baked to the table dummy index V at build time: the sweep's
        # hot loop indexes (V+1,) message/live tables directly instead of
        # materializing a per-superstep `where(dst == PAD, ...)` temp —
        # measured 3x the remaining gather+reduce cost at 500k edges
        flat_dst_safe=np.asarray(
            np.where(fd == int(PAD), np.int64(V), fd).astype(np.int32)),
        flat_wgt=np.asarray(fw),
        owner8=np.asarray(ow.astype(np.int32)),
        row_map=np.asarray(row_map, np.int32),
        dup_rows=np.asarray(dup_rows or [0], np.int32),
        dup_vertices=np.asarray(dup_vertices or [0], np.int32),
        block_edges=np.asarray(edges, np.int32),
        block_word_lo=np.asarray(
            np.where(lo_ids <= hi_ids, lo_ids // BITMAP_BITS, 0), np.int32),
        block_word_hi=np.asarray(
            np.where(lo_ids <= hi_ids, hi_ids // BITMAP_BITS + 1, 0),
            np.int32),
        bucket_shapes=tuple(bucket_shapes),
        bucket_sub_offsets=tuple(bucket_sub_offsets),
        block_rows=br,
        num_blocks=nb,
        num_subrows=r8p,
        num_rows_total=r_cat,
        num_dup=ndup,
        num_vertices=V,
    )


def coo_arrays(g: Graph) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Device-side COO (src per edge) derived from CSR without host sync."""
    # repeat via searchsorted: edge i belongs to vertex v where
    # offsets[v] <= i < offsets[v+1]
    idx = jnp.arange(g.num_edges, dtype=jnp.int32)
    src = jnp.searchsorted(g.edge_offsets[1:], idx, side="right").astype(jnp.int32)
    return src, g.edges_dst, g.edge_weights


def rmat_edges(
    num_vertices: int,
    num_edges: int,
    *,
    seed: int = 0,
    a: float = 0.57,
    b: float = 0.19,
    c: float = 0.19,
) -> tuple[np.ndarray, np.ndarray]:
    """R-MAT power-law edge generator (Graph500-style), host-side numpy.

    Used to synthesize graphs with the exact |V|/|E| of the paper's SNAP
    datasets (offline environment; see DESIGN.md §6).
    """
    rng = np.random.default_rng(seed)
    scale = int(np.ceil(np.log2(max(num_vertices, 2))))
    src = np.zeros(num_edges, np.int64)
    dst = np.zeros(num_edges, np.int64)
    for level in range(scale):
        r = rng.random(num_edges)
        right = r > a + b          # falls in c or d quadrant → dst bit set
        down = ((r > a) & (r <= a + b)) | (r > a + b + c)  # b or d → src bit
        src |= down.astype(np.int64) << level
        dst |= right.astype(np.int64) << level
    src %= num_vertices
    dst %= num_vertices
    keep = src != dst
    src, dst = src[keep], dst[keep]
    # top up dropped self-loops with uniform random edges
    missing = num_edges - len(src)
    if missing > 0:
        s2 = rng.integers(0, num_vertices, missing)
        d2 = (s2 + 1 + rng.integers(0, num_vertices - 1, missing)) % num_vertices
        src = np.concatenate([src, s2])
        dst = np.concatenate([dst, d2])
    return src.astype(np.int32), dst.astype(np.int32)
