"""Preprocessing library (paper §IV-C): FIFO, Layout, Partition, Reorder.

All host-side (numpy), mirroring the paper where preprocessing runs on the
CPU before ``Transport`` ships data to the accelerator.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import os
import time
from typing import Literal

import numpy as np

from . import graph as G
from . import tracing

# ---------------------------------------------------------------------------
# 1) FIFO — file I/O (paper: read input files / write outputs / Neo4j hook)
# ---------------------------------------------------------------------------


def read_edge_list(path: str) -> tuple[np.ndarray, np.ndarray]:
    """Read SNAP-style 'src dst' text or .npz edge files."""
    if path.endswith(".npz"):
        z = np.load(path)
        return z["src"].astype(np.int32), z["dst"].astype(np.int32)
    src, dst = [], []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith(("#", "%")):
                continue
            a, b = line.split()[:2]
            src.append(int(a)); dst.append(int(b))
    return np.asarray(src, np.int32), np.asarray(dst, np.int32)


def write_edge_list(path: str, src: np.ndarray, dst: np.ndarray) -> None:
    if path.endswith(".npz"):
        np.savez_compressed(path, src=src, dst=dst)
        return
    with open(path, "w") as f:
        for a, b in zip(src, dst):
            f.write(f"{int(a)} {int(b)}\n")


def write_values(path: str, values: np.ndarray) -> None:
    np.save(path, np.asarray(values))


# ---------------------------------------------------------------------------
# 2) Layout — COO / CSR / CSC / ELL conversions
# ---------------------------------------------------------------------------

Layout = Literal["csr", "csc", "ell"]


def layout(src: np.ndarray, dst: np.ndarray, to: Layout = "csr",
           num_vertices: int | None = None, weights: np.ndarray | None = None):
    """Paper: ``GraphCSC = Layout(Graph, CSC)``."""
    if to == "csr":
        return G.from_edge_list(src, dst, num_vertices=num_vertices, weights=weights)
    if to == "csc":
        return G.from_edge_list(dst, src, num_vertices=num_vertices, weights=weights)
    if to == "ell":
        g = G.from_edge_list(src, dst, num_vertices=num_vertices, weights=weights)
        return G.bucketize(g)
    raise ValueError(to)


# ---------------------------------------------------------------------------
# 2b) Graph-keyed layout cache — translation-time preprocessing, memoized
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GraphLayouts:
    """Lazily-built derived layouts for one graph, shared across translates.

    Holds every translation-time preprocessing product — the transposed
    CSR (pull's in-edge view), its degree-bucketed ELL (dense pull), the
    reverse COO (sparse pull), and the forward ELL (compacted push) — each
    built once on first request and reused by every subsequent
    ``translate()`` on the same graph.  Each build runs in the host span
    ``repro.layout.<product>``; ``build_times_s`` records its self seconds
    per product and key (what the
    ``TranslationReport.translate_breakdown['preprocess_s']`` entry sums).
    """

    graph: G.Graph                      # strong ref: keeps the id-key valid
    build_times_s: dict = dataclasses.field(default_factory=dict)
    _reverse: G.Graph | None = None
    _reverse_bucketed: G.BucketedGraph | None = None
    _reverse_coo: tuple | None = None
    _forward_ell: dict = dataclasses.field(default_factory=dict)
    _forward_ell_shards: dict = dataclasses.field(default_factory=dict)
    _pull_plan: dict = dataclasses.field(default_factory=dict)

    def _timed(self, product: str, build, key: str | None = None):
        # record *self* time: a nested build (reverse_bucketed → reverse)
        # books its own entry, so its seconds are not counted twice
        with tracing.span("layout." + product) as sp:
            out = build()
        self.build_times_s[key or product] = sp.self_s
        return out

    def reverse(self) -> G.Graph:
        """Transposed CSR (``Layout(Graph, CSC)``): pull's in-edge view."""
        if self._reverse is None:
            self._reverse = self._timed("reverse", lambda: G.reverse(self.graph))
        return self._reverse

    def reverse_bucketed(self) -> G.BucketedGraph:
        """Degree-bucketed ELL of the transposed graph (dense pull blocks)."""
        if self._reverse_bucketed is None:
            self._reverse_bucketed = self._timed(
                "reverse_bucketed", lambda: G.bucketize(self.reverse()))
        return self._reverse_bucketed

    def reverse_coo(self) -> tuple:
        """``(dst, src, wgt)`` COO of the transposed graph (sparse pull)."""
        if self._reverse_coo is None:
            self._reverse_coo = self._timed(
                "reverse_coo", lambda: G.coo_arrays(self.reverse()))
        return self._reverse_coo

    def pull_plan(self, block_slots: int = 64) -> G.PullBitmapPlan:
        """Static metadata of the bitmap-frontier pull plane.

        Block structure + scatter-free combine maps over the reversed
        bucketed ELL (:func:`repro.core.graph.pull_bitmap_plan`), keyed
        per ``block_slots``.  The per-superstep any-active summaries are
        *not* cached here — only the frontier-independent layout is.
        """
        if block_slots not in self._pull_plan:
            rb = self.reverse_bucketed()
            self._pull_plan[block_slots] = self._timed(
                "pull_plan",
                lambda: G.pull_bitmap_plan(rb, block_slots=block_slots),
                key=f"pull_plan_s{block_slots}")
        return self._pull_plan[block_slots]

    def forward_ell(self, width: int = 8) -> G.ForwardELL:
        """Fixed-width forward ELL (the compacted push engine's layout)."""
        if width not in self._forward_ell:
            self._forward_ell[width] = self._timed(
                "forward_ell", lambda: G.forward_ell(self.graph, width=width),
                key=f"forward_ell_w{width}")
        return self._forward_ell[width]

    def forward_ell_shards(self, width: int, pes: int) -> G.ShardedForwardELL:
        """Per-PE row-interval partition of the forward ELL (multi-PE push).

        Degree-balanced contiguous intervals cut at vertex boundaries
        (:func:`repro.core.graph.shard_forward_ell`), keyed per
        ``(width, pes)`` so elastic re-plans onto a different PE count
        re-partition once and then hit the cache.
        """
        key = (width, pes)
        if key not in self._forward_ell_shards:
            fe = self.forward_ell(width)
            self._forward_ell_shards[key] = self._timed(
                "forward_ell_shards", lambda: G.shard_forward_ell(fe, pes),
                key=f"forward_ell_shards_w{width}_p{pes}")
        return self._forward_ell_shards[key]

    def nbytes(self) -> int:
        """Bytes resident in the *derived* products (base graph excluded).

        Products can share buffers (a shard view aliasing its flat ELL
        counts once per holder), so this is an upper bound — the right
        direction for a budget.
        """
        products = [self._reverse, self._reverse_bucketed, self._reverse_coo,
                    *self._forward_ell.values(),
                    *self._forward_ell_shards.values(),
                    *self._pull_plan.values()]
        return sum(_product_nbytes(p) for p in products if p is not None)

    def stats(self) -> dict:
        """Per-entry report: resident bytes + what was built and for how long."""
        return {"resident_bytes": self.nbytes(),
                "products": sorted(self.build_times_s),
                "build_times_s": dict(self.build_times_s)}


def _product_nbytes(obj) -> int:
    """Total array bytes reachable from a layout product.

    Arrays (numpy or jax) report ``nbytes``; dataclasses, tuples, lists
    and dicts are walked.  Scalars and unknown leaves count zero.
    """
    if obj is None or isinstance(obj, (bool, int, float, str, bytes)):
        return 0
    nb = getattr(obj, "nbytes", None)
    if isinstance(nb, (int, np.integer)):
        return int(nb)
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return sum(_product_nbytes(getattr(obj, f.name))
                   for f in dataclasses.fields(obj))
    if isinstance(obj, dict):
        return sum(_product_nbytes(v) for v in obj.values())
    if isinstance(obj, (list, tuple)):
        return sum(_product_nbytes(v) for v in obj)
    return 0


_LAYOUT_CACHE: collections.OrderedDict = collections.OrderedDict()
_LAYOUT_CACHE_MAX = 8
_LAYOUT_CACHE_MAX_BYTES: int | None = None
_layout_cache_hits = 0
_layout_cache_misses = 0
_layout_cache_evictions = 0


def _layout_key(g: G.Graph) -> tuple:
    return (id(g.edge_offsets), id(g.edges_dst), id(g.edge_weights),
            g.num_vertices, g.num_edges)


def layouts_for(g: G.Graph) -> GraphLayouts:
    """Memoized :class:`GraphLayouts` for ``g`` (graph-identity keyed).

    Keyed on the identity of the graph's *structure arrays* (offsets,
    destinations, weights) rather than the ``Graph`` wrapper, so pytree
    rebuilds like ``g.with_values(...)`` still hit.  Entries hold strong
    references to the keyed arrays (via ``GraphLayouts.graph``), so an id
    can never be recycled while its entry is live; an LRU bound of
    ``_LAYOUT_CACHE_MAX`` graphs keeps memory in check.
    """
    global _layout_cache_hits, _layout_cache_misses
    key = _layout_key(g)
    hit = _LAYOUT_CACHE.get(key)
    if hit is not None and hit.graph.edges_dst is g.edges_dst \
            and hit.graph.edge_offsets is g.edge_offsets \
            and hit.graph.edge_weights is g.edge_weights:
        _LAYOUT_CACHE.move_to_end(key)
        _layout_cache_hits += 1
        # entries grow lazily after insertion — re-check the byte budget
        _enforce_layout_budget(keep=key)
        return hit
    entry = GraphLayouts(graph=g)
    _LAYOUT_CACHE[key] = entry
    _LAYOUT_CACHE.move_to_end(key)
    _enforce_layout_budget(keep=key)
    _layout_cache_misses += 1
    return entry


def _enforce_layout_budget(keep: tuple | None = None) -> None:
    """Evict LRU entries past the entry cap or the byte budget.

    ``keep`` (the entry just inserted or hit) is never evicted, so a
    single graph larger than the budget still translates — the budget
    bounds what is *retained across* graphs, not one graph's floor.
    """
    global _layout_cache_evictions

    def over() -> bool:
        if len(_LAYOUT_CACHE) > _LAYOUT_CACHE_MAX:
            return True
        return (_LAYOUT_CACHE_MAX_BYTES is not None
                and layout_cache_resident_bytes() > _LAYOUT_CACHE_MAX_BYTES)

    while over():
        victim = next((k for k in _LAYOUT_CACHE if k != keep), None)
        if victim is None:
            break
        del _LAYOUT_CACHE[victim]
        _layout_cache_evictions += 1


def set_layout_cache_limit(max_bytes: int | None, *,
                           max_entries: int | None = None) -> None:
    """Set the layout-cache byte budget (``None`` = unbounded).

    ``max_entries`` optionally re-caps the entry count as well.  The new
    limits are enforced immediately against whatever is resident.
    """
    global _LAYOUT_CACHE_MAX, _LAYOUT_CACHE_MAX_BYTES
    if max_bytes is not None and max_bytes < 0:
        raise ValueError(f"max_bytes must be >= 0, got {max_bytes}")
    _LAYOUT_CACHE_MAX_BYTES = max_bytes
    if max_entries is not None:
        if max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        _LAYOUT_CACHE_MAX = max_entries
    _enforce_layout_budget()


def layout_cache_resident_bytes() -> int:
    """Derived-product bytes currently held across all cached layouts."""
    return sum(e.nbytes() for e in _LAYOUT_CACHE.values())


def layout_cache_info() -> dict:
    """Cache observability for tests/benchmarks.

    Hits/misses/size as before, plus the byte-budget view: resident
    derived-product bytes, the configured ``max_bytes`` (``None`` when
    unbounded), and how many entries the budget has evicted.
    """
    return {"hits": _layout_cache_hits, "misses": _layout_cache_misses,
            "size": len(_LAYOUT_CACHE),
            "resident_bytes": layout_cache_resident_bytes(),
            "max_bytes": _LAYOUT_CACHE_MAX_BYTES,
            "evictions": _layout_cache_evictions}


def layout_cache_clear() -> None:
    """Drop every cached layout (tests and memory-pressure hook).

    Note the translator's staging cache pins layouts too — to actually
    release layout memory call
    ``repro.core.translator.staging_cache_clear()`` first.
    """
    global _layout_cache_hits, _layout_cache_misses, _layout_cache_evictions
    _LAYOUT_CACHE.clear()
    _layout_cache_hits = 0
    _layout_cache_misses = 0
    _layout_cache_evictions = 0


# ---------------------------------------------------------------------------
# 2c) PartitionStore — byte-budgeted per-partition streamed layouts
# ---------------------------------------------------------------------------


def _ell_pack(keys: np.ndarray, slots: np.ndarray, wgt: np.ndarray, *,
              width: int, rows: int, num_vertices: int) -> dict:
    """Pack grouped COO into a fixed-shape width-``width`` ELL.

    ``keys`` must be sorted ascending (the group id per edge — source for
    the push plane, destination/owner for pull); each group's edges fill
    ``ceil(count/width)`` rows.  Output arrays are padded to exactly
    ``rows`` rows so every partition of a plane shares one shape (one jit
    trace streams them all): pad key/slot = ``num_vertices`` (the safe
    index into a ``(V+1,)`` partial table), pad weight = 0.
    """
    keys = np.asarray(keys)
    n = len(keys)
    row_key = np.full(rows, num_vertices, np.int32)
    slot = np.full((rows, width), num_vertices, np.int32)
    w = np.zeros((rows, width), np.float32)
    if n:
        uniq, counts = np.unique(keys, return_counts=True)
        rows_per = -(-counts // width)
        starts = np.zeros(len(uniq), np.int64)
        np.cumsum(counts[:-1], out=starts[1:])
        row0 = np.zeros(len(uniq), np.int64)
        np.cumsum(rows_per[:-1], out=row0[1:])
        within = np.arange(n) - np.repeat(starts, counts)
        r = np.repeat(row0, counts) + within // width
        c = within % width
        used = int(rows_per.sum())
        if used > rows:
            raise ValueError(f"ELL needs {used} rows, given {rows}")
        row_key[:used] = np.repeat(uniq, rows_per).astype(np.int32)
        slot[r, c] = np.asarray(slots, np.int32)
        w[r, c] = np.asarray(wgt, np.float32)
    return {"key": row_key, "slot": slot, "wgt": w}


class PartitionStore:
    """Byte-budgeted LRU of per-partition streamed ELL layouts.

    The out-of-core engine's host-side data plane: partitions are
    contiguous source-vertex intervals (``cuts``), and each has two lazy
    layouts keyed ``(p, plane)`` —

    * ``push``: forward ELL, rows grouped by **source** (``key`` = global
      src id, ``slot`` = destinations);
    * ``pull``: reversed ELL, rows grouped by **destination/owner**
      (``key`` = global dst id, ``slot`` = senders).

    All partitions of a plane are padded to that plane's max row count,
    so the streamed superstep kernel traces once and every partition's
    arrays are donatable jit *arguments*, not baked constants.  Layouts
    build lazily from one partition's COO (``Graph`` slice or
    ``PartitionContainer`` member) and evict LRU past ``max_bytes`` —
    but never below two entries, the double-buffer floor.
    """

    def __init__(self, source, cuts, *, width: int = 8,
                 max_bytes: int | None = None):
        self.source = source
        self.cuts = np.asarray(cuts, np.int64)
        self.partitions = len(self.cuts) - 1
        self.width = int(width)
        self.max_bytes = max_bytes
        self.num_vertices = int(source.num_vertices)
        deg = np.asarray(source.out_degrees, np.int64)
        cum = np.zeros(self.num_vertices + 1, np.int64)
        np.cumsum(deg, out=cum[1:])
        self.edges_per_partition = cum[self.cuts[1:]] - cum[self.cuts[:-1]]
        push = np.asarray([
            int((-(-deg[self.cuts[p]:self.cuts[p + 1]] // self.width)).sum())
            for p in range(self.partitions)], np.int64)
        self.push_rows_max = max(int(push.max(initial=0)), 1)
        self.pull_rows_max = max(int(self._pull_rows().max(initial=0)), 1)
        self._entry_bytes = {
            plane: rows * (4 + 8 * self.width)
            for plane, rows in (("push", self.push_rows_max),
                                ("pull", self.pull_rows_max))}
        self._cache: collections.OrderedDict = collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.builds = 0
        self.build_s = 0.0

    def _pull_rows(self) -> np.ndarray:
        """Exact per-partition pull (dst-grouped) row counts.

        Containers precompute these at build time for the matching width;
        otherwise one pass over each partition's destinations counts them
        (for a resident ``Graph`` that is a cheap slice + bincount).
        """
        pr = getattr(self.source, "pull_rows", None)
        if pr is not None and getattr(self.source, "width", None) == self.width:
            return np.asarray(pr, np.int64)
        rows = np.zeros(self.partitions, np.int64)
        for p in range(self.partitions):
            _, dst, _ = self._coo(p)
            if len(dst):
                cnt = np.bincount(dst)
                rows[p] = int((-(-cnt // self.width)).sum())
        return rows

    def _coo(self, p: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        if hasattr(self.source, "partition_coo"):
            return self.source.partition_coo(p)
        return G.partition_coo(self.source, int(self.cuts[p]),
                               int(self.cuts[p + 1]))

    def push_arrays(self, p: int) -> dict:
        """Partition ``p``'s forward (src-grouped) streamed ELL."""
        return self._get(p, "push")

    def pull_arrays(self, p: int) -> dict:
        """Partition ``p``'s reversed (dst-grouped) streamed ELL."""
        return self._get(p, "pull")

    def _get(self, p: int, plane: str) -> dict:
        key = (p, plane)
        hit = self._cache.get(key)
        if hit is not None:
            self._cache.move_to_end(key)
            self.hits += 1
            return hit
        self.misses += 1
        self.builds += 1
        t0 = time.perf_counter()
        src, dst, wgt = self._coo(p)
        if plane == "push":
            order = np.argsort(src, kind="stable")
            entry = _ell_pack(src[order], dst[order], wgt[order],
                              width=self.width, rows=self.push_rows_max,
                              num_vertices=self.num_vertices)
        else:
            order = np.argsort(dst, kind="stable")
            entry = _ell_pack(dst[order], src[order], wgt[order],
                              width=self.width, rows=self.pull_rows_max,
                              num_vertices=self.num_vertices)
        self.build_s += time.perf_counter() - t0
        self._cache[key] = entry
        self._evict(keep=key)
        return entry

    def evict_partition(self, p: int) -> int:
        """Drop both plane entries for partition ``p``; returns the count.

        The checksum-recovery hook: on a fetch-side
        :class:`~repro.errors.ChecksumError` the stream evicts whatever
        this partition cached and rebuilds from the container once —
        a poisoned cache entry must not survive the retry.
        """
        dropped = 0
        for plane in ("push", "pull"):
            if self._cache.pop((p, plane), None) is not None:
                dropped += 1
                self.evictions += 1
        return dropped

    def _evict(self, keep: tuple) -> None:
        if self.max_bytes is None:
            return
        while (self.resident_bytes() > self.max_bytes
               and len(self._cache) > 2):
            victim = next((k for k in self._cache if k != keep), None)
            if victim is None:
                break
            del self._cache[victim]
            self.evictions += 1

    def resident_bytes(self) -> int:
        return sum(self._entry_bytes[plane] for _, plane in self._cache)

    def stats(self) -> dict:
        """Store observability, merged into partitioned run stats."""
        return {"partitions": self.partitions,
                "width": self.width,
                "resident_bytes": self.resident_bytes(),
                "max_bytes": self.max_bytes,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "builds": self.builds,
                "build_s": self.build_s,
                "push_rows": self.push_rows_max,
                "pull_rows": self.pull_rows_max,
                "entry_bytes": dict(self._entry_bytes)}


# ---------------------------------------------------------------------------
# 3) Partition — edge partitions for PEs (paper cites PowerLyra/PathGraph)
# ---------------------------------------------------------------------------


def partition_edges(src: np.ndarray, dst: np.ndarray, parts: int,
                    strategy: str = "block") -> list[np.ndarray]:
    """Return per-part edge index arrays.

    * ``block``  — contiguous equal-size slices (paper's "basic partition")
    * ``dst_hash`` — by destination (vertex-cut-free pull partitions)
    * ``hybrid`` — PowerLyra-style: low-degree dst grouped by dst, hub
      destinations' edges striped round-robin across parts
    """
    e = len(src)
    ids = np.arange(e)
    if parts <= 1:
        return [ids]
    if strategy == "block":
        return list(np.array_split(ids, parts))
    if strategy == "dst_hash":
        return [ids[dst % parts == p] for p in range(parts)]
    if strategy == "hybrid":
        deg = np.bincount(dst)
        hub_cut = max(np.percentile(deg[deg > 0], 99.0), 64) if (deg > 0).any() else 64
        is_hub_edge = deg[dst] > hub_cut
        out: list[list] = [[] for _ in range(parts)]
        normal = ids[~is_hub_edge]
        for p in range(parts):
            out[p].extend(normal[dst[normal] % parts == p].tolist())
        hub = ids[is_hub_edge]
        for i, eid in enumerate(hub):
            out[i % parts].append(eid)
        return [np.asarray(sorted(o), np.int64) for o in out]
    if strategy == "community":
        # paper §IV-C3: "separate graph with graph algorithms, such as …
        # community detection" — components (via the DSL's WCC) are packed
        # into parts greedily by size; cross-part edges are minimized by
        # construction (an edge never crosses components).
        from . import algorithms as alg
        from . import graph as G
        nv = int(max(src.max(initial=-1), dst.max(initial=-1)) + 1)
        g = G.from_edge_list(src, dst, num_vertices=nv)
        labels, _, _ = alg.wcc(g)
        labels = np.asarray(labels)
        comps, sizes = np.unique(labels, return_counts=True)
        order = np.argsort(-sizes)
        part_of_comp = {}
        load = np.zeros(parts, np.int64)
        for ci in order:
            p = int(np.argmin(load))
            part_of_comp[int(comps[ci])] = p
            load[p] += sizes[ci]
        edge_part = np.asarray([part_of_comp[int(l)] for l in labels[src]])
        return [ids[edge_part == p] for p in range(parts)]
    raise ValueError(strategy)


# ---------------------------------------------------------------------------
# 4) Reorder — degree sort / locality (paper cites Balaji & Lucia)
# ---------------------------------------------------------------------------


def reorder(src: np.ndarray, dst: np.ndarray, num_vertices: int,
            strategy: str = "degree") -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Relabel vertices; returns (new_src, new_dst, perm) with
    ``perm[old_id] = new_id``.

    * ``degree``   — descending out-degree (hubs first → BRAM/VMEM-resident)
    * ``bfs``      — BFS order from the max-degree vertex (locality)
    * ``identity`` — no-op
    """
    if strategy == "identity":
        return src, dst, np.arange(num_vertices)
    deg = np.bincount(src, minlength=num_vertices)
    if strategy == "degree":
        order = np.argsort(-deg, kind="stable")          # new→old
    elif strategy == "bfs":
        adj_off = np.zeros(num_vertices + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=num_vertices), out=adj_off[1:])
        by_src = np.argsort(src, kind="stable")
        sorted_dst = dst[by_src]
        seen = np.zeros(num_vertices, bool)
        order_list = []
        from collections import deque
        for root in np.argsort(-deg, kind="stable"):
            if seen[root]:
                continue
            q = deque([root]); seen[root] = True
            while q:
                v = q.popleft()
                order_list.append(v)
                for u in sorted_dst[adj_off[v]:adj_off[v + 1]]:
                    if not seen[u]:
                        seen[u] = True
                        q.append(u)
        order = np.asarray(order_list)
    else:
        raise ValueError(strategy)
    perm = np.empty(num_vertices, np.int64)
    perm[order] = np.arange(num_vertices)
    return perm[src].astype(np.int32), perm[dst].astype(np.int32), perm


# ---------------------------------------------------------------------------
# Dataset synthesis at the paper's SNAP sizes (offline stand-ins)
# ---------------------------------------------------------------------------

PAPER_GRAPHS = {
    # name: (|V|, |E|)  — Table V
    "email-Eu-core": (1_005, 25_571),
    "soc-Slashdot0922": (82_168, 948_464),
}


def load_paper_graph(name: str, seed: int = 0, cache_dir: str | None = None) -> G.Graph:
    """R-MAT graph with the exact |V|/|E| of the paper's dataset."""
    v, e = PAPER_GRAPHS[name]
    if cache_dir:
        path = os.path.join(cache_dir, f"{name}.npz")
        if os.path.exists(path):
            src, dst = read_edge_list(path)
            return G.from_edge_list(src, dst, num_vertices=v)
    src, dst = G.rmat_edges(v, e, seed=seed)
    if cache_dir:
        os.makedirs(cache_dir, exist_ok=True)
        write_edge_list(os.path.join(cache_dir, f"{name}.npz"), src, dst)
    return G.from_edge_list(src, dst, num_vertices=v)
