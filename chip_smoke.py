#!/usr/bin/env python3
"""Smoke run of the graph engine's main path on a TPU.

Builds an R-MAT graph from ``--seed`` alone (Graph500 Kronecker
A/B/C = .57/.19/.19, edgefactor 16, edge weights U[0,1)), runs BFS
(direction ``auto``) and SSSP through ``translate()``/``run()`` from
seeded roots, serves a few bfs/sssp/dist queries through ``GraphServer``,
and checks every answer against plain NumPy references kept in this file:
frontier BFS for levels (exact) and Bellman-Ford over dst-sorted edges for
distances (to ``DIST_RTOL``).  Every timing printed is a smoke timing of
one cold process, not a benchmark.

    python chip_smoke.py             # one chip: R-MAT scale 22 (2^22 V, 2^26 E)
    python chip_smoke.py --pes 4     # BFS + SSSP sharded over four chips

Exits non-zero before any phase unless JAX's first device is a TPU, and
on any mismatch or exception.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import algorithms as alg  # noqa: E402
from repro.core import dsl  # noqa: E402
from repro.core import graph as G  # noqa: E402
from repro.core.scheduler import (AdmissionPolicy, DirectionPolicy,  # noqa: E402
                                  ScheduleConfig)
from repro.core.translator import translate  # noqa: E402
from repro.serve.graph_serve import GraphServer  # noqa: E402

EDGEFACTOR = 16
UNREACHED = alg.INT_MAX          # the BFS program's unreached level
# SSSP sums the same float32 terms in the same order as the reference, so
# distances agree exactly in practice; the bound leaves room for
# reassociation only
DIST_RTOL = 1e-6


# ---------------------------------------------------------------------------
# Graph and roots, from the seed alone
# ---------------------------------------------------------------------------


def build_edges(scale: int, seed: int):
    """Graph500-style R-MAT edge list: ``(V, src, dst, weights)``."""
    V = 1 << scale
    src, dst = G.rmat_edges(V, EDGEFACTOR * V, seed=seed)
    w = np.random.default_rng([seed, 1]).random(len(src), dtype=np.float32)
    return V, src, dst, w


def pick_roots(src: np.ndarray, V: int, k: int, seed: int) -> list[int]:
    """``k`` distinct seeded roots of nonzero out-degree."""
    candidates = np.flatnonzero(np.bincount(src, minlength=V))
    rng = np.random.default_rng([seed, 2])
    return [int(r) for r in rng.choice(candidates, size=k, replace=False)]


# ---------------------------------------------------------------------------
# NumPy references (independent of the translator)
# ---------------------------------------------------------------------------


class Reference:
    """BFS levels and SSSP distances from plain NumPy over the edge list."""

    def __init__(self, V: int, src, dst, w):
        self.V = V
        by_src = np.argsort(src, kind="stable")
        self.out_dst = dst[by_src]
        self.out_off = np.searchsorted(src[by_src], np.arange(V + 1))
        by_dst = np.argsort(dst, kind="stable")
        self.in_src = src[by_dst]
        self.in_w = w[by_dst]
        dst_sorted = dst[by_dst]
        self.heads = np.flatnonzero(np.r_[True, dst_sorted[1:]
                                          != dst_sorted[:-1]])
        self.head_dst = dst_sorted[self.heads]

    def bfs(self, root: int) -> np.ndarray:
        """Level-synchronous frontier BFS; unreached = ``UNREACHED``."""
        level = np.full(self.V, UNREACHED, np.int32)
        level[root] = 0
        frontier = np.asarray([root])
        depth = 0
        while frontier.size:
            depth += 1
            lo, hi = self.out_off[frontier], self.out_off[frontier + 1]
            n = hi - lo
            first = np.repeat(lo - np.cumsum(n) + n, n)
            nbrs = self.out_dst[first + np.arange(int(n.sum()))]
            nbrs = np.unique(nbrs[level[nbrs] == UNREACHED])
            level[nbrs] = depth
            frontier = nbrs
        return level

    def sssp(self, root: int) -> np.ndarray:
        """Bellman-Ford over dst-sorted edges, float32, to the fixpoint."""
        dist = np.full(self.V, np.inf, np.float32)
        dist[root] = 0.0
        while True:
            cand = dist[self.in_src] + self.in_w
            best = np.minimum.reduceat(cand, self.heads)
            new = dist.copy()
            new[self.head_dst] = np.minimum(dist[self.head_dst], best)
            if np.array_equal(new, dist):
                return dist
            dist = new


def levels_match(got, want) -> tuple[bool, str]:
    got = np.asarray(got)
    bad = int(np.count_nonzero(got != want))
    return bad == 0, f"{bad} of {want.size} levels differ"


def dists_match(got, want) -> tuple[bool, str]:
    got = np.asarray(got, np.float32)
    inf_ok = np.array_equal(np.isinf(got), np.isinf(want))
    fin = np.isfinite(want)
    diff = np.abs(got[fin] - want[fin])
    worst = float(diff.max()) if diff.size else 0.0
    ok = inf_ok and bool(np.all(diff <= DIST_RTOL * np.maximum(
        np.abs(want[fin]), 1.0)))
    return ok, (f"max |diff| {worst!r} over {int(fin.sum())} reached, "
                f"unreached pattern {'equal' if inf_ok else 'DIFFERS'}")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def engine_phase(g, roots, pes: int, log) -> dict:
    """BFS (auto) and SSSP through translate()/run(); ``{(kind, root):
    values}``.  With ``pes > 1`` also checks the plan really spans ``pes``
    distinct devices."""
    import jax

    out = {}
    schedule = ScheduleConfig(pes=pes,
                              direction=DirectionPolicy(mode="auto"))
    for kind, program in (("bfs", dsl.bfs_program(alg.INT_MAX)),
                          ("sssp", dsl.sssp_program())):
        t0 = time.perf_counter()
        prog = translate(program, g, schedule)
        tt = time.perf_counter() - t0
        rep = prog.report
        b = rep.translate_breakdown
        log(f"{kind}: translate {tt!r} s (preprocess {b['preprocess_s']!r}, "
            f"passes {b['passes_s']!r}, aot {b['aot_s']!r}) "
            f"backend={rep.backend} pull_sweep={rep.pull_sweep} "
            f"pes={rep.pes} exchange_plane={rep.exchange_plane}")
        if pes > 1:
            devices = prog.layouts["fwd_pe"]["row_src"].sharding.device_set
            platform = jax.devices()[0].platform
            if rep.pes != pes or len(devices) != pes or any(
                    d.platform != platform for d in devices):
                raise AssertionError(
                    f"{kind}: asked for pes={pes}, report.pes={rep.pes}, "
                    f"sharded layout spans {len(devices)} devices "
                    f"({sorted({d.platform for d in devices})})")
            log(f"{kind}: forward ELL sharded over "
                f"{sorted(d.id for d in devices)} "
                f"({sorted({d.platform for d in devices})})")
        for i, root in enumerate(roots):
            t0 = time.perf_counter()
            values, iters = prog.run(roots=root)
            values = jax.block_until_ready(values)
            dt = time.perf_counter() - t0
            stats = prog.last_run_stats
            note = " (includes the run loop's compile)" if i == 0 else ""
            log(f"{kind}: root {root}: run {dt!r} s{note}, "
                f"supersteps {int(iters)} (push {stats['push_supersteps']}, "
                f"pull {stats['pull_supersteps']}), "
                f"terminated={stats['terminated']}")
            out[(kind, root)] = np.asarray(values)
    return out


def server_phase(g, roots, log) -> list:
    """bfs/sssp/dist queries through GraphServer; ``[(query, want_kind,
    want_root)]`` answered."""
    server = GraphServer(g, schedule=ScheduleConfig(
        direction=DirectionPolicy(mode="auto")),
        admission=AdmissionPolicy(slots=4))
    rng = np.random.default_rng(len(roots))
    queries = []
    for root in roots[:3]:
        queries.append(server.submit("bfs", root))
    for root in roots[:3]:
        queries.append(server.submit("sssp", root))
    for root in roots[2:4]:
        target = int(rng.integers(g.num_vertices))
        queries.append(server.submit("dist", root, target=target))
    t0 = time.perf_counter()
    server.run()
    dt = time.perf_counter() - t0
    log(f"server: {len(queries)} queries answered in {dt!r} s "
        f"(includes compiles), served_by="
        f"{sorted({q.served_by for q in queries})}")
    return queries


def check(results: dict, queries: list, ref: Reference, log) -> bool:
    """Compare every engine and server answer with the references."""
    want = {}

    def expected(kind, root):
        if (kind, root) not in want:
            t0 = time.perf_counter()
            want[(kind, root)] = (ref.bfs(root) if kind == "bfs"
                                  else ref.sssp(root))
            log(f"reference {kind} root {root}: "
                f"{time.perf_counter() - t0!r} s")
        return want[(kind, root)]

    ok = True
    for (kind, root), got in results.items():
        match = levels_match if kind == "bfs" else dists_match
        good, msg = match(got, expected(kind, root))
        log(f"engine {kind} root {root}: {'match' if good else 'MISMATCH'}"
            f" ({msg})")
        ok &= good
    for q in queries:
        if q.status != "done" or q.answer_quality != "exact":
            log(f"server q{q.qid} {q.kind}: status={q.status} "
                f"quality={q.answer_quality}: MISMATCH")
            ok = False
            continue
        if q.kind == "dist":
            d = expected("sssp", q.root)[q.target]
            got = np.float32(q.result)
            good = (np.isinf(d) and np.isinf(got)) or bool(
                abs(got - d) <= DIST_RTOL * max(abs(float(d)), 1.0))
            msg = f"d({q.root}->{q.target}) = {float(got)!r} vs {float(d)!r}"
        else:
            match = levels_match if q.kind == "bfs" else dists_match
            good, msg = match(q.result, expected(q.kind, q.root))
        log(f"server q{q.qid} {q.kind} root {q.root} "
            f"({q.served_by}): {'match' if good else 'MISMATCH'} ({msg})")
        ok &= good
    return ok


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=int, default=22,
                    help="R-MAT scale: 2^scale vertices, 16x edges")
    ap.add_argument("--roots", type=int, default=4)
    ap.add_argument("--pes", type=int, default=1,
                    help="shard BFS + SSSP over this many chips (runs "
                         "only that phase and its reference check)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is "
              f"platform {devices[0].platform!r}; nothing was run",
              file=sys.stderr)
        return 2
    if args.pes > 1 and (len(devices) < args.pes or any(
            d.platform != "tpu" for d in devices[:args.pes])):
        print(f"chip_smoke: --pes {args.pes} needs {args.pes} TPU devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()

    def log(msg):
        print(f"[smoke] {msg}", flush=True)

    dev = devices[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache}; timings are smoke timings, not a "
        "benchmark")
    t0 = time.perf_counter()
    V, src, dst, w = build_edges(args.scale, args.seed)
    g = G.from_edge_list(src, dst, num_vertices=V, weights=w)
    roots = pick_roots(src, V, args.roots, args.seed)
    log(f"graph: R-MAT scale {args.scale}, V={V}, E={g.num_edges}, "
        f"host build {time.perf_counter() - t0!r} s; roots {roots}")
    t0 = time.perf_counter()
    ref = Reference(V, src, dst, w)
    log(f"reference index build {time.perf_counter() - t0!r} s")
    del src, dst, w

    results = engine_phase(g, roots, args.pes, log)
    queries = [] if args.pes > 1 else server_phase(g, roots, log)
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')!r} "
        f"(device 0, bytes_limit {stats.get('bytes_limit')!r})")
    if not check(results, queries, ref, log):
        print("chip_smoke: results disagree with the NumPy reference",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
