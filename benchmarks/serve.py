"""Serving-plane benchmark: sustained QPS on a mixed query stream.

Drives :class:`repro.serve.graph_serve.GraphServer` with a seeded mixed
bfs / sssp / ppr / dist stream on the 50k/500k acceptance-scale R-MAT
(weighted), verifies **every** served answer bit-exact against the
sequential ``run(roots=root)`` oracle, and writes ``BENCH_serve.json``
(QPS, per-kind counts, served-by split, landmark pin rate, speedup over
serving the same stream sequentially) through the shared stamping helper
(:mod:`benchmarks.common`), appending a compact record to
``reports/graphs/history.jsonl``.

The serve plane and the oracle are warmed (translate + one run per
program shape) before the clock starts — the artifact measures sustained
serving throughput, not jit tracing.

``python -m benchmarks.serve``            full artifact (120 queries)
``python -m benchmarks.serve --smoke``    CI smoke: small graph, short
                                          stream, exits non-zero unless
                                          every answer matches and QPS > 0
"""
from __future__ import annotations

import sys
import time

import numpy as np

from .common import append_history, write_payload


def _build(num_vertices: int, num_edges: int, seed: int):
    from repro.core import graph as G
    rng = np.random.default_rng(seed)
    src, dst = G.rmat_edges(num_vertices, num_edges, seed=seed)
    w = rng.uniform(0.5, 2.0, size=src.shape[0]).astype(np.float32)
    return G.from_edge_list(src, dst, weights=w, num_vertices=num_vertices)


def _stream(rng, num_vertices: int, queries: int, ppr_roots) -> list[tuple]:
    """Seeded mixed stream: 50% bfs, 30% sssp, 10% ppr, 10% dist."""
    out = []
    for i in range(queries):
        r = i % 10
        if r < 5:
            out.append(("bfs", int(rng.integers(num_vertices)), None))
        elif r < 8:
            out.append(("sssp", int(rng.integers(num_vertices)), None))
        elif r < 9:
            out.append(("ppr", int(rng.choice(ppr_roots)), None))
        else:
            s, t = (int(x) for x in rng.integers(0, num_vertices, 2))
            out.append(("dist", s, t))
    return out


def collect(num_vertices: int = 50_000, num_edges: int = 500_000, *,
            queries: int = 120, seed: int = 0, landmarks: int = 8,
            slots: int = 8, slice_supersteps: int = 4) -> dict:
    from repro.core import dsl
    from repro.core.scheduler import (AdmissionPolicy, DirectionPolicy,
                                      ScheduleConfig)
    from repro.core.translator import translate
    from repro.serve.graph_serve import GraphServer

    g = _build(num_vertices, num_edges, seed)
    # pull-pinned: under vmap an 'auto' superstep lowers the direction
    # cond to execute-both-branches selects (~2x a pinned batch — see
    # run_batch's docstring), so the serving configuration pins pull for
    # throughput; answers are bit-identical across modes either way
    sched = ScheduleConfig(direction=DirectionPolicy(mode="pull"))
    adm = AdmissionPolicy(slots=slots, slice_supersteps=slice_supersteps)
    rng = np.random.default_rng(seed + 1)
    ppr_roots = rng.integers(0, num_vertices, 4)
    stream = _stream(rng, num_vertices, queries, ppr_roots)

    # ---- sequential oracle (also the warm-up: one translate + run per
    # program shape, so the timed section measures serving, not tracing)
    oracles: dict = {}
    seq_wall = 0.0
    t_lm0 = time.perf_counter()
    warm = GraphServer(g, schedule=sched, admission=adm,
                       landmarks=landmarks)
    landmark_build_s = time.perf_counter() - t_lm0
    for kind, root, _tgt in stream:
        prog = warm._program_for(kind, root)
        key = (prog, root)
        if key in oracles:
            continue
        cp = translate(prog, g, sched)
        t0 = time.perf_counter()
        vals, iters = cp.run(roots=root)
        vals = np.asarray(vals)                  # blocks until ready
        seq_wall += time.perf_counter() - t0
        oracles[key] = (vals, int(iters))
    # warm the batched slice loops (vmapped jits compile per slot count)
    for kind in ("bfs", "sssp", "dist"):
        warm.submit(kind, int(ppr_roots[0]), target=0
                    if kind == "dist" else None)
    warm.submit("ppr", int(ppr_roots[0]))
    warm.run()

    # ---- timed serve: fresh server, same compiled programs (staging
    # cache + shared loop caches keep everything warm)
    srv = GraphServer(g, schedule=sched, admission=adm,
                      landmarks=landmarks)
    t0 = time.perf_counter()
    handles = [srv.submit(kind, root, target=tgt)
               for kind, root, tgt in stream]
    srv.run()
    wall = time.perf_counter() - t0

    # ---- verify every answer against the oracle
    checked = 0
    for (kind, root, tgt), q in zip(stream, handles):
        assert q.done, (kind, root, q.status)
        ref, iters = oracles[(q.program, root)]
        if kind == "dist":
            ok = q.result == float(ref[tgt])
        else:
            ok = np.array_equal(np.asarray(q.result), ref) \
                and q.iters == iters
        if not ok:
            raise AssertionError(
                f"served answer mismatch: {kind} root={root} tgt={tgt} "
                f"served_by={q.served_by}")
        checked += 1

    by_kind: dict[str, int] = {}
    by_path: dict[str, int] = {}
    for (kind, _r, _t), q in zip(stream, handles):
        by_kind[kind] = by_kind.get(kind, 0) + 1
        by_path[q.served_by] = by_path.get(q.served_by, 0) + 1
    supersteps = sum(grp.supersteps for grp in srv._groups.values())
    dist_total = by_kind.get("dist", 0)
    pinned = sum(1 for (k, _r, _t), q in zip(stream, handles)
                 if k == "dist" and q.served_by == "landmark")
    return {
        "bench": "serve",
        "graph": {"num_vertices": num_vertices, "num_edges": num_edges,
                  "generator": f"rmat(seed={seed}), weights U(0.5,2)"},
        "admission": adm.describe(),
        "direction": sched.direction.describe(),
        "stream": {"queries": queries, "by_kind": by_kind,
                   "distinct_programs": len(srv._programs)},
        "served": {"wall_s": wall, "qps": queries / wall,
                   "supersteps": supersteps, "by_path": by_path},
        "verified": {"checked": checked, "bit_exact": True},
        "sequential": {"wall_s": seq_wall,
                       "distinct_runs": len(oracles),
                       "speedup_serve_vs_sequential": seq_wall / wall},
        "landmarks": {"k": landmarks, "build_s": landmark_build_s,
                      "dist_queries": dist_total,
                      "pinned": pinned,
                      "pin_rate": pinned / dist_total if dist_total else
                      None},
    }


def main() -> None:
    argv = sys.argv[1:]
    smoke = "--smoke" in argv
    if smoke:
        argv.remove("--smoke")
    path = argv[0] if argv else "BENCH_serve.json"
    if smoke:
        data = collect(3_000, 30_000, queries=24, landmarks=4, slots=4)
        qps = data["served"]["qps"]
        assert data["verified"]["bit_exact"] and qps > 0
        print(f"serve smoke ok: {data['verified']['checked']} answers "
              f"bit-exact, {qps:.1f} qps "
              f"(by_path={data['served']['by_path']})")
        return
    data = collect()
    write_payload(path, data)
    hist = append_history(
        {"bench": "serve",
         "qps": data["served"]["qps"],
         "wall_s": data["served"]["wall_s"],
         "queries": data["stream"]["queries"],
         "speedup_serve_vs_sequential":
             data["sequential"]["speedup_serve_vs_sequential"]},
        stamped=data)
    print(f"wrote {path} (schema {data['schema']}, commit "
          f"{data['commit']}); appended {hist}")
    s = data["served"]
    print(f"  {data['stream']['queries']} queries "
          f"({data['stream']['by_kind']}) in {s['wall_s']:.2f}s "
          f"= {s['qps']:.1f} qps sustained, {s['supersteps']} supersteps, "
          f"by_path={s['by_path']}")
    print(f"  all {data['verified']['checked']} answers bit-exact vs "
          f"sequential oracle; sequential replay "
          f"{data['sequential']['wall_s']:.2f}s -> "
          f"{data['sequential']['speedup_serve_vs_sequential']:.2f}x")
    lm = data["landmarks"]
    print(f"  landmarks k={lm['k']}: {lm['pinned']}/{lm['dist_queries']} "
          f"dist queries pinned (build {lm['build_s']:.2f}s)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
