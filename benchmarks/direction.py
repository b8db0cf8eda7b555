"""Direction-optimization crossover: BFS push vs pull vs auto.

The tentpole claim behind the dual-mode engine: on a frontier algorithm the
pull engine streams all E edges every superstep, while the
direction-optimized engine pays ~Σ out_deg(frontier) on push supersteps —
so BFS total edge work drops from O(diameter·E) toward O(E).  Since the
frontier-compacted forward-ELL engine the claim must hold in *wall time*
too, not just in the traversal counter.  Per R-MAT scale this module
measures:

* wall-clock per full BFS run and MTEPS (traversed edges / second) for
  ``direction='pull' | 'push' | 'auto'``;
* the algorithmic edge-traversal counters from ``report.run_stats``
  (E per pull superstep, m_f per push superstep), the direction-switch
  counts, and the compacted vs dense-fallback push superstep split;
* translate time (TT) per mode, its preprocess/passes/AOT breakdown, and
  the repeat-translate time on the cached graph (the preprocessing +
  staging caches at work);
* measured per-edge engine costs — the pull stream's ns/edge vs the
  compacted push kernel's ns/slot — from which the compaction/fallback
  crossover is re-derived (this is what calibrates the
  ``DirectionPolicy`` defaults and ``push_capacity_tiers``).

``collect()`` returns one scale's dict; ``collect_sweep()`` runs the
10k/50k/200k ladder (the ``benchmarks/run.py --json`` payload →
``BENCH_graph.json``); ``run()`` renders the standard CSV rows.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core import algorithms as alg
from repro.core import dsl
from repro.core import graph as G
from repro.core.scheduler import DirectionPolicy, ScheduleConfig
from repro.core.translator import translate

MODES = ("pull", "push", "auto")

# the multi-scale ladder: (num_vertices, num_edges); 50k/500k is the
# acceptance scale whose results surface at the payload's top level
SWEEP_SCALES = ((10_000, 100_000), (50_000, 500_000), (200_000, 2_000_000))


def _time_run(prog, root, repeats=3):
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        values, iters = prog.run(roots=root)
        jax.block_until_ready(values)
        best = min(best, time.perf_counter() - t0)
    return best, values, iters


def _time_interleaved(progs: dict, root, repeats=5) -> dict:
    """Best-of-``repeats`` wall per program, *interleaved* round-robin.

    Timing each candidate in its own contiguous block lets multi-ms
    drift on a shared 2-core box (background compiles, cache state,
    scheduler phase) land entirely on one candidate and skew ratios by
    2x; round-robin rounds expose every candidate to the same drift, so
    min-per-candidate ratios stay meaningful.  One warm-up run each
    (compile + cache fill) is excluded.
    """
    out = {}
    for name, prog in progs.items():
        values, _ = prog.run(roots=root)              # warm-up, untimed
        jax.block_until_ready(values)
        out[name] = float("inf")
    for _ in range(repeats):
        for name, prog in progs.items():
            t0 = time.perf_counter()
            values, _ = prog.run(roots=root)
            jax.block_until_ready(values)
            out[name] = min(out[name], time.perf_counter() - t0)
    return out


def _time_fn(fn, *args, repeats=5):
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def _measure_engine_costs(g, prog_pull, prog_push, root, width) -> dict:
    """Per-edge engine costs + the re-derived compaction crossover.

    Times one pull superstep (dense O(E) stream) and one *compacted* push
    superstep (root-only frontier → smallest capacity tier), then derives
    the row count at which compacted push cost would reach pull cost —
    the measurement behind the engine's tier/fallback boundary and the
    recalibrated alpha/beta defaults (see ``DirectionPolicy``).
    """
    v0, a0 = prog_pull.init_state(roots=root)
    t_pull = _time_fn(prog_pull.superstep, v0, a0)
    t_push = _time_fn(prog_push.superstep_push, v0, a0)
    tiers = prog_push.report.push_tiers
    costs = {
        "pull_superstep_s": t_pull,
        "pull_ns_per_edge": t_pull / max(g.num_edges, 1) * 1e9,
        "push_compacted_superstep_s": t_push,
    }
    if tiers:
        small = tiers[0]
        # upper bound: the whole small-tier superstep charged to its slots
        w = width
        ns_per_slot = t_push / (small * w) * 1e9
        costs.update({
            "push_tiers_rows": list(tiers),
            "push_ns_per_slot_upper": ns_per_slot,
            # rows where compacted cost would reach one pull superstep:
            # beyond this the engine's dense fallback is the right call
            "derived_crossover_rows": int(t_pull / (ns_per_slot * 1e-9 * w)),
        })
    return costs


def collect(num_vertices: int = 50_000, num_edges: int = 500_000,
            seed: int = 0, root: int = 0, repeats: int = 5) -> dict:
    """Run the BFS direction sweep at one scale; JSON-serializable dict."""
    src, dst = G.rmat_edges(num_vertices, num_edges, seed=seed)
    g = G.from_edge_list(src, dst, num_vertices=num_vertices)
    out = {
        "graph": {"num_vertices": g.num_vertices, "num_edges": g.num_edges,
                  "generator": f"rmat(seed={seed})"},
        "modes": {},
    }
    program = dsl.bfs_program(alg.INT_MAX)
    progs = {}
    repeat_s = {}
    push_ell_width = ScheduleConfig().push_ell_width
    for mode in MODES:
        cfg = ScheduleConfig(direction=DirectionPolicy(mode=mode))
        progs[mode] = translate(program, g, cfg)
        # repeat translate of identical inputs: preprocessing + staging
        # caches make this milliseconds (the acceptance criterion)
        t0 = time.perf_counter()
        translate(program, g, cfg)
        repeat_s[mode] = time.perf_counter() - t0
    # the bitmap-vs-dense pull-plane A/B the verify-script regression
    # guard pins: the forced block-skipping sweep vs the flat dense sweep
    # the shipped default resolves to on this (XLA) backend
    progs["pull_bitmap"] = translate(
        program, g, ScheduleConfig(direction=DirectionPolicy(mode="pull"),
                                   pull_sweep="bitmap"))
    progs["pull_dense"] = translate(
        program, g, ScheduleConfig(direction=DirectionPolicy(mode="pull"),
                                   pull_sweep="dense"))
    walls = _time_interleaved(progs, root, repeats)
    baseline = None
    for mode in MODES:
        prog = progs[mode]
        levels, iters = prog.run(roots=root)
        lv = np.asarray(levels)
        if baseline is None:
            baseline = lv
        else:                      # all modes must agree bit-exactly
            assert np.array_equal(baseline, lv), f"{mode} diverged from pull"
        te = alg.traversed_edges(g, levels)
        out["modes"][mode] = {
            "wall_s": walls[mode],
            "iters": int(iters),
            "mteps": te / walls[mode] / 1e6,
            "translate_time_s": prog.report.translate_time_s,
            "translate_repeat_s": repeat_s[mode],
            "translate_breakdown": prog.report.translate_breakdown,
            "backend": prog.report.backend,
            "push_layout": prog.report.push_layout,
            "pull_sweep": prog.report.pull_sweep,
            **prog.report.run_stats,
        }
    pull, auto = out["modes"]["pull"], out["modes"]["auto"]
    bstats = progs["pull_bitmap"].last_run_stats
    out["pull_plane"] = {
        "default_sweep": out["modes"]["pull"]["pull_sweep"],
        "dense_wall_s": walls["pull_dense"],
        "bitmap_wall_s": walls["pull_bitmap"],
        "wall_ratio_bitmap_vs_dense":
            walls["pull_bitmap"] / walls["pull_dense"],
        "blocks_total": progs["pull_bitmap"].report.pull_blocks_total,
        "blocks_swept": bstats["pull_blocks_swept"],
        "blocks_skipped": bstats["pull_blocks_skipped"],
    }
    out["crossover"] = {
        "traversal_reduction_auto_vs_pull":
            pull["edges_traversed"] / max(auto["edges_traversed"], 1),
        "speedup_auto_vs_pull": pull["wall_s"] / auto["wall_s"],
        "reached": int((baseline < alg.INT_MAX).sum()),
        **_measure_engine_costs(g, progs["pull"], progs["push"], root,
                                push_ell_width),
    }
    return out


def collect_sweep(scales=SWEEP_SCALES, seed: int = 0, root: int = 0,
                  repeats: int = 5) -> dict:
    """Multi-scale sweep; the 50k acceptance scale stays at the top level
    (back-compat for CI consumers of ``BENCH_graph.json``), every scale
    lands under ``sweep`` keyed by vertex count."""
    sweep = {}
    primary = None
    for v, e in scales:
        data = collect(num_vertices=v, num_edges=e, seed=seed, root=root,
                       repeats=repeats)
        sweep[str(v)] = data
        if (v, e) == (50_000, 500_000):
            primary = data
    out = dict(primary if primary is not None
               else sweep[str(scales[-1][0])])
    out["sweep"] = {
        k: {"graph": d["graph"],
            "mteps": {m: d["modes"][m]["mteps"] for m in MODES},
            "wall_s": {m: d["modes"][m]["wall_s"] for m in MODES},
            "speedup_auto_vs_pull": d["crossover"]["speedup_auto_vs_pull"],
            "traversal_reduction_auto_vs_pull":
                d["crossover"]["traversal_reduction_auto_vs_pull"]}
        for k, d in sweep.items()}
    return out


def collect_pe_sweep(max_pes: int, num_vertices: int = 50_000,
                     num_edges: int = 500_000, seed: int = 0, root: int = 0,
                     repeats: int = 3) -> dict:
    """Per-PE scaling of the sharded push engine (BFS, auto direction).

    For pes ∈ {1, 2, 4, … max_pes} (powers of two, clamped to the device
    pool): wall time, the direction/exchange counters from
    ``report.run_stats`` (``exchange_supersteps`` / ``exchange_bytes`` are
    the *executed* collectives, recorded by the run loop), the static
    per-PE interval balance (``push_pe_rows`` / ``push_pe_edges``), and
    the ``CommManager``'s accumulated totals.  The run-stat counters are
    per-run; the comm totals accumulate over every timed repeat (that is
    what they measure — the accumulation plane), so the payload records
    ``repeats`` to keep the two reconcilable:
    ``comm_collective_bytes_total == repeats · exchange_bytes``.
    Results are asserted bit-identical to pes=1 before anything is
    recorded.  Run via ``python -m benchmarks.run --pes N`` (which
    forces N host devices before jax initializes); payload lands under
    ``pe_sweep`` in ``BENCH_graph.json``.
    """
    import jax as _jax

    from repro.core.comm import CommManager

    src, dst = G.rmat_edges(num_vertices, num_edges, seed=seed)
    g = G.from_edge_list(src, dst, num_vertices=num_vertices)
    pes_ladder = [1]
    while pes_ladder[-1] * 2 <= min(max_pes, len(_jax.devices())):
        pes_ladder.append(pes_ladder[-1] * 2)
    out = {"graph": {"num_vertices": g.num_vertices,
                     "num_edges": g.num_edges,
                     "generator": f"rmat(seed={seed})"},
           # comm_* totals below accumulate over this many timed runs;
           # the run_stats counters in the same record are per-run
           "repeats": repeats,
           "per_pes": {}}
    baseline = None
    for pes in pes_ladder:
        comm = CommManager()
        prog = translate(dsl.bfs_program(alg.INT_MAX), g,
                         ScheduleConfig(pes=pes), comm)
        wall_s, levels, iters = _time_run(prog, root, repeats)
        lv = np.asarray(levels)
        if baseline is None:
            baseline = lv
        else:
            assert np.array_equal(baseline, lv), f"pes={pes} diverged"
        te = alg.traversed_edges(g, levels)
        out["per_pes"][str(pes)] = {
            "wall_s": wall_s,
            "mteps": te / wall_s / 1e6,
            "iters": int(iters),
            "report_pes": prog.report.pes,
            "exchange_plane": prog.report.exchange_plane,
            "est_collective_bytes": prog.report.est_collective_bytes,
            "push_pe_rows": list(prog.report.push_pe_rows or ()),
            "push_pe_edges": list(prog.report.push_pe_edges or ()),
            "comm_collective_bytes_total":
                comm.stats.collective_bytes_total,
            "comm_collective_supersteps": comm.stats.collective_supersteps,
            **prog.report.run_stats,
        }
    one = out["per_pes"]["1"]["wall_s"]
    out["speedup_vs_1pe"] = {p: one / d["wall_s"]
                            for p, d in out["per_pes"].items()}
    return out


def run() -> list[tuple[str, float, str]]:
    """CSV rows for the benchmark driver (smaller default for quick runs)."""
    data = collect(num_vertices=20_000, num_edges=200_000, repeats=2)
    rows = []
    for mode, m in data["modes"].items():
        rows.append((f"direction/bfs_{mode}_wall", m["wall_s"] * 1e6,
                     f"{m['mteps']:.1f}MTEPS"))
        rows.append((f"direction/bfs_{mode}_edges_traversed", 0.0,
                     str(m["edges_traversed"])))
        rows.append((f"direction/bfs_{mode}_supersteps", 0.0,
                     f"push={m['push_supersteps']}"
                     f"(compacted={m['push_compacted_supersteps']}),"
                     f"pull={m['pull_supersteps']}"))
        rows.append((f"direction/bfs_{mode}_translate_repeat",
                     m["translate_repeat_s"] * 1e6, "cached"))
    c = data["crossover"]
    rows.append(("direction/traversal_reduction_auto_vs_pull", 0.0,
                 f"{c['traversal_reduction_auto_vs_pull']:.2f}x"))
    rows.append(("direction/speedup_auto_vs_pull", 0.0,
                 f"{c['speedup_auto_vs_pull']:.2f}x"))
    rows.append(("direction/pull_ns_per_edge", 0.0,
                 f"{c['pull_ns_per_edge']:.1f}ns"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    for r in run():
        print(",".join(map(str, r)))
