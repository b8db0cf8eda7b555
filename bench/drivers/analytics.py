"""Graph500-style analytics: roots one after another through
``CompiledGraphProgram.run``.

Set-up generates the graph on the device, ingests it, translates the
cell's program and runs it once from the cheapest vertex, which compiles
(or loads) the run loop.  The window then walks the seeded roots in order,
wrapping around, and pulls each answer to the host; it ends with the first
root to finish after ``seconds``.  Only whole roots count.
"""
from __future__ import annotations

import time

import numpy as np

from bench import reference as R
from bench.drivers import load_graph
from bench.gen import kronecker

UNREACHED = 2**30           # the BFS program's sentinel, set by the harness


def make(cell):
    return Analytics(cell)


class Analytics:
    kind = "analytics"

    def __init__(self, cell):
        self.cell = cell
        self.program = cell.params["program"]
        if self.program not in ("bfs", "sssp"):
            raise ValueError(f"analytics program must be bfs or sssp, "
                             f"not {self.program!r}")
        self.records: dict = {"program": self.program, "translate": [],
                              "runs": []}
        self.answers: dict[int, np.ndarray] = {}
        self.attempted = self.failed = self.checked = 0

    # -- set-up ------------------------------------------------------------

    def setup(self):
        from repro.core import dsl
        from repro.core.scheduler import DirectionPolicy, ScheduleConfig
        from repro.core.translator import translate

        cell = self.cell
        self.edges, g = load_graph(cell)
        e = self.edges
        program = (dsl.bfs_program(UNREACHED) if self.program == "bfs"
                   else dsl.sssp_program())
        schedule = ScheduleConfig(direction=DirectionPolicy(
            mode=cell.params["direction"]))
        with cell.spans("translate"):
            self.prog = translate(program, g, schedule)
        self.records["translate"].append(
            dict(self.prog.report.translate_breakdown))
        self.roots = kronecker.pick_roots(e, cell.config["search_keys"])
        with cell.spans("warmup"):
            values, _ = self.prog.run(roots=kronecker.idle_vertex(e))
            np.asarray(values)

    # -- window ------------------------------------------------------------

    def window(self, seconds: float):
        spans, prog, runs = self.cell.spans, self.prog, self.records["runs"]
        t0 = time.perf_counter()
        i = 0
        while True:
            root = int(self.roots[i % len(self.roots)])
            with spans("root"):
                start = time.perf_counter()
                values, iters = prog.run(roots=root)
                answer = np.asarray(values)
                end = time.perf_counter()
            stats = prog.last_run_stats
            runs.append({"root": root, "start_s": start - t0,
                         "end_s": end - t0, "iters": int(iters),
                         "edges_traversed": stats["edges_traversed"],
                         "push_supersteps": stats["push_supersteps"],
                         "pull_supersteps": stats["pull_supersteps"],
                         "terminated": stats["terminated"]})
            self.answers.setdefault(root, answer)
            i += 1
            if end - t0 >= seconds:
                break
        self.records["elapsed_s"] = runs[-1]["end_s"]
        self.records["num_vertices"] = self.edges.num_vertices
        # work per root, from the benchmark's own graph: the directed edges
        # out of the vertices the answer reached (twice the input tuples of
        # the root's component)
        degree = self.edges.degree
        for run in runs:
            run["reached_edges"] = int(degree[self._reached(
                self.answers[run["root"]])].sum())

    def _reached(self, answer):
        if self.program == "bfs":
            return answer != UNREACHED
        return np.isfinite(answer)

    def release(self):
        del self.prog

    def summary(self) -> str:
        runs = self.records["runs"]
        return (f"window: {len(runs)} roots in "
                f"{self.records['elapsed_s']!r} s, "
                f"{sum(r['iters'] for r in runs)} supersteps "
                f"({sum(r['push_supersteps'] for r in runs)} push, "
                f"{sum(r['pull_supersteps'] for r in runs)} pull)")

    # -- correctness -------------------------------------------------------

    def check(self) -> dict:
        """Compare a seeded sample of the window's distinct roots."""
        runs = self.records["runs"]
        self.attempted = len(runs)
        self.failed = sum(r["terminated"] != "converged" for r in runs)
        done = sorted(self.answers)
        rng = np.random.default_rng([int(self.cell.seed), 3])
        k = min(self.cell.params["check_roots"], len(done))
        sample = [done[j] for j in rng.permutation(len(done))[:k]]
        out = {}
        if self.program == "bfs":
            diff = 0
            for root in sample:
                d = R.level_diff(self.answers[root],
                                 R.bfs_levels(self.edges, root), UNREACHED)
                self.failed += d > 0
                diff += d
            out["bfs_level_diff"] = diff
        else:
            reach, gap = 0, 0.0
            for root in sample:
                want = R.sssp(self.edges, root)
                r = R.reach_diff(self.answers[root], want)
                g = R.dist_gap(self.answers[root], want)
                self.failed += (r > 0
                                or g > self.cell.params["limits"]["sssp_gap"])
                reach += r
                gap = max(gap, g)
            out["sssp_reach_diff"] = reach
            out["sssp_gap"] = gap
        self.checked = len(sample)
        return out
