"""Readings of the controls at a cell's own size, for setting its limits.

    python3 -m bench.control --workload <cell> --seeds 11,12,13

Not part of a benchmark run.  For each seed it makes the cell's graph
and the answers a run would check (the first roots of the window),
computes each with the reference in the program's place under one broken guarantee — BFS stopped
one level early, SSSP in bfloat16 — and prints the numbers the run
compares, one JSON line per seed.  Needs the chip only to generate the
graph.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from bench import reference as R
from bench import run as harness
from bench.gen import kronecker

UNREACHED = 2**30


def control_bfs(edges, root):
    full = R.bfs_levels(edges, root)
    short = R.bfs_levels(edges, root, max_depth=max(R.depth_of(full) - 1, 0))
    return np.where(short < 0, UNREACHED, short), full


def readings(spec: dict, seed: int) -> dict:
    params = spec["spec"]
    edges = kronecker.from_config(spec["config"], seed)
    level = reach = 0
    gap = 0.0
    # the run samples among the roots it completed; a run completes at
    # least this many, so the control reads the first of them
    roots = kronecker.pick_roots(edges, spec["config"]["search_keys"])
    for root in roots[:params["check_roots"]].tolist():
        if params["program"] == "bfs":
            got, want = control_bfs(edges, root)
            level += R.level_diff(got, want, UNREACHED)
        else:
            got = R.sssp_rounded(edges, root, R.round_bf16)
            want = R.sssp(edges, root)
            reach += R.reach_diff(got, want)
            gap = max(gap, R.dist_gap(got, want))
    return ({"bfs_level_diff": level} if params["program"] == "bfs"
            else {"sssp_reach_diff": reach, "sssp_gap": gap})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    benchmark = harness.load_json(harness.ROOT, "BENCHMARK.json")
    spec = harness.cell_spec(benchmark, args.workload)
    harness.require_chip(spec["entry"]["chips"])
    limits = spec["spec"]["limits"]
    for seed in (int(s) for s in args.seeds.split(",")):
        got = readings(spec, seed)
        fails = sorted(k for k, v in got.items() if v > limits[k])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": got, "fails": fails}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
