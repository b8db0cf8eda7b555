"""Paper Fig. 5: development-cost stages (program preparation, system
compilation, environment deployment) for the light-weight path vs the
general-purpose strawman."""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core import algorithms as alg
from repro.core import dsl
from repro.core import graph as G
from repro.core.comm import CommManager
from repro.core.preprocess import load_paper_graph
from repro.core.scheduler import ScheduleConfig
from repro.core.translator import translate


def run() -> list[tuple[str, float, str]]:
    rows = []
    g_host = load_paper_graph("email-Eu-core", cache_dir="reports/graphs")

    # stage 1: program preparation = building the DSL program object
    t0 = time.perf_counter()
    program = dsl.bfs_program(alg.INT_MAX)
    prep = time.perf_counter() - t0

    # stage 2: system compilation = light-weight translation + AOT staging
    t0 = time.perf_counter()
    prog = translate(program, g_host, ScheduleConfig(backend="sparse"))
    compile_s = time.perf_counter() - t0

    # stage 3: environment deployment = transport + first superstep
    comm = CommManager()
    t0 = time.perf_counter()
    g_dev = comm.transport(g_host)
    values, active = prog.init_state(roots=0)
    values, active = prog.superstep(values, active)
    jax.block_until_ready(values)
    deploy = time.perf_counter() - t0

    rows.append(("fig5/prepare_s", prep * 1e6, f"{prep * 1e3:.2f}ms"))
    rows.append(("fig5/compile_s", compile_s * 1e6, f"{compile_s:.2f}s"))
    rows.append(("fig5/deploy_s", deploy * 1e6, f"{deploy * 1e3:.1f}ms"))
    total = prep + compile_s + deploy
    rows.append(("fig5/total_s", total * 1e6, f"{total:.2f}s"))
    # the paper's qualitative claim: compilation dominates but stays small
    # ("within tens of seconds"), vs hours for synthesis flows
    rows.append(("fig5/paper_claim_tens_of_seconds", 0.0,
                 str(bool(total < 60))))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    for r in run():
        print(",".join(map(str, r)))
