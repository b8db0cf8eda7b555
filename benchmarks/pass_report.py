"""Translation-time breakdown per pipeline pass (TT decomposed).

The paper reports translation time (TT) as one number; with the IR
refactor we can decompose it: per-pass wall time for every DSL program
template, plus the share of TT spent in AOT compilation vs. the pass
pipeline. Rows:

  pass_report/<program>/<pass>_us      — one pipeline pass
  pass_report/<program>/pipeline_us    — all passes (lower + run)
  pass_report/<program>/aot_share      — AOT-compile fraction of total TT
"""
from __future__ import annotations

import time

from repro.core import dsl
from repro.core import graph as G
from repro.core.ir import lower_program
from repro.core.passes import PassContext, default_pipeline
from repro.core.scheduler import ScheduleConfig, plan
from repro.core.translator import translate


def run() -> list[tuple[str, float, str]]:
    rows: list[tuple[str, float, str]] = []
    src, dst = G.rmat_edges(2_000, 16_000, seed=0)
    g = G.from_edge_list(src, dst, num_vertices=2_000)
    cfg = ScheduleConfig()
    ctx = PassContext(
        schedule=cfg,
        plan=plan(cfg, num_vertices=g.num_vertices, num_edges=g.num_edges),
        use_pallas=False,
        num_vertices=g.num_vertices, num_edges=g.num_edges)

    for name, factory in sorted(dsl.PROGRAM_TEMPLATES.items()):
        prog = factory()
        t0 = time.perf_counter()
        ir, report = default_pipeline().run(lower_program(prog), ctx)
        pipeline_s = time.perf_counter() - t0
        for rec in report.records:
            rows.append((f"pass_report/{name}/{rec.name}_us",
                         rec.time_s * 1e6,
                         "changed" if rec.changed else "no_change"))
        rows.append((f"pass_report/{name}/pipeline_us", pipeline_s * 1e6,
                     ir.backend or "?"))

        t1 = time.perf_counter()
        compiled = translate(prog, g, cfg)
        tt = time.perf_counter() - t1
        aot_share = max(0.0, tt - pipeline_s) / tt
        rows.append((f"pass_report/{name}/TT_us", tt * 1e6,
                     f"{compiled.report.backend}"))
        rows.append((f"pass_report/{name}/aot_share", 0.0,
                     f"{aot_share:.2f}"))
        # the jaxpr analyzer's share of this translate (cache-warm after
        # the pipeline run above — the cold trace cost shows up in the
        # per-pass program-analysis_us row instead)
        bd = compiled.report.translate_breakdown
        rows.append((f"pass_report/{name}/analysis_us",
                     bd["analysis_s"] * 1e6,
                     f"diags={len(compiled.report.diagnostics)}"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    for r in run():
        print(",".join(map(str, r)))
