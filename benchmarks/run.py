"""Benchmark driver: one module per paper table/figure.

Default mode prints ``name,us_per_call,derived`` CSV rows for the selected
modules.  ``--json [path]`` runs the direction-optimization graph benchmark
as a multi-scale sweep (10k/50k/200k-vertex R-MAT, 10x edges each) and
writes the machine-readable payload — BFS MTEPS and wall time for
push/pull/auto per scale, edge-traversal / direction-switch / compaction /
pull-block-skip counters, the bitmap-vs-dense pull-plane A/B, translate-time
breakdowns (incl. cached repeat), and measured per-edge engine costs — to
``BENCH_graph.json`` (CI's perf artifact).  The payload is
schema-versioned (``schema``/``timestamp``/``commit``) and every ``--json``
run also appends a compact record to ``reports/graphs/history.jsonl`` so
the perf trajectory accumulates across PRs instead of being overwritten.
The 50k/500k acceptance scale keeps its fields at the payload top level.

``--pes N`` runs the multi-PE scaling sweep of the sharded push engine
(BFS auto at pes ∈ {1, 2, …, N} on N forced host devices — the flag must
be handled before jax initializes, which is why this driver imports the
benchmark modules lazily) and merges the payload under ``pe_sweep`` in
``BENCH_graph.json``: per-PE wall time / MTEPS, the executed exchange
bytes and supersteps recorded by the run loop, and the interval balance.
On an accelerator host the sweep runs on the real devices, and refuses
to run with fewer than N.  ``--pes`` is a separate invocation from
``--json`` (enforced): forced host devices change XLA:CPU scheduling, so
the single-PE acceptance sweep must never run under them.

``--scale`` runs the out-of-core scale sweep (``benchmarks.scale``:
partitioned BFS/SSSP over 500k/5M/20M-edge R-MAT containers under a
partition budget smaller than the edge stream) and merges the payload
under ``scale_sweep`` — per scale: MTEPS, bytes streamed h2d, partitions
skipped, transfer/compute overlap efficiency, and a peak-memory
snapshot.  Each scale point also appends its own history record carrying
a ``scale`` field, so the trajectory file distinguishes the resident
acceptance sweep (``scale: "50k/500k"``) from the streamed points.
"""
from __future__ import annotations

import json
import os
import sys

from repro.compile_cache import enable_compile_cache

from .common import (BENCH_SCHEMA, append_history, memory_snapshot,  # noqa: F401
                     stamp as _stamp)


def _append_history(payload: dict) -> str:
    """Append this sweep's headline numbers to reports/graphs/history.jsonl.

    ``BENCH_graph.json`` is overwritten every run; the history line keeps
    the perf trajectory across PRs.  Stamping (schema/timestamp/commit)
    rides :func:`benchmarks.common.append_history` — the same helper the
    serving benchmark uses, so the two payloads can't drift.
    """
    entry = {
        "mteps": {m: d["mteps"] for m, d in payload.get("modes", {}).items()},
        "wall_s": {m: d["wall_s"]
                   for m, d in payload.get("modes", {}).items()},
        "speedup_auto_vs_pull":
            payload.get("crossover", {}).get("speedup_auto_vs_pull"),
        "traversal_reduction_auto_vs_pull":
            payload.get("crossover", {}).get(
                "traversal_reduction_auto_vs_pull"),
        "pull_plane": payload.get("pull_plane"),
        # every history record names its scale so the streamed scale-sweep
        # points and this resident acceptance sweep stay distinguishable
        "scale": "50k/500k",
    }
    return append_history(entry, stamped=payload)


def _run_csv(only: list[str]) -> None:
    from . import (direction, fig5, lm_step, pass_report, roofline, table_iv,
                   table_v)
    mods = {
        "table_iv": table_iv,
        "table_v": table_v,
        "fig5": fig5,
        "lm_step": lm_step,
        "roofline": roofline,
        "pass_report": pass_report,
        "direction": direction,
    }
    only = only or list(mods)
    print("name,us_per_call,derived")
    for name in only:
        for row in mods[name].run():
            print(",".join(str(x) for x in row), flush=True)


def _run_json(path: str) -> None:
    from . import direction
    data = direction.collect_sweep()
    data["memory"] = memory_snapshot()
    _stamp(data)
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
    hist = _append_history(data)
    c = data["crossover"]
    print(f"wrote {path} (schema {data['schema']}, commit {data['commit']}); "
          f"appended {hist}")
    p = data.get("pull_plane", {})
    if p:
        print(f"  pull plane (default={p['default_sweep']}): "
              f"bitmap {p['bitmap_wall_s']*1e3:.1f} ms vs "
              f"dense {p['dense_wall_s']*1e3:.1f} ms "
              f"({p['wall_ratio_bitmap_vs_dense']:.2f}x), "
              f"blocks {p['blocks_swept']}/{p['blocks_skipped']} "
              f"swept/skipped")
    for mode, m in data["modes"].items():
        print(f"  bfs[{mode}] @50k: {m['mteps']:.1f} MTEPS, "
              f"{m['edges_traversed']} edges traversed, "
              f"TT={m['translate_time_s']:.2f}s "
              f"(repeat {m['translate_repeat_s'] * 1e3:.0f}ms)")
    print(f"  auto vs pull @50k: "
          f"{c['traversal_reduction_auto_vs_pull']:.2f}x fewer "
          f"edge-traversals, {c['speedup_auto_vs_pull']:.2f}x wall-clock")
    for v, s in sorted(data["sweep"].items(), key=lambda kv: int(kv[0])):
        print(f"  sweep V={v}: auto {s['mteps']['auto']:.1f} MTEPS, "
              f"{s['speedup_auto_vs_pull']:.2f}x vs pull, "
              f"{s['traversal_reduction_auto_vs_pull']:.2f}x fewer edges")


def _run_pes(max_pes: int, path: str) -> None:
    import jax

    from . import direction
    devices = jax.devices()
    if len(devices) < max_pes:
        print(f"error: --pes {max_pes} needs {max_pes} devices, found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        raise SystemExit(2)
    data = direction.collect_pe_sweep(max_pes)
    payload = {}
    if os.path.exists(path):
        with open(path) as f:
            payload = json.load(f)
    payload["pe_sweep"] = data
    _stamp(payload)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"merged pe_sweep into {path}")
    for pes, d in sorted(data["per_pes"].items(), key=lambda kv: int(kv[0])):
        print(f"  pes={pes}: {d['wall_s']*1e3:.1f} ms "
              f"({data['speedup_vs_1pe'][pes]:.2f}x vs 1 PE), "
              f"{d['mteps']:.1f} MTEPS, push={d['push_supersteps']}"
              f"(compacted={d['push_compacted_supersteps']}), "
              f"exchange {d['exchange_supersteps']} supersteps / "
              f"{d['exchange_bytes']} B")


def _run_scale(path: str) -> None:
    from . import scale
    data = scale.collect_scale_sweep()
    payload = {}
    if os.path.exists(path):
        with open(path) as f:
            payload = json.load(f)
    payload["scale_sweep"] = data
    _stamp(payload)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"merged scale_sweep into {path}")
    for label, s in data["scales"].items():
        b = s["bfs"]
        append_history({
            "scale": label,
            "mteps": {"bfs": b["mteps"]},
            "wall_s": {"bfs": b["wall_s"]},
            "partition_bytes_h2d": b["partition_bytes_h2d"],
            "partitions_skipped": b["partitions_skipped"],
            "overlap_efficiency": b["overlap_efficiency"],
            "peak_host_rss_bytes": s["memory"]["peak_host_rss_bytes"],
        }, stamped=payload)
        check = s.get("resident_crosscheck_bitexact")
        extra = "" if check is None else f", resident cross-check={check}"
        print(f"  scale {label} (V={s['num_vertices']}): "
              f"bfs {b['mteps']:.1f} MTEPS in {b['wall_s']:.2f}s, "
              f"{b['partition_bytes_h2d'] / 1e6:.1f} MB h2d, "
              f"{b['partitions_skipped']}/{b['partitions_swept']} "
              f"parts skipped/swept, "
              f"overlap {b['overlap_efficiency']:.2f}{extra}")
        if "sssp" in s:
            ss = s["sssp"]
            print(f"  scale {label}: sssp {ss['mteps']:.1f} MTEPS in "
                  f"{ss['wall_s']:.2f}s, "
                  f"{ss['partition_bytes_h2d'] / 1e6:.1f} MB h2d")
    print(f"  appended {len(data['scales'])} scale records to history")


def main() -> None:
    argv = sys.argv[1:]
    max_pes = None
    if "--pes" in argv:
        i = argv.index("--pes")
        try:
            max_pes = int(argv[i + 1])
        except (IndexError, ValueError):
            print("error: --pes needs a device count (--pes N)",
                  file=sys.stderr)
            raise SystemExit(2)
        if max_pes < 1:
            print(f"error: --pes must be >= 1, got {max_pes}",
                  file=sys.stderr)
            raise SystemExit(2)
        del argv[i:i + 2]
        # must land before the lazy benchmark imports pull in jax: on a
        # CPU host the sweep needs N forced host devices; on an
        # accelerator host the flag is inert and the sweep runs on the
        # real devices (or _run_pes refuses when there are fewer than N)
        import re
        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(r"xla_force_host_platform_device_count=(\d+)", flags)
        if m is None:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={max_pes}"
            ).strip()
        elif int(m.group(1)) < max_pes:
            # a smaller inherited count would silently truncate the sweep
            print(f"error: XLA_FLAGS already forces "
                  f"{m.group(1)} host devices (< --pes {max_pes}); "
                  "unset it or lower --pes", file=sys.stderr)
            raise SystemExit(2)
    enable_compile_cache()    # after the device flags: this imports jax
    if "--json" in argv:
        argv.remove("--json")
        if max_pes is not None:
            # forced host devices are a debug configuration that changes
            # XLA:CPU scheduling — the single-PE acceptance sweep must
            # never run under it, or the artifact's headline numbers stop
            # being comparable across CI runs.  Run the two sweeps as
            # separate invocations; --pes merges into the existing file.
            print("error: --pes and --json are separate runs "
                  "(run --json first, then --pes N to merge pe_sweep)",
                  file=sys.stderr)
            raise SystemExit(2)
        if "--scale" in argv:
            print("error: --json and --scale are separate runs "
                  "(run --json first, then --scale to merge scale_sweep)",
                  file=sys.stderr)
            raise SystemExit(2)
        _run_json(argv[0] if argv else "BENCH_graph.json")
        return
    if "--scale" in argv:
        argv.remove("--scale")
        if max_pes is not None:
            print("error: --pes and --scale are separate runs",
                  file=sys.stderr)
            raise SystemExit(2)
        _run_scale(argv[0] if argv else "BENCH_graph.json")
        return
    if max_pes is not None:
        _run_pes(max_pes, argv[0] if argv else "BENCH_graph.json")
        return
    _run_csv(argv)


if __name__ == '__main__':
    main()
