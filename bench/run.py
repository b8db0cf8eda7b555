"""Run one benchmark cell once and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell is an entry of ``BENCHMARK.json``'s
``workloads``; its file ``bench/workloads/<cell>.json`` names the window
driver (``bench/drivers/<driver>.py``) and the traffic, and its
configuration is ``bench/configs/<config>.json``.  Each metric is read by
``bench/metrics/<metric>.py``.  A run sets up (generation, ingest,
translate, compile, warm-up), measures for ``--seconds``, reads the peak
device memory, drops the program's state and compares a seeded sample of
the window's answers with the references in ``bench/reference.py``.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` traces
the window with the JAX profiler and reports the per-layer metrics.  The
last line of standard output is one JSON object; the numbers compared
with their limits are the last lines of standard error and the last key
of that object.  Exits non-zero, printing no result, unless JAX's first
device is a TPU and there are as many as the cell asks for.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class BenchError(Exception):
    """The cell cannot be run here; the run prints no result."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (metric names may hold
    dots, so they are loaded by path)."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.exists(path):
        raise BenchError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(benchmark: dict, name: str) -> dict:
    """The cell's entry, its workload file and its configuration."""
    entry = next((w for w in benchmark["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    spec = load_json(BENCH_DIR, "workloads", f"{name}.json")
    if (spec["config"], spec["traffic"]) != (entry["config"],
                                              entry["traffic"]):
        raise BenchError(f"bench/workloads/{name}.json disagrees with "
                         "BENCHMARK.json on config or traffic")
    config = load_json(BENCH_DIR, "configs", f"{entry['config']}.json")
    return {"entry": entry, "spec": spec, "config": config}


def metrics_for(benchmark: dict, cell: str, trace: bool) -> list[dict]:
    group = benchmark["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def require_chip(chips: int):
    """JAX's devices, if the first is a TPU and there are ``chips``."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU, but JAX's first device is "
                         f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX has "
                         f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache():
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache`` (a fixed path: the path is part of the key).

    The same rule as the program's ``repro.compile_cache``, kept here on
    purpose: the benchmark owns where its runs compile and what they cache
    (every program, however quick to compile), so that a change to the
    program cannot move work into or out of ``setup_s`` through the cache's
    settings."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run_cell(spec: dict, *, name: str, seed: int, seconds: float,
             trace: bool, metrics: list[dict], devices, peaks: dict,
             start: float) -> tuple[dict, str]:
    """Set up, measure, check.  Returns the result object and the driver's
    one-line report of the window.  The caller has already chosen the
    devices and their peaks."""
    import jax

    from bench import trace as tracing
    from bench.spans import Spans

    params = spec["spec"]

    def peak() -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)

    cell = types.SimpleNamespace(name=name, seed=seed, seconds=seconds,
                                 config=spec["config"], params=params,
                                 spans=Spans(peak))
    driver = importlib.import_module(
        f"bench.drivers.{params['driver']}").make(cell)
    readers = {m["name"]: load_module("metrics", m["name"]) for m in metrics}

    driver.setup()
    setup_s = time.perf_counter() - start
    log(f"{name}: set-up {setup_s!r} s "
        f"({ {k: cell.spans.total(k) for k in cell.spans.intervals} })")
    log(f"{name}: peak bytes in use at the end of each set-up phase "
        f"{cell.spans.peaks}")

    compiles = []

    def count(event, *_a, **_k):
        if event in COMPILE_EVENTS:
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(count)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    summary = None
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            with jax.profiler.trace(trace_dir, profiler_options=opts):
                with cell.spans("window"):
                    driver.window(seconds)
        else:
            with cell.spans("window"):
                driver.window(seconds)
        jax.monitoring.unregister_event_duration_listener(count)
        log(f"{name}: window {cell.spans.total('window')!r} s, "
            f"{len(compiles)} compile events inside it")
        memory_peak = peak()
        if trace:
            summary = tracing.reduce(tracing.find_trace(trace_dir),
                                     devices=len(devices))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    driver.release()
    t_check = time.perf_counter()
    checks = driver.check()
    log(f"{name}: checked {driver.checked} answers in "
        f"{time.perf_counter() - t_check!r} s")

    run = types.SimpleNamespace(
        driver=driver.kind, records=driver.records, spans=cell.spans,
        setup_s=setup_s, seconds=seconds, memory_peak_bytes=memory_peak,
        trace=summary, peaks=peaks, params=params)
    values = {}
    for m in metrics:
        v = readers[m["name"]].read(run)
        if v is not None and math.isfinite(v):
            values[m["name"]] = {"value": v, "unit": m["unit"]}

    limits = params["limits"]
    compared = {k: {"value": checks[k], "limit": limits[k]}
                for k in limits if k in checks}
    correct = (driver.failed == 0 and len(compared) == len(checks)
               and all(c["value"] <= c["limit"] for c in compared.values()))
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": driver.attempted,
              "failed": driver.failed, "metrics": values, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    # the phase after which the peak was reached, for reading what set it
    result["peak_bytes_after"] = dict(cell.spans.peaks)
    result["checks"] = compared
    return result, driver.summary()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        benchmark = load_json(ROOT, "BENCHMARK.json")
        spec = cell_spec(benchmark, args.workload)
        metrics = metrics_for(benchmark, args.workload, bool(args.trace))
        if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
            raise BenchError(f"the system under test is missing: no "
                             f"{os.path.join(ROOT, 'src', 'repro')}")
        sys.path.insert(0, os.path.join(ROOT, "src"))
        devices = require_chip(spec["entry"]["chips"])
        table = load_json(BENCH_DIR, "peaks.json")["devices"]
        if devices[0].device_kind not in table:
            raise BenchError(f"no peaks for device kind "
                             f"{devices[0].device_kind!r} in bench/peaks.json")
        enable_compile_cache()
    except (BenchError, OSError, KeyError, ValueError) as e:
        print(f"bench: {type(e).__name__}: {e}; nothing was run",
              file=sys.stderr)
        return 2
    result, summary = run_cell(spec, name=args.workload, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      metrics=metrics, devices=devices,
                      peaks=table[devices[0].device_kind],
                      start=PROCESS_START)
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"[bench] {summary}")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
