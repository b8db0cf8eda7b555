"""Aggregate the dry-run sweep JSONs into the §Roofline table (CSV + md)."""
from __future__ import annotations

import glob
import json
import os

HBM_GIB = 16.0


def load_cells(pattern: str = "reports/cell_*.json") -> list[dict]:
    cells = []
    for f in sorted(glob.glob(pattern)):
        with open(f) as fh:
            cells.extend(json.load(fh))
    return cells


def run() -> list[tuple[str, float, str]]:
    rows: list[tuple[str, float, str]] = []
    cells = load_cells()
    if not cells:
        rows.append(("roofline/no_sweep_data_yet", 0.0, "run reports/run_sweep.sh"))
        return rows
    n_ok = sum(c["status"] == "ok" for c in cells)
    n_skip = sum(c["status"] == "skip" for c in cells)
    n_err = sum(c["status"] == "error" for c in cells)
    rows.append(("roofline/cells_ok", 0.0, str(n_ok)))
    rows.append(("roofline/cells_skip", 0.0, str(n_skip)))
    rows.append(("roofline/cells_error", 0.0, str(n_err)))
    for c in cells:
        key = f"roofline/{c['arch']}/{c['shape']}/{c['mesh']}"
        if c["status"] == "skip":
            rows.append((key, 0.0, "skip"))
            continue
        if c["status"] == "error":
            rows.append((key, 0.0, "ERROR " + c.get("error", "")[:60]))
            continue
        fits = c["live_bytes_per_device"] / 2**30
        detail = f"live={fits:.2f}GiB"
        if "roofline" in c:
            r = c["roofline"]
            detail += (f" dom={r['dominant']}"
                       f" c={r['compute_s']:.3f}s m={r['memory_s']:.3f}s"
                       f" l={r['collective_s']:.3f}s"
                       f" useful={r['useful_ratio']:.2f}")
        rows.append((key, c.get("compile_s", 0.0) * 1e6, detail))
    return rows


def markdown_table(cells: list[dict]) -> str:
    """Full §Roofline markdown (used to build EXPERIMENTS.md)."""
    lines = ["| arch | shape | mesh | live GiB | fits | dominant | compute s "
             "| memory s | collective s | MODEL_FLOPS | useful |",
             "|---|---|---|---|---|---|---|---|---|---|---|"]
    for c in cells:
        if c["status"] != "ok":
            lines.append(f"| {c['arch']} | {c['shape']} | {c['mesh']} | — | "
                         f"{c['status']} | | | | | | |")
            continue
        r = c.get("roofline", {})
        live = c["live_bytes_per_device"] / 2**30
        lines.append(
            f"| {c['arch']} | {c['shape']} | {c['mesh']} | {live:.2f} | "
            f"{'✓' if c['fits_16gb'] else '✗'} | {r.get('dominant', '—')} | "
            f"{r.get('compute_s', 0):.4f} | {r.get('memory_s', 0):.4f} | "
            f"{r.get('collective_s', 0):.4f} | "
            f"{r.get('model_flops', 0):.3e} | "
            f"{r.get('useful_ratio', 0):.3f} |")
    return "\n".join(lines)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    for r in run():
        print(",".join(map(str, r)))
