"""Paper Table IV: graph atomic-operator extensibility comparison.

The paper counts the programmable operator surface of each accelerator
framework (GraFBoost 4, Foregraph 5, GraphOps 7, GraphSoC 17, FAgraph 25+).
We count ours from the live registry.
"""
from __future__ import annotations

import time

from repro.core.operators import OPERATOR_REGISTRY

PAPER_COUNTS = {
    "GraFBoost'18": 4,
    "Foregraph'17": 5,
    "GraphOps'16": 7,
    "GraphSoc'15": 17,
    "FAgraph (paper)": 25,
}


def run() -> list[tuple[str, float, str]]:
    t0 = time.perf_counter()
    ours = len(OPERATOR_REGISTRY)
    dt = (time.perf_counter() - t0) * 1e6
    rows = [("table_iv/ours_operator_count", dt, str(ours))]
    for name, n in PAPER_COUNTS.items():
        rows.append((f"table_iv/{name.replace(' ', '_')}", 0.0, str(n)))
    assert ours >= 25, "paper claims 25+ operators; registry shrank"
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    for r in run():
        print(",".join(map(str, r)))
