"""A whole run on the CPU, skipping only the harness's look for a chip,
with the timed path broken underneath: ``correct`` has to come out false.

Each cell is cut to scale 8 and a 2-second window.  The faults are those
the cells can have on one chip, which run one root at a time (no batch to
halve, no exchange between chips): a step that returns its state
unchanged, and an answer altered where it is produced; and the controls —
the references, stopped a level early (BFS) or computed in bfloat16
(SSSP), put in the program's place.
"""
import copy
import time

import jax
import numpy as np
import pytest

from bench import reference as R
from bench import run as harness

CELLS = ("graph500-s22.bfs", "graph500-s21.sssp")


def run_small(cell, seed=2**32 + 3):
    bm = harness.load_json(harness.ROOT, "BENCHMARK.json")
    spec = copy.deepcopy(harness.cell_spec(bm, cell))
    spec["config"]["scale"] = 8
    return harness.run_cell(
        spec, name=cell, seed=seed, seconds=2.0, trace=False,
        metrics=harness.metrics_for(bm, cell, False),
        devices=jax.devices()[:1], peaks={"hbm_bytes_per_s": 819e9},
        start=time.perf_counter())[0]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = run_small(cell)
    assert res["correct"], res
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def _patch_run(monkeypatch, change):
    from repro.core.translator import CompiledGraphProgram

    orig = CompiledGraphProgram.run

    def run(self, roots=None, values=None, **kw):
        out, iters = orig(self, roots=roots, values=values, **kw)
        return change(self, roots, np.asarray(out)), iters

    monkeypatch.setattr(CompiledGraphProgram, "run", run)


@pytest.mark.parametrize("cell", CELLS)
def test_analytics_state_unchanged(monkeypatch, cell):
    _patch_run(monkeypatch, lambda prog, roots, out: np.asarray(
        prog.init_state(roots=roots)[0]))
    assert not run_small(cell)["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_analytics_answer_altered(monkeypatch, cell):
    def alter(prog, roots, out):
        out = out.copy()
        out[roots] = out[roots] + 1     # the root's own level / distance
        return out

    _patch_run(monkeypatch, alter)
    assert not run_small(cell)["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_analytics_control(monkeypatch, cell):
    """The reference in the program's place, one guarantee broken."""
    from bench.drivers import analytics

    orig_setup = analytics.Analytics.setup

    def setup(self):
        orig_setup(self)
        edges = self.edges

        def control(prog, roots, out):
            if self.program == "bfs":
                full = R.bfs_levels(edges, roots)
                short = R.bfs_levels(edges, roots,
                                     max_depth=max(R.depth_of(full) - 1, 0))
                return np.where(short < 0, analytics.UNREACHED, short)
            return R.sssp_rounded(edges, roots, R.round_bf16)

        _patch_run(monkeypatch, control)

    monkeypatch.setattr(analytics.Analytics, "setup", setup)
    assert not run_small(cell)["correct"]
