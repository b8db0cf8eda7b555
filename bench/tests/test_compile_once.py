"""Every seed of a configuration gives the translator the same shapes, so
the programs compiled in one run's set-up serve all later runs from the
compile cache."""
import hashlib

import pytest

from bench.gen import kronecker as K


@pytest.mark.parametrize("program", ["bfs", "sssp"])
def test_run_loop_is_the_same_program_for_every_seed(program):
    from repro.core import dsl
    from repro.core import graph as G
    from repro.core.scheduler import DirectionPolicy, ScheduleConfig
    from repro.core.translator import translate

    texts = []
    for seed in (3, 2**32 + 9):
        e = K.generate(seed, scale=10, edgefactor=16, structure_seed=1)
        g = G.from_edge_list(e.src, e.dst, num_vertices=e.num_vertices,
                             weights=e.weights, sort=False)
        prog = translate(dsl.bfs_program() if program == "bfs"
                         else dsl.sssp_program(), g,
                         ScheduleConfig(direction=DirectionPolicy("auto")))
        v0, a0 = prog.init_state(roots=0)
        text = prog.run_loop.lower(prog.layouts, v0, a0).as_text()
        texts.append(hashlib.sha1(text.encode()).hexdigest())
    assert texts[0] == texts[1]
