"""Compile rehearsal of the resident engine for one TPU v5e (no chip needed).

The only test file that describes the chip.  The TPU compiler is installed
here and compiles for a chip that is described, not attached: the module
fixture below describes a v5e topology (and skips where it cannot), and
each test lowers the jitted run loop from ``ShapeDtypeStruct``s placed on
one described chip.  Nothing touches libtpu at import: the description
happens inside the fixture, after a test of this file has started.

What the compiles pin:

* the graph layouts are jit *arguments* — the lowered program's text does
  not grow when the graph's edge count doubles, and is far smaller than
  the layouts (a closed-over array would lower to an HLO constant);
* ``memory_analysis()`` counts the layouts among the arguments.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms as alg
from repro.core import dsl
from repro.core import graph as G
from repro.core.scheduler import DirectionPolicy, ScheduleConfig
from repro.core.translator import translate

SCALE = 14                 # 2^14 vertices; the host build takes ~1 s
EDGEFACTORS = (8, 16)      # the same vertices, then twice the edges

PROGRAMS = {"bfs": lambda: dsl.bfs_program(alg.INT_MAX),
            "sssp": dsl.sssp_program}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but can
    never be read back without the chip: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def graphs():
    V = 1 << SCALE
    out = {}
    for ef in EDGEFACTORS:
        src, dst = G.rmat_edges(V, ef * V, seed=3)
        w = np.random.default_rng(3).random(len(src), dtype=np.float32)
        out[ef] = G.from_edge_list(src, dst, num_vertices=V, weights=w)
    return out


def _compile_run_loop(program, g, mode, sharding):
    """Translate on the host, then lower + compile the run loop for the
    described chip from shapes alone."""
    prog = translate(program, g,
                     ScheduleConfig(direction=DirectionPolicy(mode=mode)),
                     aot_compile=False)

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    V = g.num_vertices
    lowered = prog.run_loop.lower(
        jax.tree.map(on_chip, prog.layouts),
        jax.ShapeDtypeStruct((V,), program.value_dtype, sharding=sharding),
        jax.ShapeDtypeStruct((V,), jnp.bool_, sharding=sharding))
    return prog, lowered, lowered.compile()


@pytest.mark.parametrize("mode", ["pull", "push"])
@pytest.mark.parametrize("kind", ["bfs", "sssp"])
def test_resident_loop_compiles_for_v5e(kind, mode, graphs, one_chip,
                                        no_persistent_cache):
    program = PROGRAMS[kind]()
    text_chars, layout_bytes = {}, {}
    for ef, g in graphs.items():
        prog, lowered, compiled = _compile_run_loop(program, g, mode,
                                                    one_chip)
        assert prog.report.backend == "dense_xla"
        assert prog.report.directions == (("pull",) if mode == "pull"
                                           else ("pull", "push"))
        layout_bytes[ef] = sum(a.nbytes
                               for a in jax.tree.leaves(prog.layouts))
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes >= layout_bytes[ef]
        text_chars[ef] = len(lowered.as_text())
    small, big = EDGEFACTORS
    assert layout_bytes[big] > 1.5 * layout_bytes[small]
    # no layout is baked in: the program text stays put (a few shape
    # digits and degree buckets may differ) while the layouts double
    assert text_chars[big] <= 1.02 * text_chars[small]
    assert text_chars[big] < layout_bytes[big] / 10
