"""Direct coverage for scheduler.plan / plan_for_devices edge cases and the
comm manager's message-quantization round-trip (property-tested when
hypothesis is available, deterministic bounds otherwise)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.comm import CommManager
from repro.errors import DeviceCountError
from repro.core.scheduler import (DirectionPolicy, ScheduleConfig,
                                  choose_backend, plan, plan_for_devices)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# scheduler.plan / plan_for_devices
# ---------------------------------------------------------------------------


def test_plan_more_pipelines_than_edges():
    """Asking for more chunks than edge blocks collapses, never zero-sizes."""
    p = plan(ScheduleConfig(pipelines=16), num_vertices=50, num_edges=100)
    assert p.num_chunks == 1
    assert p.chunk_size == 100
    p = plan(ScheduleConfig(pipelines=8), num_vertices=10_000,
             num_edges=100_000)
    assert p.num_chunks == 8
    assert p.num_chunks * p.chunk_size >= 100_000


def test_plan_single_edge_graph():
    p = plan(ScheduleConfig(pipelines=8), num_vertices=2, num_edges=1)
    assert p.num_chunks == 1 and p.chunk_size == 1


def test_plan_explicit_backend_override_beats_heuristic():
    # avg degree 10 → heuristic says dense; override wins both ways
    p = plan(ScheduleConfig(backend="sparse"), num_vertices=100,
             num_edges=1000)
    assert p.backend == "sparse"
    p = plan(ScheduleConfig(backend="dense"), num_vertices=1000,
             num_edges=1500)   # avg degree 1.5 → heuristic says sparse
    assert p.backend == "dense"
    assert choose_backend(ScheduleConfig(backend="dense"), num_vertices=1,
                          num_edges=1, avg_degree=0.1) == "dense"


def test_plan_elastic_degrade_to_single_device():
    """pes > available devices raises a typed error instead of quietly
    running on fewer PEs; plan_for_devices is the explicit degrade."""
    import jax
    one = jax.devices()[:1]                   # simulate a 1-device pool
    with pytest.raises(DeviceCountError) as err:
        plan(ScheduleConfig(pes=8), num_vertices=10, num_edges=50,
             devices=one)
    assert err.value.requested == 8 and err.value.available == 1
    assert isinstance(err.value, ValueError)
    p = plan_for_devices(ScheduleConfig(pes=8), num_devices=1,
                         num_vertices=10, num_edges=50)
    assert p.mesh is None          # explicit degrade: single device
    assert p.pes == 1
    assert f"direction={p.direction.describe()}" in p.describe()
    assert p.describe().endswith(f"pull_sweep={p.config.pull_sweep}")


def test_plan_builds_pe_mesh_when_devices_allow():
    """With forced host devices (conftest), pes>1 resolves to a real mesh."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    p = plan(ScheduleConfig(pes=2), num_vertices=10_000, num_edges=100_000)
    assert p.mesh is not None and p.pes == 2
    assert p.num_chunks % 2 == 0   # plan owns the PE rounding


def test_plan_for_devices_clamps_pes():
    cfg = ScheduleConfig(pes=8)
    for n in (0, 1):
        p = plan_for_devices(cfg, num_devices=n, num_vertices=10,
                             num_edges=50)
        assert p.mesh is None
        assert p.config.pes == 1


def test_plan_carries_direction_policy():
    pol = DirectionPolicy(mode="push", alpha=7, beta=9)
    p = plan(ScheduleConfig(direction=pol), num_vertices=10, num_edges=50)
    assert p.direction is pol
    assert "push" in p.describe()


def test_schedule_config_validation():
    with pytest.raises(ValueError):
        ScheduleConfig(pipelines=0)
    with pytest.raises(ValueError):
        ScheduleConfig(backend="fpga")
    with pytest.raises(TypeError):
        ScheduleConfig(direction="auto")   # must be a DirectionPolicy


# ---------------------------------------------------------------------------
# comm manager: collective-volume estimate + executed-run accounting
# ---------------------------------------------------------------------------


def test_estimate_collective_bytes_ring_formula():
    """Pin the wire model: ring all-reduce moves 2·(p−1)/p · V per
    participant — at itemsize bytes/element full-precision, at 1
    byte/element quantized (quantized_psum's int8 all-to-all + int8
    all-gather phases execute exactly that), plus two float32 scale
    scalars through the same ring."""
    comm = CommManager()
    assert comm.estimate_collective_bytes(1000, jnp.float32, pes=1) == 0
    vol = comm.estimate_collective_bytes(1000, jnp.float32, pes=4)
    assert vol == int(2 * 3 / 4 * 1000 * 4)            # 6000
    assert comm.stats.collective_bytes_per_superstep == vol
    q = comm.estimate_collective_bytes(1000, jnp.float32, pes=4,
                                       quantized=True)
    assert q == int(2 * 3 / 4 * 1000) + 2 * int(2 * 3 / 4 * 4)  # 1500 + 12
    assert q < vol / 2                          # ~4x saving for float32
    # the saving holds at any PE count (the ring volume scales the same
    # way quantized and not — no gather-of-full-tables blowup)
    assert comm.estimate_collective_bytes(1000, jnp.float32, pes=16,
                                          quantized=True) < \
        comm.estimate_collective_bytes(1000, jnp.float32, pes=16) / 2
    # int32 payload at pes=2: half the buffer crosses each link twice
    assert comm.estimate_collective_bytes(64, jnp.int32, pes=2) == \
        int(2 * 1 / 2 * 64 * 4)


def test_estimate_frontier_bytes_formula():
    """Pin the mask-exchange wire model: packed bitmap = (p−1)·⌈V/32⌉·4
    received per participant (all_gather of word tables), int8 pmax ring
    = 2·(p−1)/p·V — packed wins 8× at p=2, break-even at p=16."""
    comm = CommManager()
    assert comm.estimate_frontier_bytes(1000, pes=1) == 0
    packed2 = comm.estimate_frontier_bytes(1024, pes=2, packed=True)
    assert packed2 == 1 * 32 * 4                       # (p−1)·V/32·4
    assert comm.stats.frontier_bytes_per_superstep == packed2
    int8_2 = comm.estimate_frontier_bytes(1024, pes=2, packed=False)
    assert int8_2 == int(2 * 1 / 2 * 1024)
    assert int8_2 == 8 * packed2                       # the 8× reduction
    # break-even at p=16: (p−1)/8 vs 2·(p−1)/p of V
    p16p = comm.estimate_frontier_bytes(3200, pes=16, packed=True)
    p16i = comm.estimate_frontier_bytes(3200, pes=16, packed=False)
    assert p16p == p16i == 6000


def test_bitmap_or_equals_pmax_of_unpacked():
    """The packed-bitmap OR combine is bit-exact vs the int8 pmax form."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    import jax.numpy as jnp
    from repro.core import graph as G
    from repro.core._jax_compat import make_mesh
    from jax.sharding import PartitionSpec as P
    rng = np.random.default_rng(5)
    V = 101                                          # not a multiple of 32
    masks = rng.random((2, V)) < 0.3
    mesh = make_mesh((2,), ("pe",), devices=jax.devices()[:2])

    def pe_body(m):
        m = m[0]
        words = CommManager.bitmap_or(G.pack_bits(m), "pe", pes=2)
        packed = G.unpack_bits(words, V)
        ref = jax.lax.pmax(m.astype(jnp.int8), "pe") != 0
        return packed[None], ref[None]

    packed, ref = jax.shard_map(pe_body, mesh=mesh, in_specs=(P("pe"),),
                                out_specs=(P("pe"), P("pe")))(
        jnp.asarray(masks))
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(packed[0]),
                                  masks[0] | masks[1])


def test_estimate_does_not_clobber_run_totals():
    """Repeated estimates refresh the per-superstep figure only; the
    executed-run totals accumulate separately via record_collective."""
    comm = CommManager()
    comm.estimate_collective_bytes(1000, jnp.float32, pes=4)
    comm.stats.record_collective(6000, supersteps=3)
    comm.stats.record_collective(6000, supersteps=2)
    comm.estimate_collective_bytes(1000, jnp.float32, pes=4)  # re-estimate
    assert comm.stats.collective_supersteps == 5
    assert comm.stats.collective_bytes_total == 5 * 6000
    rep = comm.report()
    assert rep["collective_bytes_per_superstep"] == 6000
    assert rep["collective_bytes_total"] == 30000
    assert rep["collective_supersteps"] == 5


# ---------------------------------------------------------------------------
# comm manager: quantize/dequantize round-trip
# ---------------------------------------------------------------------------


def _roundtrip_error_bound(x: np.ndarray) -> float:
    # symmetric int8: scale = max|x|/127, rounding error ≤ scale/2
    return max(np.abs(x).max(), 1e-8) / 127.0 * 0.5 + 1e-6


def test_quantize_roundtrip_deterministic():
    for seed in range(5):
        x = np.random.default_rng(seed).normal(scale=10 ** seed, size=64) \
            .astype(np.float32)
        q, s = CommManager.quantize_messages(jnp.asarray(x))
        assert q.dtype == jnp.int8
        deq = np.asarray(CommManager.dequantize_messages(q, s))
        assert np.abs(deq - x).max() <= _roundtrip_error_bound(x)


def test_quantize_all_zero_is_exact():
    q, s = CommManager.quantize_messages(jnp.zeros(16))
    np.testing.assert_array_equal(
        np.asarray(CommManager.dequantize_messages(q, s)), np.zeros(16))


if HAVE_HYPOTHESIS:
    @given(st.lists(st.floats(-1e6, 1e6, width=32), min_size=1, max_size=128))
    @settings(max_examples=50, deadline=None)
    def test_quantize_roundtrip_property(xs):
        """∀x: |dequant(quant(x)) − x| ≤ scale/2 (symmetric int8 bound)."""
        x = np.asarray(xs, np.float32)
        q, s = CommManager.quantize_messages(jnp.asarray(x))
        deq = np.asarray(CommManager.dequantize_messages(q, s))
        assert np.abs(deq - x).max() <= _roundtrip_error_bound(x)
else:
    @pytest.mark.skip(reason="property tests need the hypothesis package")
    def test_quantize_roundtrip_property():
        """Placeholder so the skip is visible in the report."""
