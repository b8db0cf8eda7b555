"""Sharding resolver units + multi-device lowering (subprocess)."""
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding.partition import (DECODE_RULES, MeshInfo, TRAIN_RULES,
                                      resolve)

MESH = MeshInfo({"data": 16, "model": 16})
POD_MESH = MeshInfo({"pod": 2, "data": 16, "model": 16})


def test_heads_shard_when_divisible():
    spec = resolve((5120, 32, 128), ("embed", "heads", "head_dim"),
                   MESH, TRAIN_RULES, fsdp=True)
    assert spec == P("data", "model", None)


def test_kv_heads_fallback_when_indivisible():
    # kv=8 against model=16 → kv stays unsharded, FSDP takes embed
    spec = resolve((5120, 8, 128), ("embed", "kv_heads", "head_dim"),
                   MESH, TRAIN_RULES, fsdp=True)
    assert spec == P("data", None, None)


def test_moonshot_kv16_shards():
    spec = resolve((2048, 16, 128), ("embed", "kv_heads", "head_dim"),
                   MESH, TRAIN_RULES, fsdp=True)
    assert spec == P("data", "model", None)


def test_expert_parallel_vs_tp_fallback():
    # moonshot: 64 experts → EP on the expert axis
    spec = resolve((64, 2048, 1408), ("experts", "embed", "expert_ffn"),
                   MESH, TRAIN_RULES, fsdp=True)
    assert spec == P("model", "data", None)
    # grok: 8 experts → TP on the expert-ffn axis instead
    spec = resolve((8, 6144, 32768), ("experts", "embed", "expert_ffn"),
                   MESH, TRAIN_RULES, fsdp=True)
    assert spec == P(None, "data", "model")


def test_vocab_tables_skip_fsdp():
    spec = resolve((131072, 5120), ("vocab", "embed"), MESH, TRAIN_RULES,
                   fsdp=True)
    assert spec == P("model", None)      # no 'data' on the embed dim


def test_whisper_vocab_indivisible_falls_back():
    # 51866 % 16 != 0 → vocab unsharded; embed dim takes model? no rule →
    # stays None (vocab leaf also opts out of fsdp)
    spec = resolve((51866, 1280), ("vocab", "embed"), MESH, TRAIN_RULES,
                   fsdp=True)
    assert spec == P(None, None)


def test_batch_takes_pod_and_data():
    spec = resolve((256, 4096), ("batch", "seq"), POD_MESH, TRAIN_RULES)
    assert spec == P(("pod", "data"), "model")


def test_decode_cache_seq_sharding():
    spec = resolve((128, 32768, 8, 128),
                   ("batch", "cache_seq", "cache_kv", "head_dim"),
                   MESH, DECODE_RULES)
    assert spec == P("data", "model", None, None)


def test_long_context_batch1():
    # batch=1 can't shard; sequence takes the spare axes
    spec = resolve((1, 524288, 4, 256),
                   ("batch", "cache_seq", "cache_kv", "head_dim"),
                   MESH, DECODE_RULES)
    assert spec[0] is None
    assert spec[1] is not None


@pytest.mark.slow
def test_multi_device_lowering(subproc):
    """Small arch lowers + compiles on an 8-device (2,4) mesh; memory and
    collective inventory come out sane."""
    out = subproc("""
import jax, jax.numpy as jnp
from repro.configs import smoke_config
from repro.launch import cells as C
import dataclasses
from repro.core._jax_compat import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
import repro.configs.base as B
cfg = dataclasses.replace(smoke_config("qwen3-8b"),
                          d_model=64, vocab_size=512, microbatch_seqs=4)
shape = B.ShapeConfig("t", 32, 8, "train")
import repro.configs.registry as R
R_SHAPES = dict(B.SHAPES); B.SHAPES["t"] = shape
cell = C.build_cell("qwen3-8b", "t", mesh, cfg_override=cfg)
with jax.set_mesh(mesh):
    comp = jax.jit(cell.fn, donate_argnums=cell.donate).lower(*cell.args).compile()
m = comp.memory_analysis()
assert m.temp_size_in_bytes < 1 << 30
from repro.launch.costing import collective_bytes
coll, counts = collective_bytes(comp.as_text())
assert coll > 0, "expected collectives on a 2x4 mesh"
print("MULTIDEV_OK", counts)
""", devices=8)
    assert "MULTIDEV_OK" in out


@pytest.mark.slow
def test_production_mesh_shapes(subproc):
    out = subproc("""
from repro.launch.mesh import make_production_mesh
m = make_production_mesh()
assert m.devices.shape == (16, 16) and m.axis_names == ("data", "model")
m2 = make_production_mesh(multi_pod=True)
assert m2.devices.shape == (2, 16, 16)
assert m2.axis_names == ("pod", "data", "model")
print("MESH_OK")
""", devices=512)
    assert "MESH_OK" in out


@pytest.mark.slow
def test_checkpoint_reshard_across_meshes(subproc):
    """Elastic restart: save on a (4,2) mesh, restore onto (2,2) — the
    fault-tolerance path after losing half the nodes."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train import checkpoint as ckpt
from repro.core._jax_compat import make_mesh
d = tempfile.mkdtemp()
mesh1 = make_mesh((4, 2), ("data", "model"))
tree = {"w": jax.device_put(jnp.arange(64.0).reshape(8, 8),
                            NamedSharding(mesh1, P("data", "model")))}
ckpt.save(d, 1, tree)
mesh2 = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
shardings = {"w": NamedSharding(mesh2, P("data", "model"))}
restored = ckpt.restore(d, 1, tree, shardings=shardings)
np.testing.assert_array_equal(np.asarray(restored["w"]),
                              np.arange(64.0).reshape(8, 8))
assert restored["w"].sharding.mesh.devices.shape == (2, 2)
print("RESHARD_OK")
""", devices=8)
    assert "RESHARD_OK" in out
