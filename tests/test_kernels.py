"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""
import os
import pathlib
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.kernels.ref import (GATHER_OPS, REDUCE_OPS, edge_block_reduce_ref,
                               segment_reduce_ref)

PAD = np.iinfo(np.int32).max


def _mk(V, R, W, dtype, seed=0, pad_frac=0.3):
    rng = np.random.default_rng(seed)
    nbr = rng.integers(0, V, (R, W)).astype(np.int32)
    nbr[rng.random((R, W)) < pad_frac] = PAD
    wgt = rng.uniform(0.5, 2, (R, W)).astype(np.float32)
    if np.issubdtype(np.dtype(dtype), np.integer):
        vals = rng.integers(0, 50, V).astype(dtype)
    else:
        vals = rng.uniform(0, 5, V).astype(dtype)
    deg = rng.integers(1, 9, V).astype(np.int32)
    act = rng.random(V) < 0.6
    return (jnp.asarray(nbr), jnp.asarray(wgt), jnp.asarray(vals),
            jnp.asarray(deg), jnp.asarray(act))


@pytest.mark.parametrize("gather", GATHER_OPS)
@pytest.mark.parametrize("reduce", REDUCE_OPS)
def test_edge_block_all_modules(gather, reduce):
    args = _mk(257, 41, 16, np.float32, seed=1)
    a, ga = kops.edge_block_reduce(*args, gather=gather, reduce=reduce,
                                   block_rows=16)
    b, gb = edge_block_reduce_ref(*args, gather=gather, reduce=reduce)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(ga), np.asarray(gb))


@pytest.mark.parametrize("shape", [(64, 8, 8), (1000, 130, 32),
                                   (513, 7, 64), (128, 1, 8)])
def test_edge_block_shapes(shape):
    V, R, W = shape
    args = _mk(V, R, W, np.float32, seed=V)
    a, _ = kops.edge_block_reduce(*args, gather="add_w", reduce="min",
                                  block_rows=32)
    b, _ = edge_block_reduce_ref(*args, gather="add_w", reduce="min")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_edge_block_dtypes(dtype):
    args = _mk(100, 20, 8, dtype, seed=5)
    a, _ = kops.edge_block_reduce(*args, gather="plus_one", reduce="min",
                                  block_rows=8)
    b, _ = edge_block_reduce_ref(*args, gather="plus_one", reduce="min")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)
    assert a.dtype == b.dtype


def test_edge_block_inactive_masking():
    nbr, wgt, vals, deg, _ = _mk(50, 10, 8, np.float32, seed=9)
    act = jnp.zeros(50, bool)   # nothing active → identity everywhere
    red, got = kops.edge_block_reduce(nbr, wgt, vals, deg, act,
                                      gather="copy", reduce="add",
                                      block_rows=8)
    assert float(jnp.abs(red).sum()) == 0.0
    assert not bool(got.any())


@pytest.mark.parametrize("reduce", REDUCE_OPS)
@pytest.mark.parametrize("E,NS,block", [(1000, 97, 256), (5000, 1, 512),
                                        (4096, 4096, 4096), (77, 10, 128)])
def test_segment_reduce(reduce, E, NS, block):
    rng = np.random.default_rng(E + NS)
    seg = np.sort(rng.integers(0, NS, E)).astype(np.int32)
    val = rng.normal(size=E).astype(np.float32)
    a = kops.segment_reduce(jnp.asarray(seg), jnp.asarray(val), NS,
                            reduce=reduce, block_e=block)
    b = segment_reduce_ref(jnp.asarray(seg), jnp.asarray(val), NS,
                           reduce=reduce)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-4)


def test_segment_reduce_empty_segments():
    seg = jnp.asarray([0, 0, 5, 5, 5], jnp.int32)
    val = jnp.ones(5, jnp.float32)
    out = kops.segment_reduce(seg, val, 8, reduce="add", block_e=4)
    np.testing.assert_allclose(np.asarray(out),
                               [2, 0, 0, 0, 0, 3, 0, 0])


@pytest.mark.parametrize("B,S,K,G,h,bs", [
    (2, 64, 4, 2, 16, 16), (3, 100, 2, 4, 32, 32),
    (1, 512, 8, 4, 128, 128), (2, 33, 1, 1, 8, 8)])
def test_decode_gqa_kernel(B, S, K, G, h, bs):
    from repro.kernels.ref import decode_gqa_ref
    rng = np.random.default_rng(B * S)
    q = jnp.asarray(rng.normal(size=(B, K, G, h)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, S, K, h)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, S, K, h)), jnp.float32)
    length = jnp.asarray(rng.integers(1, S + 1, B), jnp.int32)
    pos = jnp.where(jnp.arange(S)[None] < length[:, None],
                    jnp.arange(S)[None], -1).astype(jnp.int32)
    a = kops.decode_gqa(q, kc, vc, pos, length, block_s=bs)
    b = decode_gqa_ref(q, kc, vc, pos, length)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-5)


def test_decode_gqa_bf16():
    from repro.kernels.ref import decode_gqa_ref
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(2, 4, 2, 16)), jnp.bfloat16)
    kc = jnp.asarray(rng.normal(size=(2, 64, 4, 16)), jnp.bfloat16)
    vc = jnp.asarray(rng.normal(size=(2, 64, 4, 16)), jnp.bfloat16)
    length = jnp.asarray([64, 30], jnp.int32)
    pos = jnp.where(jnp.arange(64)[None] < length[:, None],
                    jnp.arange(64)[None], -1).astype(jnp.int32)
    a = kops.decode_gqa(q, kc, vc, pos, length, block_s=16)
    b = decode_gqa_ref(q, kc, vc, pos, length)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=3e-2)


def test_kernels_package_imports_in_a_fresh_interpreter():
    """``import repro.kernels`` first (before ``repro.core``) must not hit
    the kernels ↔ translator import cycle."""
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(src), os.environ.get("PYTHONPATH", "")]))
    for module in ("repro.kernels", "repro.kernels.pull_bitmap",
                   "repro.kernels.push_ell"):
        proc = subprocess.run([sys.executable, "-c", f"import {module}"],
                              env=env, capture_output=True, text=True,
                              timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
