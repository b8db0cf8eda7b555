"""Pallas TPU kernel: segment reduce over sorted CSR edge streams.

The sparse-path companion of :mod:`edge_block`: messages arrive sorted by
destination (CSR order); the kernel streams ``(block_e,)`` edge tiles through
VMEM and accumulates into the full ``(V/128, 128)`` output tile, which stays
VMEM-resident across the sequential TPU grid (output revisiting) — the same
BRAM-accumulator structure the paper's Reduce module uses.

TPU note: the in-kernel scatter-accumulate is expressed with
``jnp``/``.at[]`` ops, which Mosaic lowers via sorted-run segmented scans; on
this CPU host we validate with ``interpret=True`` against ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _kernel(seg_ref, val_ref, out_ref, *, reduce: str, num_segments: int):
    i = pl.program_id(0)
    ident = {"add": 0.0, "min": jnp.inf, "max": -jnp.inf}[reduce]
    if jnp.issubdtype(out_ref.dtype, jnp.integer):
        info = jnp.iinfo(out_ref.dtype)
        ident = {"add": 0, "min": info.max, "max": info.min}[reduce]

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, ident)

    seg = seg_ref[...]                       # (block_e,)
    val = val_ref[...]                       # (block_e,)
    flat = out_ref[...].reshape(-1)
    valid = seg < num_segments               # padded tail has seg == INT_MAX
    safe = jnp.where(valid, seg, 0)
    val = jnp.where(valid, val, jnp.asarray(ident, val.dtype))
    if reduce == "add":
        flat = flat.at[safe].add(jnp.where(valid, val, 0))
    elif reduce == "min":
        flat = flat.at[safe].min(val)
    else:
        flat = flat.at[safe].max(val)
    out_ref[...] = flat.reshape(out_ref.shape)


def segment_reduce(
    seg: jax.Array,           # (E,) int32 sorted segment ids
    val: jax.Array,           # (E,)
    num_segments: int,
    *,
    reduce: str = "add",
    block_e: int = 4096,
    interpret: bool,
) -> jax.Array:
    E = seg.shape[0]
    epad = (-E) % block_e
    if epad:
        seg = jnp.pad(seg, (0, epad), constant_values=jnp.iinfo(jnp.int32).max)
        val = jnp.pad(val, (0, epad))
    vpad = (-num_segments) % LANES
    vr = (num_segments + vpad) // LANES
    grid = (seg.shape[0] // block_e,)

    out = pl.pallas_call(
        functools.partial(_kernel, reduce=reduce, num_segments=num_segments),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_e,), lambda i: (i,)),
            pl.BlockSpec((block_e,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((vr, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((vr, LANES), val.dtype),
        interpret=interpret,
    )(seg, val)
    return out.reshape(-1)[:num_segments]
