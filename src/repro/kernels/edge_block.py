"""Pallas TPU kernel: gather-apply-reduce over dense ELL edge blocks.

This is the translator's flagship "hardware module" — the TPU adaptation of
the paper's pipelined edge-processing unit:

* The **vertex-value table** (and degree/frontier tables) live as a 2-D
  ``(V/128, 128)`` VMEM-resident tile — the BRAM vertex cache of the paper.
  (Graphs whose tables exceed the VMEM budget are routed to the sparse
  backend by the translator, mirroring the paper's module-selection.)
* **Edge blocks** stream through VMEM as ``(block_rows, W)`` tiles
  (``W ≤ 1024`` by bucket construction) — pipeline streaming.
* The gather/reduce op pair is *static* configuration (a module parameter,
  not data), so each translated program compiles to a specialized kernel —
  exactly how the paper parameterizes pre-built RTL modules.

Row-blocking keeps the MXU/VPU lanes full: ``W`` is a multiple of 8 and the
value table rows are 128-lane aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import GATHER_OPS, REDUCE_OPS, _gather_msg, _identity

PAD = jnp.iinfo(jnp.int32).max
LANES = 128


def _kernel(nbr_ref, wgt_ref, val_ref, deg_ref, act_ref,
            out_ref, any_ref, *, gather: str, reduce: str,
            mask_inactive: bool):
    nbr = nbr_ref[...]                       # (bR, W) int32
    wgt = wgt_ref[...]                       # (bR, W)
    table = val_ref[...]                     # (Vr, 128) VMEM vertex cache
    degs = deg_ref[...]                      # (Vr, 128)
    acts = act_ref[...]                      # (Vr, 128) int8 mask

    valid = nbr != PAD
    safe = jnp.where(valid, nbr, 0)
    row, lane = safe // LANES, safe % LANES  # 2-D VMEM gather addressing
    v = table[row, lane]
    d = degs[row, lane]
    a = acts[row, lane] != 0

    msg = _gather_msg(gather, v, wgt.astype(v.dtype), d)
    live = valid & a if mask_inactive else valid
    ident = jnp.asarray(_identity(reduce, msg.dtype), msg.dtype)
    msg = jnp.where(live, msg, ident)
    red = {"add": jnp.sum, "min": jnp.min, "max": jnp.max}[reduce](msg, axis=1)
    out_ref[...] = red
    any_ref[...] = jnp.any(live, axis=1).astype(jnp.int8)


def _kernel_skip(live_ref, nbr_ref, wgt_ref, val_ref, deg_ref, act_ref,
                 out_ref, any_ref, *, gather: str, reduce: str,
                 mask_inactive: bool):
    """The per-block early-out variant: a dead edge block never gathers.

    ``live_ref`` holds this grid block's precomputed liveness flag (the
    bitmap pull plane's any-active summary).  A dead block writes the
    reduce identity and an empty touched mask without touching the vertex
    cache — the paper's "block never enters the pipeline", expressed as
    predicated execution (both branches write, so the outputs are always
    defined).
    """
    live = live_ref[0] != 0

    @pl.when(live)
    def _():
        _kernel(nbr_ref, wgt_ref, val_ref, deg_ref, act_ref, out_ref,
                any_ref, gather=gather, reduce=reduce,
                mask_inactive=mask_inactive)

    @pl.when(jnp.logical_not(live))
    def _():
        ident = jnp.asarray(_identity(reduce, out_ref.dtype), out_ref.dtype)
        out_ref[...] = jnp.full_like(out_ref[...], ident)
        any_ref[...] = jnp.zeros_like(any_ref[...])


def edge_block_reduce(
    nbr: jax.Array,          # (R, W) int32, PAD-padded
    wgt: jax.Array,          # (R, W)
    values: jax.Array,       # (V,)
    degrees: jax.Array,      # (V,)
    active: jax.Array,       # (V,) bool
    *,
    gather: str,
    reduce: str,
    mask_inactive: bool = True,
    block_rows: int = 128,
    interpret: bool,
    block_live: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Pallas dispatch with padding/unpadding. Returns (reduced, any_live).

    ``block_live`` (optional, ``(ceil(R/block_rows),)`` bool/int8) enables
    the per-block early-out: grid step ``i`` checks ``block_live[i]`` and,
    when dead, writes the reduce identity without gathering from the
    vertex cache.  Callers must pass a *conservative* liveness (never
    False for a block holding a live edge) — the bitmap pull plane's
    touched summary is exact, the word-range popcount form is a valid
    over-approximation.  Results are bit-identical to the full sweep.
    """
    assert gather in GATHER_OPS and reduce in REDUCE_OPS
    R, W = nbr.shape
    V = values.shape[0]

    # pad vertex tables to (Vr, 128) 2-D VMEM tiles
    vpad = (-V) % LANES
    table = jnp.pad(values, (0, vpad)).reshape(-1, LANES)
    degs = jnp.pad(degrees, (0, vpad)).reshape(-1, LANES)
    acts = jnp.pad(active.astype(jnp.int8), (0, vpad)).reshape(-1, LANES)
    vr = table.shape[0]

    # pad rows to a block multiple
    rpad = (-R) % block_rows
    if rpad:
        nbr = jnp.pad(nbr, ((0, rpad), (0, 0)), constant_values=int(PAD))
        wgt = jnp.pad(wgt, ((0, rpad), (0, 0)))
    rp = nbr.shape[0]
    grid = (rp // block_rows,)

    kernel = functools.partial(_kernel, gather=gather, reduce=reduce,
                               mask_inactive=mask_inactive)
    in_specs = [
        pl.BlockSpec((block_rows, W), lambda i: (i, 0)),   # edge block
        pl.BlockSpec((block_rows, W), lambda i: (i, 0)),   # weights
        pl.BlockSpec((vr, LANES), lambda i: (0, 0)),       # vertex cache
        pl.BlockSpec((vr, LANES), lambda i: (0, 0)),       # degree cache
        pl.BlockSpec((vr, LANES), lambda i: (0, 0)),       # frontier
    ]
    args = (nbr, wgt, table, degs, acts)
    if block_live is not None:
        assert block_live.shape[0] == grid[0], \
            f"block_live wants {grid[0]} blocks, got {block_live.shape[0]}"
        kernel = functools.partial(_kernel_skip, gather=gather,
                                   reduce=reduce,
                                   mask_inactive=mask_inactive)
        in_specs = [pl.BlockSpec((1,), lambda i: (i,))] + in_specs
        args = (block_live.astype(jnp.int8),) + args

    out, any_live = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_rows,), lambda i: (i,)),
            pl.BlockSpec((block_rows,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp,), values.dtype),
            jax.ShapeDtypeStruct((rp,), jnp.int8),
        ],
        interpret=interpret,
    )(*args)
    return out[:R], any_live[:R] != 0
