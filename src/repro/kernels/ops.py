"""Jit'd dispatch wrappers for the kernel module library.

The translator calls these; each wrapper picks Pallas (interpret on CPU,
compiled on TPU) or the jnp reference, so the same translated program runs
everywhere — the paper's "module library" with a software fallback.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import decode_gqa as _decode_gqa
from . import edge_block as _edge_block
from . import pull_bitmap as _pull_bitmap
from . import push_ell as _push_ell
from . import push_scatter as _push_scatter
from . import segment_sum as _segment_sum
from . import ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("gather", "reduce", "mask_inactive",
                                   "block_rows", "use_kernel"))
def edge_block_reduce(nbr, wgt, values, degrees, active, *, gather, reduce,
                      mask_inactive=True, block_rows=128, use_kernel=True,
                      block_live=None):
    """Dense ELL edge-block reduce (Pallas, or jnp reference).

    ``block_live`` (optional ``(ceil(R/block_rows),)`` mask) engages the
    kernel's per-block early-out: dead blocks write the reduce identity
    without gathering — the bitmap pull plane's block skip, in-kernel.
    Callers must pass a conservative liveness; results are bit-identical
    to the full sweep.  The reference path ignores it (it *is* the full
    sweep, which the early-out must match bit-for-bit).
    """
    if use_kernel:
        return _edge_block.edge_block_reduce(
            nbr, wgt, values, degrees, active,
            gather=gather, reduce=reduce, mask_inactive=mask_inactive,
            block_rows=block_rows, interpret=not _on_tpu(),
            block_live=block_live)
    return _ref.edge_block_reduce_ref(
        nbr, wgt, values, degrees, active,
        gather=gather, reduce=reduce, mask_inactive=mask_inactive)


@partial(jax.jit, static_argnames=("num_rows", "capacity", "num_vertices"))
def touched_frontier(row_src, ell_dst, active, *, num_rows, capacity,
                     num_vertices):
    """Per-superstep any-active summary of the bitmap pull plane: which
    vertices have at least one active in-neighbor.  Returns the
    ``(V+1,)`` uint8 touched table (see ``pull_bitmap.touched_table``);
    callers wanting the packed wire form apply
    ``repro.core.graph.pack_bits(table[:V] != 0)``.  ``ell_dst`` is the
    host :class:`~repro.core.graph.ForwardELL`'s row-major ``(R, W)``
    array (the kernel takes it slot-major)."""
    return _pull_bitmap.touched_table(
        row_src, ell_dst.T, active, num_rows=num_rows, capacity=capacity,
        num_vertices=num_vertices)


@partial(jax.jit, static_argnames=("num_segments", "reduce", "block_e",
                                   "use_kernel"))
def segment_reduce(seg, val, num_segments, *, reduce="add", block_e=4096,
                   use_kernel=True):
    if use_kernel:
        return _segment_sum.segment_reduce(
            seg, val, num_segments, reduce=reduce, block_e=block_e,
            interpret=not _on_tpu())
    return _ref.segment_reduce_ref(seg, val, num_segments, reduce=reduce)


@partial(jax.jit, static_argnames=("gather", "reduce", "mask_inactive",
                                   "num_chunks", "use_kernel"))
def push_scatter_reduce(src, dst, wgt, values, degrees, active, *, gather,
                        reduce, mask_inactive=True, num_chunks=8,
                        use_kernel=True):
    """Push-direction frontier scatter over flat forward-COO arrays.

    ``gather`` is a menu-module name (see ``ref.GATHER_OPS``); the
    translator passes its own traced callable to the kernel module
    directly, this wrapper is the menu-dispatch convenience for tests
    and direct callers.
    """
    if use_kernel:
        dst_c, src_c, wgt_c = _push_scatter.chunk_coo(
            dst, src, wgt, num_chunks=num_chunks)
        ident = _ref._identity(reduce, values.dtype)
        if not mask_inactive:
            active = jnp.ones_like(active)
        return _push_scatter.push_scatter_reduce(
            dst_c, src_c, wgt_c, values, degrees, active,
            gather_fn=partial(_ref.gather_msg, gather), reduce=reduce,
            identity=ident, num_vertices=values.shape[0],
            dtype=values.dtype)
    return _ref.push_scatter_reduce_ref(
        src, dst, wgt, values, degrees, active,
        gather=gather, reduce=reduce, mask_inactive=mask_inactive)


@partial(jax.jit, static_argnames=(
    "num_rows", "capacity", "gather", "reduce", "mask_inactive",
    "use_pallas", "emit_touched"))
def push_ell_reduce(row_src, ell_dst, ell_wgt, values, degrees, active, *,
                    num_rows, capacity, gather, reduce, mask_inactive=True,
                    use_pallas=False, emit_touched=True):
    """Frontier-compacted forward-ELL push reduce (menu-gather dispatch).

    The test/direct-caller convenience over
    ``push_ell.push_ell_reduce``: ``gather`` is a menu-module name, the
    reduce identity is folded here, and ``emit_touched`` defaults on so the
    result tuple matches ``ref.push_scatter_reduce_ref``.  ``ell_dst``
    and ``ell_wgt`` are the host forward ELL's row-major ``(R, W)``
    arrays (the kernel takes them slot-major).  The translator instead
    stages the kernel module directly with its own traced callable and
    capacity tiers.
    """
    if not mask_inactive:
        active = jnp.ones_like(active)
    ident = _ref._identity(reduce, values.dtype)
    return _push_ell.push_ell_reduce(
        row_src, ell_dst.T, ell_wgt.T, values, degrees, active,
        num_rows=num_rows, capacity=capacity,
        gather_fn=partial(_ref.gather_msg, gather), reduce=reduce,
        identity=ident, num_vertices=values.shape[0], dtype=values.dtype,
        gather_module=gather, use_pallas=use_pallas,
        interpret=not _on_tpu(), emit_touched=emit_touched)


@partial(jax.jit, static_argnames=("block_s", "use_kernel"))
def decode_gqa(q, k_cache, v_cache, pos, length, *, block_s=512,
               use_kernel=True):
    """Fused flash-decode GQA attention (see decode_gqa.py)."""
    if use_kernel:
        return _decode_gqa.decode_gqa(q, k_cache, v_cache, pos, length,
                                      block_s=block_s,
                                      interpret=not _on_tpu())
    return _ref.decode_gqa_ref(q, k_cache, v_cache, pos, length)
