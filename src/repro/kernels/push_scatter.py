"""Push-direction edge-processing module: frontier-compacted forward scatter.

The dual of the pull modules (``edge_block``/``segment_sum``): instead of
every vertex gathering over its in-edges, only *active* (frontier) vertices
scatter messages along their out-edges:

    red[dst]  ⊕=  gather(values[src], w, out_deg[src])     for src in frontier

Scatter with data-dependent indices does not map onto a dense Pallas grid
(TPU tiles want regular 128-lane streams), so this module is the
segment-style XLA form the translator's sparse path already uses —
``at[].add/min/max`` over chunk-streamed forward COO — plus the
*frontier compaction* that makes push pay off: each edge chunk is guarded
by a ``lax.cond`` on "any active source in this chunk", so chunks whose
sources are all outside the frontier are skipped entirely (the XLA
analogue of the FPGA's frontier FIFO feeding only live edges into the
pipeline).  With ``pipelines`` chunks this is chunk-granular compaction:
the work actually executed per superstep approaches
``Σ out_deg(frontier)`` instead of ``E`` as the frontier localizes.

``kernels.ref.push_scatter_reduce_ref`` is the pure-jnp oracle (dense, no
chunking, menu-name gathers); :func:`push_scatter_reduce` here is what the
translator stages into the push superstep for the *sparse* backend
(``PushScatterOp.layout == 'coo_chunks'``).  The dense backend uses the
frontier-compacted forward-ELL engine in :mod:`repro.kernels.push_ell`
instead — data-indexed compaction (no ``lax.cond``), capacity tiers, and
a chunked full push for wide frontiers, which is both faster per live
edge and vmap-friendly.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

PAD = jnp.iinfo(jnp.int32).max


def push_scatter_reduce(
    dst_c: jax.Array,      # (C, S) int32 destination ids, PAD-padded
    src_c: jax.Array,      # (C, S) int32 source ids (0 in padded slots)
    wgt_c: jax.Array,      # (C, S) edge weights
    values: jax.Array,     # (V,) vertex values
    degrees: jax.Array,    # (V,) out-degrees (gather's third argument)
    active: jax.Array,     # (V,) bool frontier
    *,
    gather_fn: Callable,   # (src_value, weight, degree) -> message
    reduce: str,           # 'add' | 'min' | 'max'
    identity,              # folded reduce identity (scalar, value dtype)
    num_vertices: int,
    dtype,
    skip_empty_chunks: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Chunk-streamed push scatter. Returns ``(reduced (V,), touched (V,))``.

    Streams the forward-COO edge chunks with ``lax.scan`` (the scheduler's
    ``pipelines`` knob, same as the pull path) and scatters each chunk's
    live messages with ``at[].add/min/max``.  ``skip_empty_chunks`` wraps
    every chunk in ``lax.cond`` keyed on "any active source here" — the
    chunk-granular frontier compaction that skips dead edge blocks.
    """
    identity = jnp.asarray(identity, dtype)

    def do_chunk(red_table, got_table, dst, srcs, ws, live, safe_src):
        v = values[safe_src]
        d = degrees[safe_src]
        msg = gather_fn(v, ws.astype(v.dtype), d)
        msg = jnp.where(live, msg.astype(dtype), identity)
        safe_dst = jnp.where(dst != PAD, dst, 0)
        if reduce == "add":
            red_table = red_table.at[safe_dst].add(jnp.where(live, msg, 0))
        elif reduce == "min":
            red_table = red_table.at[safe_dst].min(msg)
        else:
            red_table = red_table.at[safe_dst].max(msg)
        got_table = got_table.at[safe_dst].max(live)
        return red_table, got_table

    def chunk(carry, xs):
        red_table, got_table = carry
        dst, srcs, ws = xs
        valid = dst != PAD
        safe_src = jnp.where(valid, srcs, 0)
        live = valid & active[safe_src]
        if skip_empty_chunks:
            red_table, got_table = jax.lax.cond(
                jnp.any(live),
                lambda r, g: do_chunk(r, g, dst, srcs, ws, live, safe_src),
                lambda r, g: (r, g),
                red_table, got_table)
        else:
            red_table, got_table = do_chunk(
                red_table, got_table, dst, srcs, ws, live, safe_src)
        return (red_table, got_table), None

    init = (jnp.full((num_vertices,), identity, dtype),
            jnp.zeros((num_vertices,), bool))
    (red_table, got_table), _ = jax.lax.scan(chunk, init, (dst_c, src_c, wgt_c))
    return red_table, got_table


def chunk_coo(dst, src, wgt, *, num_chunks: int):
    """Pad and reshape flat forward-COO arrays into (C, S) edge chunks.

    Padded slots carry ``dst=PAD`` (the validity sentinel) and ``src=0``
    (a safe index); the chunk count is what the scheduler planned
    (``pipelines``), the same streaming granularity as the pull path.
    """
    e = dst.shape[0]
    csize = -(-e // num_chunks)
    pad = num_chunks * csize - e
    dst_c = jnp.pad(dst, (0, pad), constant_values=int(PAD))
    src_c = jnp.pad(src, (0, pad))
    wgt_c = jnp.pad(wgt, (0, pad))
    return (dst_c.reshape(num_chunks, csize),
            src_c.reshape(num_chunks, csize),
            wgt_c.reshape(num_chunks, csize))
