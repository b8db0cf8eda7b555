"""Frontier-compacted forward-ELL push module (the paper's frontier FIFO).

The push direction's edge-processing unit, rebuilt around the paper's
pipeline shape: a frontier FIFO feeds *only live edges* into the unit,
instead of a dense sweep guarded per chunk.  Three stages:

1. **Compaction** — the boolean frontier is compacted into a fixed-capacity
   buffer of live forward-ELL *row* indices (a vertex with out-degree ``d``
   owns ``ceil(d/width)`` rows, so hubs compact naturally).  The compaction
   is cumsum + ``searchsorted`` — pure data-indexed gathers, no
   ``lax.cond`` — so it costs O(R) vector work, stays cheap when the
   frontier is tiny, and survives ``vmap`` (a cond would lower to a
   both-branches select there).
2. **Gather + message compute** — the compacted rows' destination/weight
   blocks are gathered from the forward ELL and the per-edge messages
   computed densely over the ``(capacity, width)`` block.  On TPU this
   stage runs as a Pallas kernel (:func:`push_ell_message_block`, the
   edge-processing unit proper — same VMEM vertex-table addressing as
   ``edge_block.py``); elsewhere the XLA form is used.
3. **Scatter-combine** — messages land in the per-vertex table via a
   reduce-matched scatter over destination ids (:func:`segment_combine`,
   ``.at[].min/max/add`` with PAD slots routed to a dummy slot; on a
   v5e ~6 ns per slot).  A full sort by destination
   would allow a sorted segment reduce, but measured on XLA:CPU the sort
   (~300 ns/edge) dwarfs the unsorted segment reduce (~90 ns/edge), so the
   sort is reserved for backends where it pays.

The forward ELL arrives **slot-major** — ``ell_dst``/``ell_wgt`` are
``(width, R)`` — and every stage works on ``(width, capacity)`` blocks:
on a TPU a minor dimension of ``width`` would be tiled to 128 lanes,
padding each row-major ``(capacity, width)`` intermediate 16× (the
``ops.push_ell_reduce`` wrapper takes the host ``ForwardELL``'s
row-major arrays and transposes them).

Work is O(R + capacity·width) per superstep instead of O(E): the runtime
direction policy picks a capacity tier that covers the live row count
``r_f``.  A frontier too wide for the largest tier takes
:func:`full_push_reduce` instead: every row in storage order, chunk by
chunk, with no compaction — one vertex-table gather per *row* and the
same segment-combine per slot (see ``translator._emit_push_ell``).

``kernels.ref.push_scatter_reduce_ref`` remains the numeric oracle: for any
frontier and ``capacity >= r_f`` the compacted result equals the dense
push scatter bit-for-bit (commutative reduces only — the direction-legality
pass guarantees that).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import graph as _G
from ..core import tracing
from .ref import GATHER_OPS, PAD, REDUCE_OPS, _gather_msg, _identity

LANES = 128

# How a push computes a row's slot messages (all bit-identical; the
# translator picks one from the program's analyzed facts):
# ``table`` — weight-free gather: one gather of the masked per-vertex
# message table; ``masked`` — the reduce identity absorbs through the
# gather (SSSP's ``inf + w == inf``): one gather of the pre-masked value
# table, the weight read per slot; ``classic`` — value, degree and
# frontier gathered, identity-masked per slot.
MESSAGE_FORMS = ("table", "masked", "classic")

_SCATTER = {"add": lambda t, i, x: t.at[i].add(x),
            "min": lambda t, i, x: t.at[i].min(x),
            "max": lambda t, i, x: t.at[i].max(x)}


def segment_combine(table: jax.Array, dst_blk: jax.Array, msg: jax.Array,
                    reduce: str) -> jax.Array:
    """⊕-scatter a slot-major ``(W, n)`` message block into ``table``.

    ``table`` is a ``(V+1,)`` per-vertex table; each slot's message lands
    at its destination and PAD slots land in the dummy last slot ``V``,
    which callers slice off.  The push plane's one combine: the compacted
    tiers scatter into a fresh identity table, the full push carries one
    table across its chunks.
    """
    segs = jnp.where(dst_blk != PAD, dst_blk, table.shape[0] - 1)
    return _SCATTER[reduce](table, segs.reshape(-1), msg.reshape(-1))


def compact_rows(live: jax.Array, num_rows: int, capacity: int
                 ) -> tuple[jax.Array, jax.Array]:
    """Compact a boolean row mask into ``capacity`` live row indices.

    Returns ``(sel (capacity,) int32, ok (capacity,) bool)``: ``sel[i]`` is
    the index of the i-th live row (in storage order) and ``ok[i]`` marks
    slots past the live count (or past ``num_rows``) invalid.

    Implemented on the packed bitmap (:func:`repro.core.graph.pack_bits` +
    :func:`repro.core.graph.bitmap_select`): the original inclusive-cumsum
    + ``searchsorted`` form paid a *serial* O(R) cumsum (~8 ns/row on
    XLA:CPU — the dominant fixed cost of a compacted push superstep at
    R ≈ 100k rows); the bitmap form packs the mask elementwise and cumsums
    R/32 word popcounts instead, with the in-word position recovered by a
    five-round popcount binary search.  Selection is bit-for-bit identical
    to the cumsum form.

    Rows beyond ``capacity`` are silently dropped: callers must guarantee
    ``capacity >= live.sum()`` (the runtime policy's tier guard does).
    """
    words = _G.pack_bits(live)
    return _G.bitmap_select(words, capacity, num_items=num_rows)


def _messages_xla(dst_blk, wgt_blk, src_blk, values, degrees, *, gather_fn):
    """Reference message stage over a slot-major ``(W, C)`` block: gather
    vertex state, apply the gather fn."""
    v = values[src_blk]                                  # (C,)
    d = degrees[src_blk]
    shape = dst_blk.shape
    return gather_fn(jnp.broadcast_to(v[None, :], shape),
                     jnp.broadcast_to(wgt_blk, shape).astype(v.dtype),
                     jnp.broadcast_to(d[None, :], shape))


def _message_kernel(dst_ref, wgt_ref, src_ref, val_ref, deg_ref, out_ref,
                    *, gather: str):
    """Pallas message stage: one (block_rows, W) compacted edge block.

    Mirrors ``edge_block.py``'s VMEM addressing: the vertex value/degree
    tables live as (V/128, 128) tiles and each block row gathers its source
    vertex's state once, broadcasting it across the row's edge slots.  Only
    message *construction* runs here — the scatter stays outside (data-
    dependent scatter does not map onto a dense TPU grid; see module
    docstring).
    """
    dst = dst_ref[...]                       # (bR, W) int32, PAD-padded
    wgt = wgt_ref[...]                       # (bR, W)
    src = src_ref[...]                       # (bR, 1) int32 source per row
    table = val_ref[...]                     # (Vr, 128) VMEM vertex cache
    degs = deg_ref[...]                      # (Vr, 128)

    row, lane = src // LANES, src % LANES    # 2-D VMEM gather addressing
    v = table[row, lane]                     # (bR, 1)
    d = degs[row, lane]
    shape = dst.shape
    # masking against PAD happens in the combine stage; this stage is
    # compute-only, exactly the paper's edge-processing unit boundary
    out_ref[...] = _gather_msg(gather, jnp.broadcast_to(v, shape),
                               wgt.astype(v.dtype), jnp.broadcast_to(d, shape))


def push_ell_message_block(dst_blk, wgt_blk, src_blk, values, degrees, *,
                           gather: str, block_rows: int = 128,
                           interpret: bool) -> jax.Array:
    """Pallas dispatch for the message stage over compacted edge blocks."""
    assert gather in GATHER_OPS
    c, w = dst_blk.shape
    v = values.shape[0]
    vpad = (-v) % LANES
    table = jnp.pad(values, (0, vpad)).reshape(-1, LANES)
    degs = jnp.pad(degrees, (0, vpad)).reshape(-1, LANES)
    vr = table.shape[0]
    rpad = (-c) % block_rows
    if rpad:
        dst_blk = jnp.pad(dst_blk, ((0, rpad), (0, 0)),
                          constant_values=int(PAD))
        wgt_blk = jnp.pad(wgt_blk, ((0, rpad), (0, 0)))
        src_blk = jnp.pad(src_blk, (0, rpad))
    rp = dst_blk.shape[0]
    out = pl.pallas_call(
        functools.partial(_message_kernel, gather=gather),
        grid=(rp // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, w), lambda i: (i, 0)),    # dst block
            pl.BlockSpec((block_rows, w), lambda i: (i, 0)),    # weights
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),    # row source
            pl.BlockSpec((vr, LANES), lambda i: (0, 0)),        # vertex cache
            pl.BlockSpec((vr, LANES), lambda i: (0, 0)),        # degree cache
        ],
        out_specs=pl.BlockSpec((block_rows, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, w), values.dtype),
        interpret=interpret,
    )(dst_blk, wgt_blk, src_blk.reshape(-1, 1), table, degs)
    return out[:c]


def push_ell_reduce(
    row_src: jax.Array,     # (max(R,1),) int32 owner vertex per ELL row
    ell_dst: jax.Array,     # (W, max(R,1)) int32 destinations, PAD-padded
    ell_wgt: jax.Array,     # (W, max(R,1)) edge weights
    values: jax.Array,      # (V,) vertex values
    degrees: jax.Array,     # (V,) out-degrees (gather's third argument)
    active: jax.Array,      # (V,) bool frontier
    *,
    num_rows: int,          # logical R (0 for an edgeless graph)
    capacity: int,          # compaction buffer size (static)
    gather_fn: Callable,    # (src_value, weight, degree) -> message
    reduce: str,            # 'add' | 'min' | 'max'
    identity,               # folded reduce identity (scalar, value dtype)
    num_vertices: int,
    dtype,
    gather_module: str | None = None,   # menu name -> Pallas-eligible
    use_pallas: bool = False,
    interpret: bool,
    emit_touched: bool = False,
    stage: str = "push",    # device stage name (see repro.core.tracing)
) -> tuple[jax.Array, jax.Array | None]:
    """Frontier-compacted push superstep reduce.  Returns ``(red, touched)``.

    ``red[v]`` is the ⊕-combine of every live message targeting ``v``
    (``identity`` where none); with ``emit_touched`` a boolean touched mask
    is additionally scattered (a second segment reduce — skip it when the
    apply is an identity fixpoint, which the fusion pass probes before
    binding this layout, and ``touched`` comes back ``None``).

    Correct only while ``capacity >= r_f`` (live row count): the runtime
    direction policy guarantees that by construction, picking a capacity
    tier from ``rows_per_vertex`` before entering this kernel.
    """
    if reduce not in REDUCE_OPS:
        raise ValueError(reduce)
    with tracing.stage(stage + ".compact"):
        live = active[row_src]
        if num_rows == 0:
            live = jnp.zeros_like(live)
    return compacted_push_reduce(
        row_src, ell_dst, ell_wgt, live, values, degrees,
        num_rows=num_rows, capacity=capacity, gather_fn=gather_fn,
        reduce=reduce, identity=identity, num_vertices=num_vertices,
        dtype=dtype, gather_module=gather_module, use_pallas=use_pallas,
        interpret=interpret, emit_touched=emit_touched, stage=stage)


def compacted_push_reduce(
    row_src: jax.Array,     # (R_storage,) int32 owner vertex per row
    ell_dst: jax.Array,     # (W, R_storage) int32 destinations, PAD-padded
    ell_wgt: jax.Array,     # (W, R_storage) edge weights
    live: jax.Array,        # (R_storage,) bool: row is live this superstep
    values: jax.Array,      # (V,) vertex values
    degrees: jax.Array,     # (V,) out-degrees (gather's third argument)
    *,
    num_rows: int,          # rows eligible for selection (<= R_storage)
    capacity: int,          # compaction buffer size (static)
    gather_fn: Callable,
    reduce: str,
    identity,
    num_vertices: int,
    dtype,
    gather_module: str | None = None,
    use_pallas: bool = False,
    interpret: bool,
    emit_touched: bool = False,
    stage: str = "push",    # device stage name (see repro.core.tracing)
) -> tuple[jax.Array, jax.Array | None]:
    """Compaction → gather/message → segment-combine over a live-row mask.

    The core of :func:`push_ell_reduce`, split out so the multi-PE engine
    can hand in a *PE-local* live mask: under ``shard_map`` each PE owns a
    contiguous row interval padded to a common storage length, and its
    ``live`` mask is ``active[row_src] & row_valid`` — row ids stay local
    to the interval (the interval-offset form of compaction), while the
    destination ids in ``ell_dst`` remain global, so the returned table is
    this PE's disjoint partial over the full vertex range.  The three
    steps are the device stages ``<stage>.compact``, ``<stage>.gather``
    and ``<stage>.reduce``.
    """
    if reduce not in REDUCE_OPS:
        raise ValueError(reduce)
    identity = jnp.asarray(identity, dtype)
    with tracing.stage(stage + ".compact"):
        sel, ok = compact_rows(live, num_rows, capacity)
    with tracing.stage(stage + ".gather"):
        dst_blk = jnp.where(ok[None, :], ell_dst[:, sel], PAD)  # (W, cap)
        wgt_blk = ell_wgt[:, sel]
        src_blk = row_src[sel]
        if use_pallas and gather_module is not None:
            msg = push_ell_message_block(dst_blk.T, wgt_blk.T, src_blk,
                                         values, degrees,
                                         gather=gather_module,
                                         interpret=interpret).T
        else:
            msg = _messages_xla(dst_blk, wgt_blk, src_blk, values, degrees,
                                gather_fn=gather_fn)
    with tracing.stage(stage + ".reduce"):
        table = jnp.full((num_vertices + 1,), identity, dtype)
        red = segment_combine(table, dst_blk, msg.astype(dtype),
                              reduce)[:num_vertices]
        touched = None
        if emit_touched:
            touched = segment_combine(
                jnp.zeros((num_vertices + 1,), jnp.int32), dst_blk,
                (dst_blk != PAD).astype(jnp.int32), "max")[:num_vertices] > 0
        return red, touched


def message_table(values: jax.Array, degrees: jax.Array, active: jax.Array,
                  *, gather: str | None, gather_fn: Callable | None,
                  reduce: str, mask_inactive: bool, dtype) -> jax.Array:
    """Per-source message table for weight-free gathers: ``(V+1,)`` with
    messages masked to the reduce identity for inactive sources and the
    identity in the PAD slot (index V).

    A weight-free gather's message depends only on its source, so a sweep
    or a push collapses to ONE gather from this table (per edge slot in
    the pull sweep, per row in :func:`full_push_reduce`) instead of
    separate value/degree/frontier gathers plus per-edge arithmetic —
    bit-identical (same elementwise ops on the same operands, computed
    once per vertex instead of once per edge).
    """
    ones = jnp.ones((values.shape[0],), values.dtype)
    if gather is not None:
        msg = _gather_msg(gather, values, ones, degrees)
    else:
        msg = gather_fn(values, ones, degrees)
    ident = jnp.asarray(_identity(reduce, jnp.dtype(dtype)), dtype)
    if mask_inactive:
        msg = jnp.where(active, msg.astype(dtype), ident)
    else:
        msg = msg.astype(dtype)
    return jnp.concatenate([msg, ident[None]])


def full_push_reduce(
    row_src: jax.Array,     # (max(R,1),) int32 owner vertex per ELL row
    ell_dst: jax.Array,     # (W, max(R,1)) int32 destinations, PAD-padded
    ell_wgt: jax.Array,     # (W, max(R,1)) edge weights
    values: jax.Array,      # (V,) vertex values
    degrees: jax.Array,     # (V,) out-degrees (gather's third argument)
    active: jax.Array,      # (V,) bool frontier
    *,
    num_rows: int,          # logical R (0 for an edgeless graph)
    chunk: int,             # rows per chunk (static)
    form: str,              # one of MESSAGE_FORMS
    gather_fn: Callable,    # (src_value, weight, degree) -> message
    reduce: str,            # 'add' | 'min' | 'max'
    identity,               # folded reduce identity (scalar, value dtype)
    num_vertices: int,
    dtype,
    gather_module: str | None = None,   # menu name (the table's gather)
    mask_inactive: bool = True,
    stage: str = "push",    # device stage name (see repro.core.tracing)
) -> jax.Array:
    """Push over *every* forward-ELL row: the reduced ``(V,)`` table.

    The wide-frontier superstep, where compaction no longer pays: rows
    are taken in storage order, ``chunk`` at a time (no compaction, no
    copy of the layout), and each chunk gathers ONE per-vertex message
    per row from a table built once per call (``form``, see
    :data:`MESSAGE_FORMS`), broadcasts it across the row's slots and
    folds the slots into a ``(V+1,)`` table carried across the chunks
    with :func:`segment_combine`.  Inactive sources contribute the identity.

    The last chunk is clamped to end at row ``R`` (``dynamic_slice``
    needs it in bounds); its rows that an earlier chunk already took
    contribute the identity, so an ``add`` counts each edge once.  For
    min/max and integer add the result equals the dense pull sweep and
    :func:`repro.kernels.ref.push_scatter_reduce_ref` bit for bit.
    Device stages ``<stage>.gather`` (tables and per-row gathers) and
    ``<stage>.reduce`` (the scatter).
    """
    if reduce not in REDUCE_OPS:
        raise ValueError(reduce)
    if form not in MESSAGE_FORMS:
        raise ValueError(form)
    ident = jnp.asarray(identity, dtype)
    width = ell_dst.shape[0]
    rows = max(min(chunk, num_rows), 1)
    with tracing.stage(stage + ".gather"):
        if form == "table":
            src_t = message_table(values, degrees, active,
                                  gather=gather_module, gather_fn=gather_fn,
                                  reduce=reduce, mask_inactive=mask_inactive,
                                  dtype=dtype)
        elif form == "masked":
            src_t = jnp.where(active, values, ident)

    def body(i, table):
        start = jnp.minimum(i * rows, num_rows - rows)
        with tracing.stage(stage + ".gather"):
            fresh = start + jnp.arange(rows) >= i * rows
            rs = jax.lax.dynamic_slice_in_dim(row_src, start, rows)
            dst = jax.lax.dynamic_slice_in_dim(ell_dst, start, rows, axis=1)
            shape = (width, rows)
            if form == "table":
                msg = jnp.where(fresh, src_t[rs], ident)[None, :]
            else:
                wgt = jax.lax.dynamic_slice_in_dim(ell_wgt, start, rows,
                                                   axis=1).astype(dtype)
                deg = jnp.broadcast_to(degrees[rs][None, :], shape)
                if form == "masked":
                    v = jnp.where(fresh, src_t[rs], ident)
                    msg = gather_fn(jnp.broadcast_to(v[None, :], shape), wgt,
                                    deg).astype(dtype)
                else:
                    live = fresh & active[rs] if mask_inactive else fresh
                    v = jnp.broadcast_to(values[rs][None, :], shape)
                    msg = jnp.where(live[None, :],
                                    gather_fn(v, wgt, deg).astype(dtype),
                                    ident)
            msg = jnp.broadcast_to(msg, shape)
        with tracing.stage(stage + ".reduce"):
            return segment_combine(table, dst, msg, reduce)

    table = jnp.full((num_vertices + 1,), ident, dtype)
    trips = -(-num_rows // rows)
    return jax.lax.fori_loop(0, trips, body, table)[:num_vertices]
