"""Bitmap-frontier pull plane: block-skipping sweep over the reversed ELL.

The pull direction's answer to the compacted push engine (``push_ell.py``):
instead of streaming the *entire* reversed ELL every superstep, the sweep
skips whole edge blocks that provably contribute nothing.  The paper's
JGraph pipeline gets this from its frontier bitmap — an edge block whose
destination block holds no active vertex never enters the pipeline — and
the TPU mapping keeps the same three-phase shape:

1. **Touched summary** (:func:`touched_table`) — a cheap forward pass over
   only the frontier's out-edges (compacted rows of the forward ELL, no
   weights, no messages) marks every vertex with at least one active
   in-neighbor.  Under ``mask_inactive=True`` this is *exactly* the set of
   rows the pull sweep can affect: an untouched row reduces to the
   identity with ``got=False``, so skipping it is bit-exact — including
   for float ``add`` reduces, because skipping never reorders the
   surviving per-row lane reduction (unlike the push direction, which
   must prove commutativity first).
2. **Block liveness + compaction** — the reversed bucketed ELL's rows are
   grouped into fixed blocks (:class:`repro.core.graph.PullBitmapPlan`);
   a block is live iff any of its rows' owners is touched
   (:func:`block_liveness`, one gather + reshape + any — exact).  Live
   block ids compact into a fixed-capacity buffer with the same
   bitmap-native cumsum+searchsorted idiom as ``push_ell.compact_rows``
   (:func:`repro.core.graph.bitmap_select`).  The conservative word-range
   form (:func:`block_range_live` — popcount of the block's destination
   word interval) is exported for the in-kernel Pallas pre-filter and as
   a layout invariant; the XLA emitter uses the exact gather form because
   hub buckets' sparse owner ids make ranges much looser than gathers
   are expensive.
3. **Gathered sweep** (``translator._emit_pull_bitmap``'s
   ``sweep_gathered``, built on this module's :func:`subrow_combine`) —
   the compacted blocks' sub-rows are gathered and reduced *densely* per
   row (identical numerics to ``ref.edge_block_reduce_ref``), so swept
   edges run at the dense engine's ~1 ns/slot gather rate rather than
   the ~60-90 ns/el scatter rate that made edge-granular pull compaction
   a loss.

The Pallas path reuses ``edge_block.edge_block_reduce`` with its
``block_live`` early-out (the whole grid is scheduled; dead blocks write
the identity without gathering) — the FPGA-style "block never enters the
pipeline" form, while the XLA path compacts so the gathered work is
proportional to live blocks.  Both are bit-exact against the dense sweep.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ..core import graph as _G
from .push_ell import compact_rows
from .ref import PAD, ROW_REDUCE, _identity, gather_msg


def touched_table(row_src: jax.Array, ell_dst: jax.Array, active: jax.Array,
                  *, num_rows: int, capacity: int, num_vertices: int
                  ) -> jax.Array:
    """Per-superstep any-active summary: which vertices have a live in-edge.

    A compacted forward scatter of frontier *bits* (no weights, no message
    compute) over the slot-major forward ELL (``ell_dst`` is ``(W, R)``,
    as in ``push_ell``): live rows compact into ``capacity`` slots and
    their destination ids mark a ``(V+1,)`` uint8 table (slot ``V`` is the
    dummy the padded block maps read — it stays 0 because PAD destinations
    write 0 there).  Correct only while ``capacity`` covers the live row
    count; the translator's pre-pass tier guard enforces that, exactly
    like the push engine's capacity tiers.
    """
    live = active[row_src]
    if num_rows == 0:
        live = jnp.zeros_like(live)
    sel, ok = compact_rows(live, num_rows, capacity)
    dst_blk = jnp.where(ok[None, :], ell_dst[:, sel], PAD)
    valid = dst_blk != PAD
    idx = jnp.where(valid, dst_blk, num_vertices).reshape(-1)
    val = valid.reshape(-1).astype(jnp.uint8)
    return jnp.zeros((num_vertices + 1,), jnp.uint8).at[idx].max(val)


def block_liveness(touched_u8: jax.Array, sid_blocked: jax.Array,
                   block_rows: int) -> jax.Array:
    """Exact per-block liveness: any row owner touched.  One gather +
    reshape + any over the block's padded owner ids (pad owner = V reads
    the table's always-zero dummy slot)."""
    t = touched_u8[sid_blocked] != 0
    return t.reshape(-1, block_rows).any(axis=1)


def block_range_live(word_prefix: jax.Array, word_lo: jax.Array,
                     word_hi: jax.Array) -> jax.Array:
    """Conservative word-range liveness: popcount of the block's
    destination word interval is non-zero.

    ``word_prefix`` is the exclusive prefix sum of per-word popcounts
    (``concatenate([[0], cumsum(popcount_words(words))])``).  Never skips
    a live block (the interval covers every owner id by construction —
    pinned by test); may sweep a dead one when the interval also holds
    touched ids the block doesn't own, which on hub buckets with sparse
    owner ids makes it far looser than :func:`block_liveness` — the XLA
    emitter therefore uses the exact form and this one serves as the
    cheap pre-filter shape for in-kernel (Pallas) skipping.
    """
    return (word_prefix[word_hi] - word_prefix[word_lo]) > 0


def word_prefix(words: jax.Array) -> jax.Array:
    """Exclusive popcount prefix over bitmap words (for range queries)."""
    c = _G.popcount_words(words)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(c).astype(jnp.int32)])


def combine_rows(row_red_cat: jax.Array, plan, identity, reduce: str,
                 dtype) -> jax.Array:
    """Scatter-free combine: concatenated per-row reductions → (V,) table.

    ``row_red_cat`` must be at least ``num_rows_total + 1`` long with the
    identity at index ``num_rows_total`` (the no-in-edge dummy
    ``row_map`` points at).  The per-vertex table is one gather; split
    hubs (a vertex owning several max-width rows) fold their extra rows
    in with a tiny scatter over ``plan.dup_rows`` — the only scatter left
    in the dense pull sweep.
    """
    red_v = row_red_cat[plan.row_map].astype(dtype)
    if plan.num_dup:
        extra = row_red_cat[plan.dup_rows].astype(dtype)
        if reduce == "add":
            red_v = red_v.at[plan.dup_vertices].add(extra)
        elif reduce == "min":
            red_v = red_v.at[plan.dup_vertices].min(extra)
        else:
            red_v = red_v.at[plan.dup_vertices].max(extra)
    return red_v


def subrow_combine(sub_red: jax.Array, plan, identity, reduce: str,
                   dtype) -> jax.Array:
    """Flat sub-row reductions → (V,) table, scatter-free.

    The combine cascade of the flat width-8 view: per bucket a static
    ``reshape(W_b/8, R_b)`` reduction over the leading axis folds sub-rows
    back to bucket rows (the view is stripe-major: sub-row ``k`` of row
    ``r`` sits at ``k·R_b + r``, so the reduced axis is the short one and
    the long one stays minor — TPU-friendly; the fold runs in sub-row
    order, so float ``add`` stays deterministic), then
    :func:`combine_rows` maps rows to vertices.
    ``sub_red`` is the ``(num_subrows,)`` per-sub-row reduction (pad
    sub-rows must hold the identity).
    """
    rop = ROW_REDUCE[reduce]
    parts = []
    for (rows_b, f_b), off in zip(plan.bucket_shapes,
                                  plan.bucket_sub_offsets):
        seg = sub_red[off:off + rows_b * f_b]
        if f_b > 1:
            parts.append(rop(seg.reshape(f_b, rows_b), axis=0))
        else:
            parts.append(seg)
    parts.append(jnp.full((1,), identity, dtype))
    return combine_rows(jnp.concatenate(parts).astype(dtype), plan,
                        identity, reduce, dtype)


def message_table(values: jax.Array, degrees: jax.Array, active: jax.Array,
                  *, gather: str | None, gather_fn: Callable | None,
                  reduce: str, mask_inactive: bool, dtype) -> jax.Array:
    """Per-source message table for weight-free gathers: ``(V+1,)`` with
    messages masked to the reduce identity for inactive sources and the
    identity in the PAD slot (index V).

    A weight-free gather's message depends only on its source, so the
    sweep collapses to ONE gather from this table per edge slot instead
    of separate value/degree/frontier gathers plus per-edge arithmetic —
    bit-identical (same elementwise ops on the same operands, computed
    once per vertex instead of once per edge).
    """
    ones = jnp.ones((values.shape[0],), plan_weight_dtype(values))
    if gather is not None:
        msg = gather_msg(gather, values, ones.astype(values.dtype), degrees)
    else:
        msg = gather_fn(values, ones.astype(values.dtype), degrees)
    ident = jnp.asarray(_identity(reduce, jnp.dtype(dtype)), dtype)
    if mask_inactive:
        msg = jnp.where(active, msg.astype(dtype), ident)
    else:
        msg = msg.astype(dtype)
    return jnp.concatenate([msg, ident[None]])


def plan_weight_dtype(values):
    """Weight placeholder dtype for weight-free message tables."""
    return values.dtype if jnp.issubdtype(values.dtype, jnp.floating) \
        else jnp.float32
