"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

PAD = jnp.iinfo(jnp.int32).max

# Static gather-op menu (paper: translator maps DSL gathers onto pre-built
# module configs; unknown gathers fall back to the sparse jnp backend).
GATHER_OPS = ("copy", "plus_one", "add_w", "mul_w", "div_deg")
REDUCE_OPS = ("add", "min", "max")

# Reduce op → the jnp reduction along one axis (row/slot reductions).
ROW_REDUCE = {"add": jnp.sum, "min": jnp.min, "max": jnp.max}

# Menu gathers whose message ignores the edge weight: their per-edge
# message depends only on the source vertex, so the dense pull sweep can
# precompute one (V,)-table of masked messages and stream edges as a
# single gather (see translator._emit_dense_pull_reduce) — bit-identical
# to per-edge evaluation (same elementwise ops on the same operands).
#
# No production code keys on this tuple anymore: the translator's table
# dispatch reads the analyzer's ``weight_use`` fact (jaxpr liveness of the
# weight argument — repro.core.analysis), which covers arbitrary user
# gathers, not just these three.  The tuple is kept as the pinned
# regression oracle: tests assert the analyzer independently re-derives
# exactly this weight-free set for the menu.
WEIGHT_FREE_GATHERS = ("copy", "plus_one", "div_deg")


def _identity(reduce: str, dtype):
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return {"add": 0, "min": info.max, "max": info.min}[reduce]
    return {"add": 0.0, "min": jnp.inf, "max": -jnp.inf}[reduce]


def gather_msg(gather: str, v, w, d):
    """Evaluate a menu gather op: (src_value, weight, degree) → message.

    The ground truth each pre-built module implements; the translator's
    gather-classification pass probes user callables against these.
    """
    if gather == "copy":
        return v
    if gather == "plus_one":
        return v + 1
    if gather == "add_w":
        return v + w
    if gather == "mul_w":
        return v * w
    if gather == "div_deg":
        return v / jnp.maximum(d, 1).astype(v.dtype)
    raise ValueError(gather)


_gather_msg = gather_msg  # backward-compatible alias (pre-IR translator)


def edge_block_reduce_ref(
    nbr: jax.Array,        # (R, W) int32 neighbor ids, PAD-padded
    wgt: jax.Array,        # (R, W) edge weights
    values: jax.Array,     # (V,) vertex values
    degrees: jax.Array,    # (V,) out-degrees (for div_deg)
    active: jax.Array,     # (V,) bool frontier
    *,
    gather: str,
    reduce: str,
    mask_inactive: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Returns (reduced (R,), any_active (R,) bool)."""
    valid = nbr != PAD
    safe = jnp.where(valid, nbr, 0)
    v = values[safe]
    d = degrees[safe]
    msg = _gather_msg(gather, v, wgt.astype(v.dtype), d)
    live = valid
    if mask_inactive:
        live = live & active[safe]
    ident = jnp.asarray(_identity(reduce, msg.dtype), msg.dtype)
    msg = jnp.where(live, msg, ident)
    red = {"add": jnp.sum, "min": jnp.min, "max": jnp.max}[reduce](msg, axis=1)
    return red, jnp.any(live, axis=1)


def push_scatter_reduce_ref(
    src: jax.Array,        # (E,) int32 source vertex ids (forward COO)
    dst: jax.Array,        # (E,) int32 destination vertex ids
    wgt: jax.Array,        # (E,) edge weights
    values: jax.Array,     # (V,) vertex values
    degrees: jax.Array,    # (V,) out-degrees
    active: jax.Array,     # (V,) bool frontier
    *,
    gather: str,
    reduce: str,
    mask_inactive: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Push-direction oracle: scatter frontier messages over out-edges.

    Dense (all E edges, inactive sources masked to the reduce identity) —
    the allclose target for the chunk-streamed frontier-compacted kernel
    in ``push_scatter.py``.  Returns ``(reduced (V,), touched (V,))``.
    """
    V = values.shape[0]
    v = values[src]
    d = degrees[src]
    msg = gather_msg(gather, v, wgt.astype(v.dtype), d)
    live = active[src] if mask_inactive else jnp.ones_like(src, bool)
    ident = jnp.asarray(_identity(reduce, msg.dtype), msg.dtype)
    msg = jnp.where(live, msg, ident)
    red = jnp.full((V,), ident, msg.dtype)
    if reduce == "add":
        red = red.at[dst].add(jnp.where(live, msg, 0))
    elif reduce == "min":
        red = red.at[dst].min(msg)
    else:
        red = red.at[dst].max(msg)
    got = jnp.zeros((V,), bool).at[dst].max(live)
    return red, got


def segment_reduce_ref(
    seg: jax.Array,        # (E,) sorted int32 segment (dst vertex) ids
    val: jax.Array,        # (E,) messages
    num_segments: int,
    *,
    reduce: str = "add",
) -> jax.Array:
    if reduce == "add":
        return jax.ops.segment_sum(val, seg, num_segments)
    if reduce == "min":
        return jax.ops.segment_min(val, seg, num_segments)
    if reduce == "max":
        return jax.ops.segment_max(val, seg, num_segments)
    raise ValueError(reduce)


def decode_gqa_ref(
    q: jax.Array,          # (B, K, G, H) one query step, grouped heads
    k_cache: jax.Array,    # (B, S, K, H)
    v_cache: jax.Array,    # (B, S, K, H)
    pos: jax.Array,        # (B, S) int32 cached positions (−1 = empty)
    length: jax.Array,     # (B,) valid cache lengths
    *,
    scale: float | None = None,
) -> jax.Array:            # (B, K, G, H)
    B, S, K, H = k_cache.shape
    scale = scale if scale is not None else H ** -0.5
    s = jnp.einsum("bkgh,bskh->bkgs", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    mask = (jnp.arange(S)[None, :] < length[:, None]) & (pos >= 0)
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p, v_cache.astype(jnp.float32))
    return o.astype(q.dtype)
