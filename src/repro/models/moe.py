"""Mixture-of-Experts with gather-only dispatch/combine.

Design (see DESIGN.md §5): routing runs inside *routing groups* aligned with
the batch sharding, so every sort/argsort is over an unsharded axis. Expert
weights shard on the expert axis when ``E % mesh_model == 0`` (moonshot:
64/16 — true EP) and fall back to per-expert tensor parallelism on the ffn
axis otherwise (grok: 8 experts, F=32768/16).

**No scatters in the differentiated path.** XLA's SPMD partitioner handles
large scatters poorly (measured: the combine scatter-add materialized
18 replicated f32 (G,N,D) buffers ≈ 29 GiB on the 314 B config). Instead we
precompute two integer index maps once per routing decision —

    slot→token  (G,E,C):  which token fills expert e's c-th slot
    token→slot  (G,N,k):  (expert, slot, live) for each token's k choices

— and express dispatch and combine as *gathers* through them. The two
gathers are each other's transpose, so a pair of ``jax.custom_vjp``s makes
the backward pass gather-only too. The only scatters left build the s32
maps themselves (K·tokens elements, non-differentiated).

Capacity-overflow tokens are dropped (GShard semantics); the router adds the
standard load-balance auxiliary loss.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

import numpy as np

from ..configs.base import ArchConfig
from ..sharding import partition as ps
from .param import param


def moe_specs(cfg: ArchConfig):
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": param((D, E), ("embed", "experts"), dtype=jnp.float32),
        "w_up": param((E, D, F), ("experts", "embed", "expert_ffn")),
        "w_gate": param((E, D, F), ("experts", "embed", "expert_ffn")),
        "w_down": param((E, F, D), ("experts", "expert_ffn", "embed")),
    }


def _capacity(cfg: ArchConfig, tokens_per_group: int) -> int:
    c = int(tokens_per_group * cfg.moe_topk * cfg.capacity_factor
            / cfg.n_experts) + 1
    if c > 128:
        c = -(-c // 128) * 128
    return min(c, tokens_per_group * min(cfg.moe_topk, cfg.n_experts))


# ---------------------------------------------------------------------------
# index maps (host-of-device int plumbing; built once per routing decision)
# ---------------------------------------------------------------------------


def _routing_maps(idx: jax.Array, E: int, C: int):
    """idx: (G,N,k) top-k expert choices. Returns
    slot_tok (G,E,C) s32 token filling each slot (−1 empty), and token-major
    (e_tok, rank_tok, keep_tok) each (G,N,k)."""
    G, N, k = idx.shape
    flat_e = idx.reshape(G, N * k)
    order = jnp.argsort(flat_e, axis=-1, stable=True)          # (G,Nk)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    tok = order // k
    slot_j = order % k
    seg_start = jax.vmap(
        lambda se: jnp.searchsorted(se, jnp.arange(E), side="left"))(sorted_e)
    ranks = jnp.arange(N * k)[None, :] - jnp.take_along_axis(
        seg_start, sorted_e, axis=-1)                          # (G,Nk)
    keep = ranks < C
    safe_rank = jnp.where(keep, ranks, 0)

    gidx = jnp.arange(G)[:, None]
    # tiny s32 scatters building the maps (not differentiated)
    slot_tok = jnp.zeros((G, E, C), jnp.int32).at[
        gidx, sorted_e, safe_rank].add(
        jnp.where(keep, tok + 1, 0)) - 1                       # −1 = empty

    # token-major views of (rank, keep): invert the sort
    inv = jnp.argsort(order, axis=-1, stable=True)             # (G,Nk)
    rank_tok = jnp.take_along_axis(safe_rank, inv, -1).reshape(G, N, k)
    keep_tok = jnp.take_along_axis(keep, inv, -1).reshape(G, N, k)
    return slot_tok, idx, rank_tok, keep_tok


# ---------------------------------------------------------------------------
# transpose-pair gathers with custom VJPs
# ---------------------------------------------------------------------------


def _g_tokens(x, slot_tok):
    """(G,N,D) → (G,E,C,D): buf[g,e,c] = x[g, slot_tok[g,e,c]] (0 if empty)."""
    gidx = jnp.arange(x.shape[0])[:, None, None]
    live = slot_tok >= 0
    safe = jnp.where(live, slot_tok, 0)
    out = x[gidx, safe]
    return jnp.where(live[..., None], out, 0)


def _g_slots(z, e_tok, rank_tok, keep_tok):
    """(G,E,C,D) → (G,N,k,D): per-token view of its k expert slots."""
    gidx = jnp.arange(z.shape[0])[:, None, None]
    out = z[gidx, e_tok, rank_tok]
    return jnp.where(keep_tok[..., None], out, 0)


@partial(jax.custom_vjp, nondiff_argnums=())
def dispatch(x, slot_tok, e_tok, rank_tok, keep_tok):
    return _g_tokens(x, slot_tok)


def _dispatch_fwd(x, slot_tok, e_tok, rank_tok, keep_tok):
    return _g_tokens(x, slot_tok), (slot_tok, e_tok, rank_tok, keep_tok)


def _dispatch_bwd(res, ct):
    slot_tok, e_tok, rank_tok, keep_tok = res
    ct_x = jnp.sum(_g_slots(ct, e_tok, rank_tok, keep_tok), axis=2)
    return ct_x, None, None, None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@partial(jax.custom_vjp, nondiff_argnums=())
def undispatch(buf, slot_tok, e_tok, rank_tok, keep_tok):
    return _g_slots(buf, e_tok, rank_tok, keep_tok)


def _undispatch_fwd(buf, slot_tok, e_tok, rank_tok, keep_tok):
    return (_g_slots(buf, e_tok, rank_tok, keep_tok),
            (slot_tok, e_tok, rank_tok, keep_tok, buf.shape))


def _undispatch_bwd(res, ct):
    slot_tok, e_tok, rank_tok, keep_tok, buf_shape = res
    # ct: (G,N,k,D) → (G,E,C,D). Each live slot maps to exactly one (n,j):
    # gather ct at (slot_tok, slot_j) — build the j map from rank equality.
    G, N, k, D = ct.shape
    ct_flat = ct.reshape(G, N * k, D)
    # flat position of (token n, choice j) is n*k + j; recover per-slot flat
    # position: token = slot_tok, j found via matching rank — precomputed as
    # a gather: rank_tok[g, n, j] == c  ⇔  slot (e,c) belongs to (n,j).
    # Build slot_flat (G,E,C) = n*k + j via a tiny s32 scatter.
    gidx = jnp.arange(G)[:, None, None]
    flatpos = (jnp.arange(N)[None, :, None] * k
               + jnp.arange(k)[None, None, :])                  # (1,N,k)
    flatpos = jnp.broadcast_to(flatpos, (G, N, k))
    E, C = slot_tok.shape[1], slot_tok.shape[2]
    slot_flat = jnp.zeros((G, E, C), jnp.int32).at[
        gidx, e_tok, rank_tok].add(
        jnp.where(keep_tok, flatpos + 1, 0)) - 1
    live = slot_flat >= 0
    safe = jnp.where(live, slot_flat, 0)
    ct_buf = ct_flat[jnp.arange(G)[:, None, None], safe]
    ct_buf = jnp.where(live[..., None], ct_buf, 0)
    return ct_buf, None, None, None, None


undispatch.defvjp(_undispatch_fwd, _undispatch_bwd)


# ---------------------------------------------------------------------------
# the MoE layer
# ---------------------------------------------------------------------------


def apply_moe(cfg: ArchConfig, p, x):
    """x: (B, S, D); routing groups = batch rows. Returns (y, aux_loss)."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.moe_topk
    C = _capacity(cfg, S)

    logits = jnp.einsum("gnd,de->gne", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                    # (G,N,E)
    w, idx = jax.lax.top_k(probs, k)                           # (G,N,k)
    w = (w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)).astype(x.dtype)

    # load-balance aux loss (Switch): E · Σ_e f_e · p̄_e
    me = jnp.mean(probs, axis=(0, 1))
    fe = jnp.mean(jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32),
                  axis=(0, 1))
    aux = cfg.moe_aux_coef * E * jnp.sum(fe * me)

    maps = _routing_maps(jax.lax.stop_gradient(idx), E, C)
    slot_tok, e_tok, rank_tok, keep_tok = maps

    buf = dispatch(x, slot_tok, e_tok, rank_tok, keep_tok)     # (G,E,C,D)
    buf = ps.constrain(buf, [("pod", "data"), "model", None, None])

    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    g = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"])
    u = jnp.einsum("gecd,edf->gecf", buf, p["w_up"])
    out = jnp.einsum("gecf,efd->gecd", act(g) * u, p["w_down"])
    out = ps.constrain(out, [("pod", "data"), "model", None, None])

    o_tok = undispatch(out, slot_tok, e_tok, rank_tok, keep_tok)  # (G,N,k,D)
    y = jnp.sum(o_tok * w[..., None], axis=2)
    return y, aux


# ---------------------------------------------------------------------------
# shard_map variant (hillclimb lever, DESIGN.md §5 / EXPERIMENTS §Perf):
# expert-parallel combine as a *partial-sum + psum* instead of all-gathering
# the (G,E,C,D) expert outputs over the model axis. Per layer per microbatch
# the combine volume drops from E·C·D (gather) to N·D (psum).
# Requires E % mesh_model == 0 (true EP); otherwise falls back.
# ---------------------------------------------------------------------------


def _moe_local(cfg: ArchConfig, p, x, n_model: int):
    """Per-shard body (single-device semantics; scatters are local here)."""
    G, N, D = x.shape
    E, k = cfg.n_experts, cfg.moe_topk
    C = _capacity(cfg, N)
    e_loc = E // n_model
    my_col = jax.lax.axis_index("model")

    logits = jnp.einsum("gnd,de->gne", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    w = (w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)).astype(x.dtype)

    # aux loss is nonlinear in (fe, me): global means must be taken BEFORE
    # the product (a per-shard aux averaged afterwards is a different loss)
    me_l = jnp.mean(probs, axis=(0, 1))
    fe_l = jnp.mean(jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32),
                    axis=(0, 1))
    me = jax.lax.pmean(jax.lax.pcast(me_l, ("model",), to="varying"), ("data", "model"))
    fe = jax.lax.pmean(jax.lax.pcast(fe_l, ("model",), to="varying"), ("data", "model"))
    aux = cfg.moe_aux_coef * E * jnp.sum(fe * me)

    # keep only this shard's experts: remap to local ids, route everything
    # else to a drop bucket (expert id e_loc), then reuse the token-major
    # gather machinery (_routing_maps / dispatch / undispatch) — identical
    # autodiff structure to the validated single-device path.
    idx = jax.lax.stop_gradient(idx)
    mine = (idx // e_loc) == my_col
    local_idx = jnp.where(mine, idx - my_col * e_loc, e_loc)   # (G,N,k)
    slot_tok, e_tok, rank_tok, keep_tok = _routing_maps(
        local_idx, e_loc + 1, C)
    slot_tok = slot_tok[:, :e_loc]                # drop the overflow bucket
    keep_tok = keep_tok & (e_tok < e_loc)
    e_tok = jnp.where(e_tok < e_loc, e_tok, 0)

    # pcast: x is model-invariant but the dispatch result is model-varying;
    # marking it explicitly makes the custom-VJP cotangent types line up and
    # its transpose (psum over 'model') is exactly the right math
    xv = jax.lax.pcast(x, ("model",), to="varying")
    buf = dispatch(xv, slot_tok, e_tok, rank_tok, keep_tok)    # (G,e_loc,C,D)

    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    g = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"])
    u = jnp.einsum("gecd,edf->gecf", buf, p["w_up"])
    out = jnp.einsum("gecf,efd->gecd", act(g) * u, p["w_down"])

    o_tok = undispatch(out, slot_tok, e_tok, rank_tok, keep_tok)  # (G,N,k,D)
    y_part = jnp.sum(o_tok * w[..., None], axis=2)
    y = jax.lax.psum(y_part, "model")             # N·D combine, not E·C·D
    return y, aux


def apply_moe_shardmap(cfg: ArchConfig, p, x):
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.get_abstract_mesh()
    axes = dict(mesh.shape)
    n_model = axes.get("model", 1)
    batch_axes = tuple(a for a in ("pod", "data") if a in axes)
    bspec = batch_axes if len(batch_axes) > 1 else batch_axes[0]

    p_specs = {
        "router": P(None, None),
        "w_up": P("model", None, None),
        "w_gate": P("model", None, None),
        "w_down": P("model", None, None),
    }
    fn = jax.shard_map(
        lambda p_, x_: _moe_local(cfg, p_, x_, n_model),
        mesh=mesh,
        in_specs=(p_specs, P(bspec, None, None)),
        out_specs=(P(bspec, None, None), P()),
    )
    return fn(p, x)


def apply_moe_auto(cfg: ArchConfig, p, x):
    """Module selection (the paper's translator idea): pick the EP-psum
    shard_map implementation when the mesh allows it, else the gather one."""
    if cfg.moe_impl == "shardmap":
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is not None and mesh.axis_names:
            n_model = dict(mesh.shape).get("model", 1)
            if (n_model > 1 and cfg.n_experts % n_model == 0
                    and x.shape[0] % max(
                        np.prod([dict(mesh.shape).get(a, 1)
                                 for a in ("pod", "data")]), 1) == 0):
                return apply_moe_shardmap(cfg, p, x)
    return apply_moe(cfg, p, x)
