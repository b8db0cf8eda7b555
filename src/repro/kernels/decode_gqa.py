"""Pallas TPU kernel: fused flash-decode GQA attention (beyond-paper).

The §Roofline table shows every decode cell is HBM-bound on KV-cache reads,
and the buffer dumps show XLA-CPU (and an unfused TPU path) materializing
score/convert intermediates of the whole cache. This kernel streams the
cache through VMEM in ``(block_s, LANES)``-aligned sequence tiles and keeps
the classic flash running triple (m, l, acc) in f32 registers:

  * grid = (B, K, S/block_s) — batch × kv-head × sequence tiles; the
    sequence axis is the innermost (sequential) grid dim so the running
    softmax state carries across tiles in the output refs (TPU grids
    execute sequentially with output revisiting).
  * per tile: scores (G, block_s) = q_tile @ k_tileᵀ on the MXU,
    masked by cached positions; online max/sum rescale; acc update.
  * VMEM working set per step: q (G,h) + k/v tiles (block_s, h) + acc
    (G,h) ≈ (2·block_s + 2·G)·h·2 B ≪ 16 MB for any assigned config.

Validated against ``ref.decode_gqa_ref`` with interpret=True (tests sweep
shapes/dtypes); ops.py dispatches kernel ↔ ref like the graph modules.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -2.0e38


def _kernel(q_ref, k_ref, v_ref, pos_ref, len_ref,
            o_ref, m_ref, l_ref, *, scale: float, block_s: int):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        o_ref[0, 0] = jnp.zeros_like(o_ref[0, 0])
        m_ref[0, 0] = jnp.full_like(m_ref[0, 0], NEG_INF)
        l_ref[0, 0] = jnp.zeros_like(l_ref[0, 0])

    q = q_ref[0, 0]                     # (G, h)
    k = k_ref[0, 0]                     # (block_s, h)
    v = v_ref[0, 0]
    pos = pos_ref[0]                    # (block_s,)
    length = len_ref[0]                 # scalar: valid cache length

    s = jnp.einsum("gh,sh->gs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    slot = s_idx * block_s + jax.lax.iota(jnp.int32, block_s)
    valid = (slot < length) & (pos >= 0)
    s = jnp.where(valid[None, :], s, NEG_INF)

    m_prev = m_ref[0, 0]                # (G,)
    l_prev = l_ref[0, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * alpha + jnp.sum(p, axis=1)
    acc = o_ref[0, 0] * alpha[:, None] \
        + jnp.einsum("gs,sh->gh", p, v.astype(jnp.float32))
    o_ref[0, 0] = acc
    m_ref[0, 0] = m_new
    l_ref[0, 0] = l_new


def decode_gqa(
    q: jax.Array,          # (B, K, G, h) one query step, grouped heads
    k_cache: jax.Array,    # (B, S, K, h)
    v_cache: jax.Array,    # (B, S, K, h)
    pos: jax.Array,        # (B, S) int32 cached positions (−1 = empty)
    length: jax.Array,     # (B,) valid cache lengths
    *,
    scale: float | None = None,
    block_s: int = 512,
    interpret: bool,
) -> jax.Array:            # (B, K, G, h)
    B, S, K, h = k_cache.shape
    G = q.shape[2]
    scale = scale if scale is not None else h ** -0.5
    spad = (-S) % block_s
    if spad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, spad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, spad), (0, 0), (0, 0)))
        pos = jnp.pad(pos, ((0, 0), (0, spad)), constant_values=-1)
    sp = k_cache.shape[1]
    # (B,S,K,h) → (B,K,S,h) so the kernel's seq tiles are contiguous
    kc = jnp.swapaxes(k_cache, 1, 2)
    vc = jnp.swapaxes(v_cache, 1, 2)

    grid = (B, K, sp // block_s)
    o, m, l = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_s=block_s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, h), lambda b, n, s: (b, n, 0, 0)),
            pl.BlockSpec((1, 1, block_s, h), lambda b, n, s: (b, n, s, 0)),
            pl.BlockSpec((1, 1, block_s, h), lambda b, n, s: (b, n, s, 0)),
            pl.BlockSpec((1, block_s), lambda b, n, s: (b, s)),
            pl.BlockSpec((1,), lambda b, n, s: (b,)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, h), lambda b, n, s: (b, n, 0, 0)),
            pl.BlockSpec((1, 1, G), lambda b, n, s: (b, n, 0)),
            pl.BlockSpec((1, 1, G), lambda b, n, s: (b, n, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, K, G, h), jnp.float32),
            jax.ShapeDtypeStruct((B, K, G), jnp.float32),
            jax.ShapeDtypeStruct((B, K, G), jnp.float32),
        ],
        interpret=interpret,
    )(q, kc, vc, pos, length)
    return (o / jnp.maximum(l[..., None], 1e-38)).astype(q.dtype)
