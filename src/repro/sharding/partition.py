"""Logical-axis sharding resolver (the communication manager's planning half).

Every parameter/activation dim carries a *logical axis name*; a rule table
maps logical names to mesh-axis candidates in priority order. ``resolve``
assigns the first candidate whose size divides the dim and whose mesh axes
are unused in this spec — the divisibility-aware fallback that lets one rule
table cover all ten assigned architectures (kv=2..20, heads=8..64,
experts=8/64, vocab 51866..262144).

This is the paper's translator idea applied to distribution: a *light-weight*
planner that pattern-matches tensor roles onto a fixed menu of sharding
modules instead of a general auto-sharding search.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# A rule value is a tuple of candidates; each candidate is a mesh-axis name
# or a tuple of mesh-axis names (joint sharding of one dim).
Rules = Mapping[str, Sequence[Any]]

# ---------------------------------------------------------------------------
# Standard rule tables. 'pod' is pure DP and therefore appears only on batch
# (training) — cross-pod traffic is one gradient all-reduce per step.
# ---------------------------------------------------------------------------

TRAIN_RULES: Rules = {
    "batch":      (("pod", "data"), "data"),
    "seq":        ("model",),              # only used when attn can't shard heads
    "embed":      ("fsdp_data",),          # param-only FSDP tag (see below)
    "heads":      ("model",),
    "kv_heads":   ("model",),
    "head_dim":   (),
    "ffn":        ("model",),
    "vocab":      ("model",),
    "experts":    ("model",),
    "expert_ffn": ("model",),
    "layers":     (),
    "state":      (),                      # ssm state / conv taps
    "route_grp":  ("data",),               # MoE routing groups follow batch
}

# Params additionally get FSDP over 'data' on their largest remaining dim.
FSDP_AXIS = "data"

PREFILL_RULES: Rules = dict(TRAIN_RULES) | {
    "batch": (("pod", "data"), "data"),
    "cache_seq": ("model",),
}

# Decode is weight-stationary 2D TP: activations are tiny (B×1×D) and stay
# replicated; weights shard over data×model (embed dim takes 'data' — no
# FSDP gathers per token); the KV cache shards batch over 'data' and seq
# over 'model'.
DECODE_RULES: Rules = dict(TRAIN_RULES) | {
    "batch": (("pod", "data"), "data"),   # cache batch (activations bypass)
    "embed": ("data",),          # 2D TP: second weight dim over 'data'
    "cache_seq": ("model", ("data", "model")),
    "cache_kv": ("model",),
}


@dataclasses.dataclass(frozen=True)
class MeshInfo:
    axis_sizes: dict[str, int]

    @classmethod
    def from_mesh(cls, mesh: Mesh) -> "MeshInfo":
        return cls(dict(zip(mesh.axis_names, mesh.devices.shape)))

    def size(self, axes) -> int:
        if isinstance(axes, str):
            return self.axis_sizes.get(axes, 0)
        return int(np.prod([self.axis_sizes.get(a, 0) for a in axes]))

    def has(self, axes) -> bool:
        if isinstance(axes, str):
            return axes in self.axis_sizes
        return all(a in self.axis_sizes for a in axes)


def resolve(
    shape: Sequence[int],
    logical_axes: Sequence[str | None],
    mesh: Mesh | MeshInfo,
    rules: Rules,
    *,
    fsdp: bool = False,
    min_fsdp_dim: int = 1024,
) -> P:
    """Map each dim's logical axis to mesh axes (priority + divisibility)."""
    info = mesh if isinstance(mesh, MeshInfo) else MeshInfo.from_mesh(mesh)
    assert len(shape) == len(logical_axes), (shape, logical_axes)
    used: set[str] = set()
    spec: list[Any] = []
    for dim, name in zip(shape, logical_axes):
        assigned = None
        for cand in (rules.get(name, ()) if name else ()):
            axes = (cand,) if isinstance(cand, str) else tuple(cand)
            if not info.has(axes):
                continue
            if any(a in used for a in axes):
                continue
            sz = info.size(axes)
            if sz > 1 and dim % sz == 0:
                assigned = cand if isinstance(cand, str) else tuple(axes)
                used.update(axes)
                break
        spec.append(assigned)
    # Vocab tables opt out of FSDP: they are small once vocab-sharded, and
    # FSDP on their embed dim makes the embedding-gradient scatter replicate
    # its (B,S,D) cotangent (measured ~4.8 GiB/device fp32 buffers).
    if fsdp and "vocab" in logical_axes:
        fsdp = False
    if fsdp and info.has(FSDP_AXIS) and FSDP_AXIS not in used:
        # ZeRO-3: shard the largest eligible remaining dim over 'data'
        fs = info.size(FSDP_AXIS)
        best, best_dim = None, min_fsdp_dim - 1
        for i, (dim, cur) in enumerate(zip(shape, spec)):
            if cur is None and dim % fs == 0 and dim > best_dim:
                best, best_dim = i, dim
        if best is not None:
            spec[best] = FSDP_AXIS
    return P(*spec)


def tree_specs(
    shapes: Any,                 # pytree of (shape, dtype, logical_axes)
    mesh: Mesh,
    rules: Rules,
    *,
    fsdp: bool = False,
) -> Any:
    """Map a tree of annotated shapes to a tree of PartitionSpecs."""
    info = MeshInfo.from_mesh(mesh)

    def one(ann):
        return resolve(ann.shape, ann.logical_axes, info, rules, fsdp=fsdp)

    return jax.tree.map(one, shapes, is_leaf=lambda x: hasattr(x, "logical_axes"))


def named_shardings(specs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs, is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Activation sharding constraints (ambient mesh).
#
# Without these, XLA resolves the ZeRO conflict (params FSDP-sharded on
# 'data' along a *contraction* dim vs. activations batch-sharded on 'data')
# by replicating activations — measured 35.6 GiB/device temps on the 314 B
# MoE. Pinning the batch dim at block boundaries forces the cheap resolution
# (gather the weight shard, ZeRO-3 semantics).
# ---------------------------------------------------------------------------


def _ambient_axes() -> dict[str, int]:
    m = jax.sharding.get_abstract_mesh()
    if m is None or not m.axis_names:
        return {}
    return dict(m.shape)


def constrain_batch(x: jax.Array, *, extra: tuple = ()) -> jax.Array:
    """Pin dim 0 to the batch mesh axes (('pod','data') when both exist),
    skipping when the dim isn't divisible (e.g. batch=1 long-context)."""
    axes = _ambient_axes()
    cand = tuple(a for a in ("pod", "data") if a in axes)
    while cand:
        sz = int(np.prod([axes[a] for a in cand]))
        if sz > 1 and x.shape[0] % sz == 0:
            spec = P(cand if len(cand) > 1 else cand[0],
                     *extra, *([None] * (x.ndim - 1 - len(extra))))
            return jax.lax.with_sharding_constraint(x, spec)
        cand = cand[1:]
    return x


def constrain_residual(x: jax.Array) -> jax.Array:
    """Megatron-style sequence parallelism on the residual stream between
    layer cycles: (batch → ('pod','data'), seq → 'model'). Shrinks the
    remat-saved carry 16× (measured 6.4 GiB → 0.4 GiB f32 stacks on the
    314 B MoE); XLA re-gathers the seq dim inside attention only."""
    return constrain(x, [("pod", "data"), "model", None])


def constrain(x: jax.Array, spec_axes: Sequence[Any]) -> jax.Array:
    """Generic divisibility-checked constraint; items may be None, a mesh
    axis name, or a tuple of names."""
    axes = _ambient_axes()
    if not axes:
        return x
    out = []
    used: set[str] = set()
    for dim, cand in zip(x.shape, spec_axes):
        ok = None
        if cand is not None:
            names = (cand,) if isinstance(cand, str) else tuple(cand)
            names = tuple(a for a in names if a in axes and a not in used)
            while names:
                sz = int(np.prod([axes[a] for a in names]))
                if sz > 1 and dim % sz == 0:
                    ok = names[0] if len(names) == 1 else names
                    used.update(names)
                    break
                names = names[1:]
        out.append(ok)
    return jax.lax.with_sharding_constraint(x, P(*out))
