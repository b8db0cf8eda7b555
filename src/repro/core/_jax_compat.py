"""The mesh/``shard_map`` defaults the translator and the LM substrate share.

* ``make_mesh`` — ``jax.make_mesh`` with ``Auto`` axis types on every axis
  (the translator never relies on explicit axis types);
* ``shard_map_unchecked`` — ``jax.shard_map`` with the varying-manual-axes
  (vma) checker off, for exchanges whose replicated output the checker
  cannot type.
"""
from __future__ import annotations

import jax

__all__ = ["make_mesh", "shard_map_unchecked"]


def make_mesh(axis_shapes, axis_names, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with ``Auto`` axis types on every axis."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=tuple(jax.sharding.AxisType.Auto for _ in axis_names),
        devices=devices)


def shard_map_unchecked(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the vma checker disabled (``check_vma=False``).

    The sharded exchanges end in collectives whose results are genuinely
    replicated (every PE combines the same gathered parts), but the checker
    cannot infer that for the quantized all-gather combine, for the
    per-PE ``lax.switch`` before the push engine's collective, or for a
    ``shard_map`` batched by an outer ``vmap`` (``run_batch``): it rejects
    them with "out_specs require replication which can't be statically
    inferred".
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
