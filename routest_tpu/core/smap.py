"""``shard_map`` with this repo's calling convention (positional mesh
and specs, replication checking off unless asked). One import site so
model code never spells the keyword-only form."""

from __future__ import annotations

from jax import shard_map as _shard_map


def shard_map(f, mesh, in_specs, out_specs, check: bool = False):
    return _shard_map(f, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=check)
