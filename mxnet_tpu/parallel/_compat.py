"""``jax.shard_map`` as every caller here uses it: replication checking
(``check_vma``) off."""
from __future__ import annotations

__all__ = ["shard_map"]


def shard_map(f, mesh, in_specs, out_specs):
    import jax

    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
