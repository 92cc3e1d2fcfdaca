"""JAX names the engines reach through one module.

`shard_map` keeps the positional (f, mesh, in_specs, out_specs) call the
engines use, over the installed `jax.shard_map` (JAX 0.9).
"""

from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
