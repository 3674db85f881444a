"""The relaxed ``jax.shard_map`` shared by the pipeline/moe/ring paths."""
from __future__ import annotations

import jax


def shard_map_compat(fn, mesh, in_specs, out_specs):
    """shard_map with the varying-manual-axes check off (psum-of-partial
    outputs are not 'replicated' in the sense the checker wants, and
    pallas_call outputs carry no annotation at all)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
