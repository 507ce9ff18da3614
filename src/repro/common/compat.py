"""The few jax API calls whose spelling has drifted, in one place.

Every call site goes through these helpers instead of touching ``jax.*``
directly, so a future drift is handled here alone.
"""
from __future__ import annotations

from typing import Sequence

import jax


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off.

    The distributed FFT paths intentionally return shards whose replication
    cannot be inferred statically, so the repo always disables the check.
    """
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def axis_size(axis_name: str):
    """Size of a named mesh axis, from inside shard_map."""
    return jax.lax.axis_size(axis_name)


def make_global_array(shape, sharding, fetch):
    """Assemble a globally-sharded ``jax.Array`` from per-shard host reads.

    ``fetch(index)`` receives a normalized tuple of ``slice`` objects (one
    per dim, concrete start/stop) and must return the numpy block for that
    shard. It is called once per UNIQUE shard index — replicated shards
    (e.g. across a data-parallel axis that doesn't split the dim) reuse the
    first fetch — which is what keeps per-process reads proportional to the
    process's share of the data, not the global array.
    """
    shape = tuple(shape)
    memo = {}

    def cb(index):
        norm = tuple(sl.indices(dim) for sl, dim in zip(index, shape))
        if norm not in memo:
            memo[norm] = fetch(
                tuple(slice(a, b, c) for a, b, c in norm)
            )
        return memo[norm]

    return jax.make_array_from_callback(shape, sharding, cb)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with explicit Auto axis types."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )
