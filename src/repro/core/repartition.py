"""The paper's re-partition operator R_{x->y} as a JAX collective.

DistDL's ``repartition`` generalizes all-to-all to arbitrary Cartesian
tensors: move the sharded dimension of a tensor from dim ``src`` to dim
``dst``. Inside ``shard_map`` this is exactly ``jax.lax.all_to_all`` with
``split_axis=dst, concat_axis=src, tiled=True``:

  local X: [..., n_src/P (dim src), ..., n_dst (dim dst), ...]
  after : [..., n_src   (dim src), ..., n_dst/P (dim dst), ...]

The adjoint (conjugate transpose) of R_{src->dst} is R_{dst->src} — all-to-all
is a permutation of elements across devices, so its transpose is its inverse.
This property is exercised by the round-trip and dot-product tests.

This primitive is used by (a) the distributed FNO block (Alg. 2), (b) the
Ulysses-style sequence-parallel attention, and (c) MoE expert dispatch —
i.e. the paper's core communication pattern is a single reusable op here.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp


def repartition(x: jax.Array, src: int, dst: int, axis_name: str) -> jax.Array:
    """Move the sharded dim from ``src`` to ``dst`` (call inside shard_map).

    ``x`` is the *local* shard: dim ``src`` holds the local chunk (global
    size / P) and dim ``dst`` is fully local. After the call, dim ``src`` is
    global and dim ``dst`` holds the local chunk.
    """
    if src == dst:
        raise ValueError("src and dst dims must differ")
    return jax.lax.all_to_all(
        x, axis_name, split_axis=dst, concat_axis=src, tiled=True
    )


def repartition_t(x: jax.Array, src: int, dst: int, axis_name: str) -> jax.Array:
    """Adjoint of ``repartition(., src, dst)`` = ``repartition(., dst, src)``."""
    return repartition(x, dst, src, axis_name)


def repartition_chunked(
    x: jax.Array,
    src: int,
    dst: int,
    axis_name: str,
    *,
    chunks: int = 2,
    chunk_dim: int = 1,
) -> jax.Array:
    """Double-buffered ``repartition``: split along ``chunk_dim`` (default
    the channel dim of the canonical [b,c,x,y,z,t] layout), issue one
    all-to-all per chunk, concatenate.

    Bit-identical to the blocking call — all-to-all is a pure element
    permutation that never mixes values across ``chunk_dim``, so slicing
    first and permuting per-slice lands every element at the same place
    with the same value. What changes is the schedule: the per-chunk
    collectives are independent of each other, so a latency-hiding
    scheduler can fly chunk i's wire transfer while chunk i+1's producer
    (the local FFT work feeding this repartition) is still computing — the
    MPI-overlap recipe of Totounferoush et al., expressed at the XLA level.

    ``chunks`` is clamped to the ``chunk_dim`` extent; chunk sizes may be
    uneven (no divisibility requirement).
    """
    if chunk_dim in (src, dst):
        raise ValueError(
            f"chunk_dim {chunk_dim} must differ from src={src}/dst={dst}"
        )
    n = min(int(chunks), x.shape[chunk_dim])
    if n <= 1:
        return repartition(x, src, dst, axis_name)
    c = x.shape[chunk_dim]
    bounds = [round(i * c / n) for i in range(n + 1)]
    parts = [
        repartition(
            jax.lax.slice_in_dim(x, lo, hi, axis=chunk_dim),
            src, dst, axis_name,
        )
        for lo, hi in zip(bounds, bounds[1:])
    ]
    return jnp.concatenate(parts, axis=chunk_dim)


Move = Tuple[int, int, str]  # (src_dim, dst_dim, mesh_axis_name)


def repartition_multi(x: jax.Array, moves: Sequence[Move]) -> jax.Array:
    """Apply a sequence of per-mesh-axis moves back-to-back.

    Each move (src, dst, axis) is an independent all-to-all over ONE named
    mesh axis; the sharding of dims held by other mesh axes is untouched.
    Note the pencil FFT in ``repro.core.dfft`` does NOT call this helper —
    its two moves are interleaved with FFT/truncation steps — but performs
    the equivalent per-axis ``repartition`` calls inline; this helper is for
    schedules that re-partition several axes with no compute in between
    (e.g. transposing a whole pencil layout in one shot).
    """
    for src, dst, axis_name in moves:
        x = repartition(x, src, dst, axis_name)
    return x


def repartition_multi_t(x: jax.Array, moves: Sequence[Move]) -> jax.Array:
    """Adjoint of ``repartition_multi``: reversed moves, each transposed."""
    for src, dst, axis_name in reversed(moves):
        x = repartition(x, dst, src, axis_name)
    return x
