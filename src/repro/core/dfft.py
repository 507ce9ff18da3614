"""Distributed 4-D FFT with frequency truncation (S ∘ F and adjoints).

Implements the operators of the paper's Algorithm 2:

  forward:  S_x F_x R_{x->y} S_{yzt} F_{yzt}
  adjoint:  F_{yzt}^T S_{yzt}^T R_{x->y}^T F_x^T S_x^T

Conventions (matching the serial jnp oracle exactly):
  * data layout X[b, c, x, y, z, t], real input;
  * rFFT along the trailing time dim (real spectrum, keep first m_t bins);
  * full FFT along x, y, z: truncation keeps the m lowest positive and m
    highest (negative) frequencies -> 2m coefficients per dim (the standard
    FNO "corner" modes);
  * S^T is zero-padding back into the middle of the spectrum;
  * F^T here denotes the *inverse* FFT (the paper composes S/F with their
    adjoints such that the round trip is the identity on kept modes; using
    the unitary-scaled inverse keeps the serial and distributed paths
    bit-identical).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.common import compat
from repro.core.repartition import repartition

# Dim indices in the canonical [b, c, x, y, z, t] layout.
BDIM, CDIM, XDIM, YDIM, ZDIM, TDIM = range(6)


# ---------------------------------------------------------------------------
# Truncation S and its adjoint (zero padding).
# ---------------------------------------------------------------------------

def truncate_full(x: jax.Array, axis: int, m: int) -> jax.Array:
    """Keep 2m lowest-|k| modes of a full FFT dim: [:m] and [-m:]."""
    n = x.shape[axis]
    if 2 * m > n:
        raise ValueError(f"2m={2*m} exceeds dim size {n}")
    lo = jax.lax.slice_in_dim(x, 0, m, axis=axis)
    hi = jax.lax.slice_in_dim(x, n - m, n, axis=axis)
    return jnp.concatenate([lo, hi], axis=axis)


def pad_full(x: jax.Array, axis: int, n: int) -> jax.Array:
    """Adjoint of truncate_full: zero-fill the middle back to size n."""
    two_m = x.shape[axis]
    m = two_m // 2
    lo = jax.lax.slice_in_dim(x, 0, m, axis=axis)
    hi = jax.lax.slice_in_dim(x, m, two_m, axis=axis)
    pad_shape = list(x.shape)
    pad_shape[axis] = n - two_m
    zeros = jnp.zeros(pad_shape, dtype=x.dtype)
    return jnp.concatenate([lo, zeros, hi], axis=axis)


def truncate_rfft(x: jax.Array, axis: int, m: int) -> jax.Array:
    """Keep the first m bins of an rFFT dim."""
    return jax.lax.slice_in_dim(x, 0, m, axis=axis)


def pad_rfft(x: jax.Array, axis: int, n_bins: int) -> jax.Array:
    """Adjoint of truncate_rfft: zero-pad the tail back to n_bins."""
    pad_shape = list(x.shape)
    pad_shape[axis] = n_bins - x.shape[axis]
    return jnp.concatenate([x, jnp.zeros(pad_shape, x.dtype)], axis=axis)


# ---------------------------------------------------------------------------
# Serial oracle: S ∘ F over all four dims, each dim truncated right after
# its own transform (truncation along one dim commutes with the FFT along
# another, so later FFTs run on already-truncated tensors).
# ---------------------------------------------------------------------------

def serial_forward(
    x: jax.Array, modes: Sequence[int], *, trunc_x: bool = True
) -> jax.Array:
    """rFFT over t, then FFTs over z, y and x, each followed by its own S.

    x: real [b,c,nx,ny,nz,nt] -> complex [b,c,2mx,2my,2mz,mt]. Equal to
    rfftn over all four dims followed by corner truncation (XLA only
    lowers FFTs of rank <= 3, so the transform is composed per dim).

    ``trunc_x=False`` skips the final S_x, leaving x at full size nx — the
    fused Pallas kernel truncates x itself, as on the pencil path.
    """
    mx, my, mz, mt = modes
    xf = truncate_rfft(jnp.fft.rfft(x.astype(jnp.float32), axis=TDIM), TDIM, mt)
    xf = truncate_full(jnp.fft.fft(xf, axis=ZDIM), ZDIM, mz)
    xf = truncate_full(jnp.fft.fft(xf, axis=YDIM), YDIM, my)
    xf = jnp.fft.fft(xf, axis=XDIM)
    return truncate_full(xf, XDIM, mx) if trunc_x else xf


def serial_adjoint(
    xf: jax.Array,
    grid: Sequence[int],
    out_dtype=jnp.float32,
    *,
    pad_x: bool = True,
) -> jax.Array:
    """Mirror of ``serial_forward``: each S^T right before its own inverse
    FFT (x, y, z, then the irFFT over t); grid is the real-space
    (nx,ny,nz,nt). The per-dim 1/n factors multiply to irfftn's.

    ``pad_x=False`` means x arrives full-size (the fused kernel zero-filled
    S_x^T in-kernel).
    """
    nx, ny, nz, nt = grid
    xf = jnp.fft.ifft(pad_full(xf, XDIM, nx) if pad_x else xf, axis=XDIM)
    xf = jnp.fft.ifft(pad_full(xf, YDIM, ny), axis=YDIM)
    xf = jnp.fft.ifft(pad_full(xf, ZDIM, nz), axis=ZDIM)
    y = jnp.fft.irfft(pad_rfft(xf, TDIM, nt // 2 + 1), n=nt, axis=TDIM)
    return y.astype(out_dtype)


# ---------------------------------------------------------------------------
# Communication/compute overlap: chunk the channel extent so each chunk's
# repartition (all-to-all) is an independent collective that a latency-
# hiding scheduler can fly while the next chunk's local FFTs compute.
# Every op in the distributed pipelines (FFTs over spatial/time dims,
# truncate/pad slices, all-to-alls) treats the channel dim as a pure batch
# dim, so running the WHOLE pipeline per channel-slice and concatenating
# is bit-identical to the unchunked call — verified by the bit-identity
# check in tests/distributed_checks.py.
# ---------------------------------------------------------------------------

def _chunk_channels(fn, x: jax.Array, chunks: int) -> jax.Array:
    n = min(int(chunks), x.shape[CDIM])
    if n <= 1:
        return fn(x)
    c = x.shape[CDIM]
    bounds = [round(i * c / n) for i in range(n + 1)]
    parts = [
        fn(jax.lax.slice_in_dim(x, lo, hi, axis=CDIM))
        for lo, hi in zip(bounds, bounds[1:])
    ]
    return jnp.concatenate(parts, axis=CDIM)


# ---------------------------------------------------------------------------
# Distributed path (call inside shard_map; x sharded along XDIM).
# ---------------------------------------------------------------------------

def dist_forward(
    x: jax.Array,
    modes: Sequence[int],
    axis_name: str,
    *,
    trunc_x: bool = True,
    comm_chunks: int = 1,
) -> jax.Array:
    """Paper Alg. 2 forward transform: S_x F_x R_{x->y} S_{yzt} F_{yzt}.

    In: local real [b, c, nx/P, ny, nz, nt].
    Out: local complex [b, c, 2mx, 2my/P, 2mz, mt]
    (``trunc_x=False`` skips the final S_x — the fused Pallas kernel does
    it — leaving the x dim at full size nx).

    Truncation along y/z/t happens BEFORE the repartition — this is the
    paper's communication optimization (~160x less data on the wire than
    re-partitioning the full spectrum as in Grady et al. [31]).

    ``comm_chunks > 1`` runs the pipeline per channel-slice (bit-identical;
    see ``_chunk_channels``) so each slice's all-to-all overlaps the next
    slice's FFTs under a latency-hiding schedule.
    """
    mx, my, mz, mt = modes

    def body(x):
        # F_{yzt}: local FFT over unsharded dims (rFFT on t).
        xf = jnp.fft.rfft(x.astype(jnp.float32), axis=TDIM)
        xf = jnp.fft.fft(xf, axis=YDIM)
        xf = jnp.fft.fft(xf, axis=ZDIM)
        # S_{yzt}
        xf = truncate_full(xf, YDIM, my)
        xf = truncate_full(xf, ZDIM, mz)
        xf = truncate_rfft(xf, TDIM, mt)
        # R_{x->y}
        xf = repartition(xf, src=XDIM, dst=YDIM, axis_name=axis_name)
        # F_x, S_x
        xf = jnp.fft.fft(xf, axis=XDIM)
        if trunc_x:
            xf = truncate_full(xf, XDIM, mx)
        return xf

    return _chunk_channels(body, x, comm_chunks)


def dist_adjoint(
    xf: jax.Array,
    grid: Sequence[int],
    axis_name: str,
    out_dtype=jnp.float32,
    *,
    pad_x: bool = True,
    comm_chunks: int = 1,
) -> jax.Array:
    """Paper Alg. 2 inverse: F_{yzt}^T S_{yzt}^T R^T F_x^T S_x^T.

    In: local complex [b, c, 2mx, 2my/P, 2mz, mt] (or x already full-size
    when ``pad_x=False`` — the fused kernel zero-filled S_x^T in-kernel).
    Out: local real [b, c, nx/P, ny, nz, nt].
    """
    nx, ny, nz, nt = grid

    def body(xf):
        # S_x^T, F_x^T
        if pad_x:
            xf_ = pad_full(xf, XDIM, nx)
        else:
            xf_ = xf
        xf_ = jnp.fft.ifft(xf_, axis=XDIM)
        # R_{x->y}^T = R_{y->x}
        xf_ = repartition(xf_, src=YDIM, dst=XDIM, axis_name=axis_name)
        # S_{yzt}^T, F_{yzt}^T
        xf_ = pad_full(xf_, YDIM, ny)
        xf_ = pad_full(xf_, ZDIM, nz)
        xf_ = pad_rfft(xf_, TDIM, nt // 2 + 1)
        xf_ = jnp.fft.ifft(xf_, axis=YDIM)
        xf_ = jnp.fft.ifft(xf_, axis=ZDIM)
        y = jnp.fft.irfft(xf_, n=nt, axis=TDIM)
        return y.astype(out_dtype)

    return _chunk_channels(body, xf, comm_chunks)


# ---------------------------------------------------------------------------
# BEYOND-PAPER schedule ("eager truncation"): truncate each dim immediately
# after ITS OWN FFT, so later FFTs run on already-truncated tensors.
# Truncation along dim a commutes exactly with an FFT along dim b != a, so
# this is bit-equivalent to the paper's Alg. 2 while cutting FFT flops by
# ~2.4x and the largest spectral intermediate by ~4x (see EXPERIMENTS §Perf).
# Communication is identical (the repartition already moved the truncated
# tensor in Alg. 2).
# ---------------------------------------------------------------------------

def dist_forward_eager(
    x: jax.Array,
    modes: Sequence[int],
    axis_name: str,
    *,
    trunc_x: bool = True,
    comm_chunks: int = 1,
) -> jax.Array:
    """Like dist_forward, with per-dim eager truncation."""
    mx, my, mz, mt = modes

    def body(x):
        xf = jnp.fft.rfft(x.astype(jnp.float32), axis=TDIM)
        xf = truncate_rfft(xf, TDIM, mt)        # 33 -> mt bins before z/y FFTs
        xf = jnp.fft.fft(xf, axis=ZDIM)
        xf = truncate_full(xf, ZDIM, mz)
        xf = jnp.fft.fft(xf, axis=YDIM)
        xf = truncate_full(xf, YDIM, my)
        xf = repartition(xf, src=XDIM, dst=YDIM, axis_name=axis_name)
        xf = jnp.fft.fft(xf, axis=XDIM)
        if trunc_x:
            xf = truncate_full(xf, XDIM, mx)
        return xf

    return _chunk_channels(body, x, comm_chunks)


def dist_adjoint_eager(
    xf: jax.Array,
    grid: Sequence[int],
    axis_name: str,
    out_dtype=jnp.float32,
    *,
    pad_x: bool = True,
    comm_chunks: int = 1,
) -> jax.Array:
    """Adjoint of the eager schedule: inverse FFTs run while the OTHER dims
    are still truncated; each pad happens right before its own iFFT."""
    nx, ny, nz, nt = grid

    def body(xf):
        xf_ = pad_full(xf, XDIM, nx) if pad_x else xf
        xf_ = jnp.fft.ifft(xf_, axis=XDIM)
        xf_ = repartition(xf_, src=YDIM, dst=XDIM, axis_name=axis_name)
        xf_ = pad_full(xf_, YDIM, ny)
        xf_ = jnp.fft.ifft(xf_, axis=YDIM)
        xf_ = pad_full(xf_, ZDIM, nz)
        xf_ = jnp.fft.ifft(xf_, axis=ZDIM)
        xf_ = pad_rfft(xf_, TDIM, nt // 2 + 1)
        y = jnp.fft.irfft(xf_, n=nt, axis=TDIM)
        return y.astype(out_dtype)

    return _chunk_channels(body, xf, comm_chunks)


# ---------------------------------------------------------------------------
# 2-D pencil decomposition (BEYOND-PAPER): input sharded along BOTH x and y
# on a ("mx", "my") mesh. Algorithm 2 shards a single spatial dim, capping
# model parallelism at nx/2mx devices; pencil decomposition lifts that cap
# to (nx/2mx)*(ny/2my) by composing two per-mesh-axis repartitions:
#
#   forward:  S_x F_x R^{mx}_{x->y} S_y F_y R^{my}_{y->z} S_{zt} F_{zt}
#   adjoint:  F_{zt}^T S_{zt}^T R^{my}_{z->y} F_y^T S_y^T R^{mx}_{y->x} F_x^T S_x^T
#
# Each all-to-all moves an already-truncated tensor (the paper's comm
# optimization, applied per mesh axis). Local layout through the forward:
#
#   [b,c, nx/Px, ny/Py, nz,     nt ]   rFFT t, FFT z, truncate z/t
#   [b,c, nx/Px, ny/Py, 2mz,    mt ]   R^{my}: y-shard moves to z
#   [b,c, nx/Px, ny,    2mz/Py, mt ]   FFT y, truncate y
#   [b,c, nx/Px, 2my,   2mz/Py, mt ]   R^{mx}: x-shard moves to y
#   [b,c, nx,    2my/Px,2mz/Py, mt ]   FFT x, truncate x
#   [b,c, 2mx,   2my/Px,2mz/Py, mt ]   spectral weights sharded k_y x k_z
#
# Divisibility: Px | nx, Px | 2my, Py | ny, Py | 2mz.
# ---------------------------------------------------------------------------

def dist_forward_2d(
    x: jax.Array,
    modes: Sequence[int],
    axis_names: Tuple[str, str] = ("mx", "my"),
    *,
    trunc_x: bool = True,
    comm_chunks: int = 1,
) -> jax.Array:
    """Pencil-decomposed forward transform (call inside shard_map).

    In: local real [b, c, nx/Px, ny/Py, nz, nt], sharded x on
    ``axis_names[0]`` and y on ``axis_names[1]``.
    Out: local complex [b, c, 2mx, 2my/Px, 2mz/Py, mt].
    """
    ax_x, ax_y = axis_names
    mx, my, mz, mt = modes

    def body(x):
        # F_{zt}, S_{zt}: both dims are unsharded on every pencil.
        xf = jnp.fft.rfft(x.astype(jnp.float32), axis=TDIM)
        xf = jnp.fft.fft(xf, axis=ZDIM)
        xf = truncate_full(xf, ZDIM, mz)
        xf = truncate_rfft(xf, TDIM, mt)
        # R^{my}_{y->z}: unshard y by sharding the (truncated) z dim.
        xf = repartition(xf, src=YDIM, dst=ZDIM, axis_name=ax_y)
        xf = jnp.fft.fft(xf, axis=YDIM)
        xf = truncate_full(xf, YDIM, my)
        # R^{mx}_{x->y}: unshard x by sharding the (truncated) y dim.
        xf = repartition(xf, src=XDIM, dst=YDIM, axis_name=ax_x)
        xf = jnp.fft.fft(xf, axis=XDIM)
        if trunc_x:
            xf = truncate_full(xf, XDIM, mx)
        return xf

    return _chunk_channels(body, x, comm_chunks)


def dist_adjoint_2d(
    xf: jax.Array,
    grid: Sequence[int],
    axis_names: Tuple[str, str] = ("mx", "my"),
    out_dtype=jnp.float32,
    *,
    pad_x: bool = True,
    comm_chunks: int = 1,
) -> jax.Array:
    """Adjoint of ``dist_forward_2d`` (each R^T is the reverse all-to-all).

    In: local complex [b, c, 2mx, 2my/Px, 2mz/Py, mt].
    Out: local real [b, c, nx/Px, ny/Py, nz, nt].
    """
    ax_x, ax_y = axis_names
    nx, ny, nz, nt = grid

    def body(xf):
        xf_ = pad_full(xf, XDIM, nx) if pad_x else xf
        xf_ = jnp.fft.ifft(xf_, axis=XDIM)
        xf_ = repartition(xf_, src=YDIM, dst=XDIM, axis_name=ax_x)
        xf_ = pad_full(xf_, YDIM, ny)
        xf_ = jnp.fft.ifft(xf_, axis=YDIM)
        xf_ = repartition(xf_, src=ZDIM, dst=YDIM, axis_name=ax_y)
        xf_ = pad_full(xf_, ZDIM, nz)
        xf_ = pad_rfft(xf_, TDIM, nt // 2 + 1)
        xf_ = jnp.fft.ifft(xf_, axis=ZDIM)
        y = jnp.fft.irfft(xf_, n=nt, axis=TDIM)
        return y.astype(out_dtype)

    return _chunk_channels(body, xf, comm_chunks)


def dist_forward_2d_eager(
    x: jax.Array,
    modes: Sequence[int],
    axis_names: Tuple[str, str] = ("mx", "my"),
    *,
    trunc_x: bool = True,
    comm_chunks: int = 1,
) -> jax.Array:
    """2-D pencil forward with per-dim eager truncation: t is truncated
    before the z FFT, so the z FFT runs on an mt-deep tensor (same flop
    saving as the 1-D eager schedule; bit-equivalent to dist_forward_2d)."""
    ax_x, ax_y = axis_names
    mx, my, mz, mt = modes

    def body(x):
        xf = jnp.fft.rfft(x.astype(jnp.float32), axis=TDIM)
        xf = truncate_rfft(xf, TDIM, mt)
        xf = jnp.fft.fft(xf, axis=ZDIM)
        xf = truncate_full(xf, ZDIM, mz)
        xf = repartition(xf, src=YDIM, dst=ZDIM, axis_name=ax_y)
        xf = jnp.fft.fft(xf, axis=YDIM)
        xf = truncate_full(xf, YDIM, my)
        xf = repartition(xf, src=XDIM, dst=YDIM, axis_name=ax_x)
        xf = jnp.fft.fft(xf, axis=XDIM)
        if trunc_x:
            xf = truncate_full(xf, XDIM, mx)
        return xf

    return _chunk_channels(body, x, comm_chunks)


def dist_adjoint_2d_eager(
    xf: jax.Array,
    grid: Sequence[int],
    axis_names: Tuple[str, str] = ("mx", "my"),
    out_dtype=jnp.float32,
    *,
    pad_x: bool = True,
    comm_chunks: int = 1,
) -> jax.Array:
    """Adjoint of the eager 2-D schedule: each pad happens right before its
    own iFFT, so earlier iFFTs run on still-truncated tensors."""
    ax_x, ax_y = axis_names
    nx, ny, nz, nt = grid

    def body(xf):
        xf_ = pad_full(xf, XDIM, nx) if pad_x else xf
        xf_ = jnp.fft.ifft(xf_, axis=XDIM)
        xf_ = repartition(xf_, src=YDIM, dst=XDIM, axis_name=ax_x)
        xf_ = pad_full(xf_, YDIM, ny)
        xf_ = jnp.fft.ifft(xf_, axis=YDIM)
        xf_ = repartition(xf_, src=ZDIM, dst=YDIM, axis_name=ax_y)
        xf_ = pad_full(xf_, ZDIM, nz)
        xf_ = jnp.fft.ifft(xf_, axis=ZDIM)
        xf_ = pad_rfft(xf_, TDIM, nt // 2 + 1)
        y = jnp.fft.irfft(xf_, n=nt, axis=TDIM)
        return y.astype(out_dtype)

    return _chunk_channels(body, xf, comm_chunks)


# ---------------------------------------------------------------------------
# Grady et al. [31] baseline schedule: repartition FIRST, truncate AFTER.
# Communicates the full (untruncated along y/z/t) spectrum — the paper's
# comparison point for the 160x communication reduction.
# ---------------------------------------------------------------------------

def dist_forward_untruncated(
    x: jax.Array,
    modes: Sequence[int],
    axis_name: str,
    *,
    trunc_xzt: bool = True,
    comm_chunks: int = 1,
) -> jax.Array:
    """[31]-style forward: F_{yzt}, R_{x->y} (full tensor!), F_x, then S.

    ``trunc_xzt=False`` leaves x/z/t untruncated for the fused Pallas
    kernel; the sharded y dim is still truncated here (truncate_y_local
    needs the collective, and truncation along y commutes with the
    later in-kernel x/z/t truncation).
    """
    mx, my, mz, mt = modes

    def body(x):
        xf = jnp.fft.rfft(x.astype(jnp.float32), axis=TDIM)
        xf = jnp.fft.fft(xf, axis=YDIM)
        xf = jnp.fft.fft(xf, axis=ZDIM)
        xf = repartition(xf, src=XDIM, dst=YDIM, axis_name=axis_name)
        xf = jnp.fft.fft(xf, axis=XDIM)
        # Truncate only now (after communication).
        if trunc_xzt:
            xf = truncate_full(xf, XDIM, mx)   # before the y gather: less data
            xf = truncate_y_local(xf, my, axis_name)
            xf = truncate_full(xf, ZDIM, mz)
            xf = truncate_rfft(xf, TDIM, mt)
        else:
            xf = truncate_y_local(xf, my, axis_name)
        return xf

    return _chunk_channels(body, x, comm_chunks)


def truncate_y_local(xf: jax.Array, my: int, axis_name: str) -> jax.Array:
    """Truncate the (sharded) y dim to its local slice of the kept modes.

    With y sharded P-ways, the kept modes [:my] + [-my:] live on the first
    and last shards. Each shard materializes the full kept-y range via
    an all_gather then slices its local part — simple and only used by the
    [31] baseline path (which is deliberately communication-heavy).
    """
    full = jax.lax.all_gather(xf, axis_name, axis=YDIM, tiled=True)
    kept = truncate_full(full, YDIM, my)
    p = compat.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    local = kept.shape[YDIM] // p
    return jax.lax.dynamic_slice_in_dim(kept, idx * local, local, axis=YDIM)


def pad_y_local(xf: jax.Array, ny: int, axis_name: str) -> jax.Array:
    """Adjoint-ish inverse of truncate_y_local for the [31] baseline path."""
    full_kept = jax.lax.all_gather(xf, axis_name, axis=YDIM, tiled=True)
    padded = pad_full(full_kept, YDIM, ny)
    p = compat.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    local = ny // p
    return jax.lax.dynamic_slice_in_dim(padded, idx * local, local, axis=YDIM)


def dist_adjoint_untruncated(
    xf: jax.Array,
    grid: Sequence[int],
    axis_name: str,
    out_dtype=jnp.float32,
    *,
    pad_xzt: bool = True,
    comm_chunks: int = 1,
) -> jax.Array:
    """[31]-style inverse: pad everything first, repartition the FULL tensor.

    ``pad_xzt=False`` means x/z/t arrive already full-size (the fused
    kernel zero-filled them); only the sharded y dim still needs its
    collective pad.
    """
    nx, ny, nz, nt = grid

    def body(xf):
        if pad_xzt:
            xf_ = pad_full(xf, XDIM, nx)
            xf_ = pad_y_local(xf_, ny, axis_name)
            xf_ = pad_full(xf_, ZDIM, nz)
            xf_ = pad_rfft(xf_, TDIM, nt // 2 + 1)
        else:
            xf_ = pad_y_local(xf, ny, axis_name)
        xf_ = jnp.fft.ifft(xf_, axis=XDIM)
        xf_ = repartition(xf_, src=YDIM, dst=XDIM, axis_name=axis_name)
        xf_ = jnp.fft.ifft(xf_, axis=YDIM)
        xf_ = jnp.fft.ifft(xf_, axis=ZDIM)
        y = jnp.fft.irfft(xf_, n=nt, axis=TDIM)
        return y.astype(out_dtype)

    return _chunk_channels(body, xf, comm_chunks)
