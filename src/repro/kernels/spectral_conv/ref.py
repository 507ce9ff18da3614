"""Pure-jnp oracle for the spectral convolution (per-mode channel mixing).

Y[b, co, K] = sum_ci X[b, ci, K] * W[ci, co, K]   (complex), where K ranges
over the kept Fourier modes (possibly multi-dimensional, flattened or not).
This is the FLOP hot spot of the paper's FNO block (Alg. 2 line 2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def spectral_apply_ref(xf: jax.Array, w: jax.Array) -> jax.Array:
    """xf: [b, ci, *modes] complex; w: [ci, co, *modes] complex.

    Returns [b, co, *modes] complex. Element-wise over mode dims, contracted
    over ci (paper's einsum Y_{b c_o k...} = X_{b c_i k...} W_{c_i c_o k...}).
    """
    n_modes = xf.ndim - 2
    mode_axes = "".join(chr(ord("s") + i) for i in range(n_modes))
    eq = f"bi{mode_axes},io{mode_axes}->bo{mode_axes}"
    # full float32 (a TPU's default is one bf16 pass), like core.fno.F32
    return jnp.einsum(eq, xf, w, precision=jax.lax.Precision.HIGHEST)


# Local truncate/pad helpers: semantically identical to core.dfft's
# truncate_full/pad_full/truncate_rfft/pad_rfft, re-stated here because
# importing repro.core from the kernel package would be a circular import
# (repro.core.fno imports this package).

def _truncate_full_ref(xf: jax.Array, axis: int, m: int) -> jax.Array:
    n = xf.shape[axis]
    lo = jax.lax.slice_in_dim(xf, 0, m, axis=axis)
    hi = jax.lax.slice_in_dim(xf, n - m, n, axis=axis)
    return jnp.concatenate([lo, hi], axis=axis)


def _pad_full_ref(yf: jax.Array, axis: int, n: int) -> jax.Array:
    k = yf.shape[axis]
    m = k // 2
    lo = jax.lax.slice_in_dim(yf, 0, m, axis=axis)
    hi = jax.lax.slice_in_dim(yf, m, k, axis=axis)
    shape = list(yf.shape)
    shape[axis] = n - k
    z = jnp.zeros(shape, yf.dtype)
    return jnp.concatenate([lo, z, hi], axis=axis)


def pad_kept_ref(yk: jax.Array, trunc, t_out: int | None = None) -> jax.Array:
    """Zero-pad a kept-mode tensor [b, co, K1, K2, K3, KT] back to the fused
    output layout: full size ``trunc[d]`` on each spatial dim where trunc[d]
    is not None, and rFFT tail-pad the trailing dim to ``t_out`` when given.
    Matches the pad half of ``spectral_apply_fused_ref`` exactly.
    """
    trunc = tuple(trunc)
    kt = yk.shape[-1]
    for d, n in enumerate(trunc):
        if n is not None:
            yk = _pad_full_ref(yk, 2 + d, n)
    if t_out is not None and t_out != kt:
        shape = list(yk.shape)
        shape[-1] = t_out - kt
        yk = jnp.concatenate([yk, jnp.zeros(shape, yk.dtype)], axis=-1)
    return yk


def spectral_apply_fused_ref(
    xf: jax.Array,
    w: jax.Array,
    trunc,
    t_out: int | None = None,
) -> jax.Array:
    """Unfused XLA oracle for the fused truncate+mix+pad op.

    xf: [b, ci, E1, E2, E3, T] complex spectrum; w: [ci, co, K1, K2, K3, KT]
    complex kept-mode weights. ``trunc[d]`` (d over the three spatial dims)
    is the full size N to truncate from / pad back to, or None if the dim
    arrives pre-truncated (E_d == K_d). The trailing dim is rFFT-style:
    keep bins [:KT], pad the tail back to ``t_out`` (or stay at KT).
    """
    trunc = tuple(trunc)
    kt = w.shape[-1]
    for d, n in enumerate(trunc):
        if n is not None:
            xf = _truncate_full_ref(xf, 2 + d, w.shape[2 + d] // 2)
    if xf.shape[-1] != kt:
        xf = jax.lax.slice_in_dim(xf, 0, kt, axis=-1)
    y = spectral_apply_ref(xf, w)
    for d, n in enumerate(trunc):
        if n is not None:
            y = _pad_full_ref(y, 2 + d, n)
    if t_out is not None and t_out != kt:
        shape = list(y.shape)
        shape[-1] = t_out - kt
        y = jnp.concatenate([y, jnp.zeros(shape, y.dtype)], axis=-1)
    return y
