"""jit'd public wrappers for the spectral convolution.

Dispatches between the pure-XLA reference (used on CPU and in AOT dry-runs)
and the Pallas TPU kernels (validated in interpret mode on CPU). The
wrappers own layout and autodiff:

- ``spectral_apply``: pre-truncated modes, flattened-K kernel. The wrapper
  flattens mode dims to K, splits complex into re/im planes, and pads K to
  the kernel's block size.
- ``spectral_apply_fused``: full-spectrum input; the kernel fuses mode
  truncation, the complex channel mix, and zero-padding into one HBM pass.
- the weight-plane cache: ``cached_weight_planes(w_spec)`` computes the
  float32 (re, im) planes once per weight buffer and reuses them across
  training steps and serving rollout steps (both wrappers accept a
  ``(wr, wi)`` planes tuple in place of complex ``w``).

Autodiff: jax cannot differentiate through ``pallas_call`` in interpret
mode, so both Pallas paths carry a ``jax.custom_vjp``. The VJP follows
JAX's convention for complex bilinear ops — plain transpose, NO
conjugation — so the backward mixes have the same 4-real-matmul structure
as the forward:

  x_bar = g . w^T   (contract co):  gxr = gr.wr - gi.wi, gxi = gr.wi + gi.wr
  w_bar = x ._b g   (contract b):   gwr = xr.gr - xi.gi, gwi = xr.gi + xi.gr

which means dx literally reuses the forward kernel with transposed weight
planes, and dw is one extra kernel of the same shape family.
"""
from __future__ import annotations

import functools
import weakref

import jax
import jax.numpy as jnp

from repro.kernels.interpret import resolve_interpret
from repro.kernels.spectral_conv.kernel import (
    spectral_apply_pallas,
    spectral_dw_pallas,
    spectral_fused_dw,
    spectral_fused_pallas,
)
from repro.kernels.spectral_conv.ref import (
    pad_kept_ref,
    spectral_apply_fused_ref,
    spectral_apply_ref,
)


def _planes(z: jax.Array):
    return jnp.real(z).astype(jnp.float32), jnp.imag(z).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Weight-plane layout cache.
# ---------------------------------------------------------------------------

weight_planes = _planes
weight_planes.__doc__ = (
    "Split a complex weight tensor into float32 (re, im) planes, keeping "
    "the mode dims unflattened so the planes shard with the same "
    "PartitionSpec as the complex original."
)

# buffer identity -> (weakref-or-array, planes). Host-side: call OUTSIDE
# jit (under a trace, id() is a tracer id and caching would be wrong).
_PLANE_CACHE: dict = {}
_PLANE_STATS = {"hits": 0, "misses": 0}


def cached_weight_planes(w: jax.Array):
    """Memoized ``weight_planes``: one re/im split per live weight buffer.

    Keyed on buffer identity (id + shape + dtype), validated against a
    weakref to the original array so a recycled id can never serve stale
    planes. Intended for frozen params (serving / eval): FNORunner calls
    this once per checkpoint instead of re-laying-out ``w_spec`` on every
    block of every rollout step.
    """
    key = (id(w), tuple(w.shape), str(w.dtype))
    hit = _PLANE_CACHE.get(key)
    if hit is not None:
        ref, planes = hit
        src = ref() if isinstance(ref, weakref.ref) else ref
        if src is w:
            _PLANE_STATS["hits"] += 1
            return planes
        del _PLANE_CACHE[key]
    _PLANE_STATS["misses"] += 1
    planes = weight_planes(w)
    try:
        # the dict is bound now: at interpreter exit the global may be gone
        ref = weakref.ref(w, lambda _ref, c=_PLANE_CACHE: c.pop(key, None))
    except TypeError:  # array type without weakref support: strong ref
        ref = w
    _PLANE_CACHE[key] = (ref, planes)
    return planes


def plane_cache_stats() -> dict:
    return {**_PLANE_STATS, "entries": len(_PLANE_CACHE)}


def clear_plane_cache() -> None:
    _PLANE_CACHE.clear()
    _PLANE_STATS["hits"] = 0
    _PLANE_STATS["misses"] = 0


def _as_complex(w):
    if isinstance(w, tuple):
        wr, wi = w
        return wr + 1j * wi
    return w


# ---------------------------------------------------------------------------
# Flattened-K path (modes pre-truncated upstream).
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _flat_vjp(block_k: int, interpret):
    """custom_vjp'd flattened mix over complex (x2 [K,b,ci], w2 [K,ci,co]),
    K already padded to a block_k multiple."""

    def _mix(x2, w2):
        yr, yi = spectral_apply_pallas(
            *_planes(x2), *_planes(w2), block_k=block_k, interpret=interpret
        )
        return (yr + 1j * yi).astype(jnp.complex64)

    @jax.custom_vjp
    def f(x2, w2):
        return _mix(x2, w2)

    def fwd(x2, w2):
        return _mix(x2, w2), (x2, w2)

    def bwd(res, g):
        x2, w2 = res
        # dx = g . w^T (plain transpose): forward kernel, ci/co swapped.
        w2t = jnp.swapaxes(w2, 1, 2)
        gxr, gxi = spectral_apply_pallas(
            *_planes(g), *_planes(w2t), block_k=block_k, interpret=interpret
        )
        # dw = x ._b g (contract batch).
        gwr, gwi = spectral_dw_pallas(
            *_planes(x2), *_planes(g), block_k=block_k, interpret=interpret
        )
        return (
            (gxr + 1j * gxi).astype(x2.dtype),
            (gwr + 1j * gwi).astype(w2.dtype),
        )

    f.defvjp(fwd, bwd)
    return f


def spectral_apply(
    xf: jax.Array,
    w,
    *,
    use_pallas: bool = False,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """xf: [b, ci, *modes] complex; w: [ci, co, *modes] complex, or a
    ``(wr, wi)`` float planes tuple (e.g. from ``cached_weight_planes``).

    Returns [b, co, *modes] complex. Differentiable on both paths.
    """
    w = _as_complex(w)
    if not use_pallas:
        return spectral_apply_ref(xf, w)

    interpret = resolve_interpret(interpret)

    b, ci, *modes = xf.shape
    co = w.shape[1]
    k = 1
    for m in modes:
        k *= int(m)

    # [b, ci, K] -> [K, b, ci]; [ci, co, K] -> [K, ci, co]
    x2 = jnp.moveaxis(xf.reshape(b, ci, k), -1, 0)
    w2 = jnp.moveaxis(w.reshape(ci, co, k), -1, 0)

    pad = (-k) % block_k
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0), (0, 0)))
        w2 = jnp.pad(w2, ((0, pad), (0, 0), (0, 0)))

    y = _flat_vjp(block_k, interpret)(x2, w2)
    if pad:
        y = y[:k]
    return jnp.moveaxis(y, 0, -1).reshape(b, co, *modes)


# ---------------------------------------------------------------------------
# Fused truncate + mix + pad path (full-spectrum input).
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _fused_vjp(trunc, t_out, interpret):
    """custom_vjp'd fused op over complex (xf, w)."""

    def _mix(xf, w):
        yr, yi = spectral_fused_pallas(
            *_planes(xf), *_planes(w), trunc=trunc, t_out=t_out,
            interpret=interpret,
        )
        return (yr + 1j * yi).astype(jnp.complex64)

    @jax.custom_vjp
    def f(xf, w):
        return _mix(xf, w)

    def fwd(xf, w):
        return _mix(xf, w), (xf, w)

    def bwd(res, g):
        xf, w = res
        # dx = g . w^T: the forward fused kernel with ci/co-swapped planes,
        # reading the kept bins of g and padding back to xf's t extent.
        # Non-kept x positions got masked in the forward, so their
        # cotangent is the zero the pad re-inserts — exact, not approximate.
        wt = jnp.swapaxes(w, 0, 1)
        gxr, gxi = spectral_fused_pallas(
            *_planes(g), *_planes(wt), trunc=trunc, t_out=xf.shape[-1],
            interpret=interpret,
        )
        # dw = S(x) ._b S(g) on the kept grid only.
        gwr, gwi = spectral_fused_dw(
            *_planes(xf), *_planes(g), trunc=trunc,
            kept=tuple(int(s) for s in w.shape[2:]), interpret=interpret,
        )
        return (
            (gxr + 1j * gxi).astype(xf.dtype),
            (gwr + 1j * gwi).astype(w.dtype),
        )

    f.defvjp(fwd, bwd)
    return f


def spectral_apply_fused(
    xf: jax.Array,
    w,
    trunc,
    *,
    t_out: int | None = None,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused S^T (W ·) S: truncate + complex channel mix + zero-pad.

    xf: [b, ci, E1, E2, E3, T] complex spectrum. w: [ci, co, K1, K2, K3,
    KT] complex kept-mode weights, or a ``(wr, wi)`` float planes tuple.
    ``trunc[d]`` = full size N of spatial dim d (truncate/pad inside the
    kernel) or None if pre-truncated upstream. The rFFT-style trailing dim
    keeps bins [:KT] and pads back to ``t_out`` when given.

    The complex-``w`` Pallas path is differentiable (custom_vjp); the
    planes-tuple Pallas path is inference-only — it skips the complex
    re-combine entirely, which is the point of the plane cache.
    """
    trunc = tuple(trunc)
    if use_pallas:
        # resolved here, outside the jitted kernels' trace caches
        interpret = resolve_interpret(interpret)
    if isinstance(w, tuple):
        wr, wi = w
        if not use_pallas:
            return spectral_apply_fused_ref(xf, wr + 1j * wi, trunc, t_out)
        yr, yi = spectral_fused_pallas(
            *_planes(xf), wr, wi, trunc=trunc, t_out=t_out,
            interpret=interpret,
        )
        return (yr + 1j * yi).astype(jnp.complex64)
    if not use_pallas:
        return spectral_apply_fused_ref(xf, w, trunc, t_out)
    return _fused_vjp(trunc, t_out, interpret)(xf, w)


# ---------------------------------------------------------------------------
# Static-contribution split: cache W . S(static) once, run the fused kernel
# on the dynamic remainder only.
# ---------------------------------------------------------------------------

def spectral_static_contribution(sf: jax.Array, w) -> jax.Array:
    """Kept-mode static contribution C = W . S(h_static).

    sf: [b, ci, K1, K2, K3, KT] (or unbatched [ci, ...]) truncated kept-mode
    spectrum of the static activation; w: complex kept-mode weights or a
    ``(wr, wi)`` planes tuple (so serving can reuse ``cached_weight_planes``).
    C is what FNORunner caches per geomodel: because FFT -> truncate -> mix
    is linear up to the first nonlinearity, C is computed once and summed
    with the dynamic remainder's kept-mode mix on every warm request.
    """
    w = _as_complex(w)
    unbatched = sf.ndim == w.ndim - 1
    if unbatched:
        sf = sf[None]
    y = spectral_apply_ref(sf, w)
    return y[0] if unbatched else y


def spectral_apply_fused_add(
    xf: jax.Array,
    w,
    add: jax.Array,
    trunc,
    *,
    t_out: int | None = None,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused truncate+mix+pad on the dynamic remainder ``xf`` plus a cached
    kept-mode static contribution ``add`` [b, co, K1, K2, K3, KT].

    Zero-padding is linear, so pad(mix(trunc(xf))) + pad(add) ==
    pad(mix(trunc(xf)) + add): the Pallas kernel runs unmodified on the
    remainder and the cached contribution is padded into the same layout
    and summed outside.
    """
    y = spectral_apply_fused(
        xf, w, trunc, t_out=t_out, use_pallas=use_pallas, interpret=interpret
    )
    return y + pad_kept_ref(add.astype(y.dtype), trunc, t_out)
