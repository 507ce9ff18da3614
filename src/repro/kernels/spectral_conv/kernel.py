"""Pallas TPU kernels: fused complex per-mode channel mixing.

Motivation (TPU adaptation of the paper's hot spot): XLA lowers a complex
einsum into four real einsums, each re-reading its operands from HBM. For
FNO-sized spectral weights (GBs — they dominate the model), the op is
HBM-bandwidth-bound, so reading X and W once and doing the four real
MXU contractions from VMEM halves the dominant W-stream traffic.

Two kernel families live here:

1. The flattened-K mixing kernel (``spectral_apply_pallas`` /
   ``spectral_dw_pallas``): modes are flattened to a leading K dim so each
   grid step owns a contiguous K-tile:

     x:   [K, B, CI]   (split into re/im float32 planes)
     w:   [K, CI, CO]
     out: [K, B, CO]

   Grid: (K // block_k,). Each step does a batched complex matmul over its
   K-tile entirely in VMEM (yr = xr@wr - xi@wi; yi = xr@wi + xi@wr).
   BlockSpec tiling keeps the per-step VMEM footprint at
   block_k * (B*CI + CI*CO + B*CO) * 4B * 2 (re+im), sized by ``block_k``
   (default 128 -> ~4.5 MB at CI=CO=64, B=2, comfortably inside 16 MB
   VMEM). K is zero-padded to a block_k multiple by the ops.py wrapper.

2. The fused truncate+mix+pad kernel (``spectral_fused_pallas`` /
   ``spectral_fused_dw_pallas``): consumes the FULL spectrum in its natural
   [b, c, x, y, z, t] layout and fuses the FNO epilogue — mode truncation
   (S), per-mode channel mix (W·), and zero-padding (S^T) — into one pass.
   The unfused XLA pipeline materializes truncate -> mix -> pad as three
   HBM round trips of the mode tensor; here the grid walks the OUTPUT
   (x, y) positions (block size 1 along those leading dims, so any element
   offset is a legal block index), every block spans the full trailing
   (z, t) extents (the TPU's (8, 128) tiling rule), the weight BlockSpec
   gathers the matching kept-mode slab via a computed index map, and the
   kept z/t modes are gathered in-register — every operand streams from
   HBM exactly once. The weight planes arrive UNFLATTENED (same
   [ci, co, kx, ky, kz, kt] layout as ``w_spec``), which is what lets the
   ops-level weight-plane cache reuse one layout across every block call
   and every serving step.

Interpret-mode note: each grid step costs interpreter overhead (~ms), so
keep grids small on CPU (tests use <= a few hundred steps); on TPU the
grid is a hardware loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.interpret import resolve_interpret


# ---------------------------------------------------------------------------
# Flattened-K mixing kernels (mode dims pre-truncated and flattened to K).
# ---------------------------------------------------------------------------

def _kernel(xr_ref, xi_ref, wr_ref, wi_ref, yr_ref, yi_ref):
    xr = xr_ref[...]
    xi = xi_ref[...]
    wr = wr_ref[...]
    wi = wi_ref[...]
    # Batched matmul over the K tile: [k,b,ci] @ [k,ci,co] -> [k,b,co].
    dn = (((2,), (1,)), ((0,), (0,)))
    rr = jax.lax.dot_general(xr, wr, dn, preferred_element_type=jnp.float32)
    ii = jax.lax.dot_general(xi, wi, dn, preferred_element_type=jnp.float32)
    ri = jax.lax.dot_general(xr, wi, dn, preferred_element_type=jnp.float32)
    ir = jax.lax.dot_general(xi, wr, dn, preferred_element_type=jnp.float32)
    yr_ref[...] = rr - ii
    yi_ref[...] = ri + ir


def _kernel_dw(xr_ref, xi_ref, gr_ref, gi_ref, wr_ref, wi_ref):
    """dW of the complex mix under JAX's plain-transpose convention:
    w_bar = x ._b g (contract batch, NO conjugation), per K row."""
    xr = xr_ref[...]
    xi = xi_ref[...]
    gr = gr_ref[...]
    gi = gi_ref[...]
    # [k,b,ci] x [k,b,co] -> [k,ci,co] (contract b, batch k).
    dn = (((1,), (1,)), ((0,), (0,)))
    rr = jax.lax.dot_general(xr, gr, dn, preferred_element_type=jnp.float32)
    ii = jax.lax.dot_general(xi, gi, dn, preferred_element_type=jnp.float32)
    ri = jax.lax.dot_general(xr, gi, dn, preferred_element_type=jnp.float32)
    ir = jax.lax.dot_general(xi, gr, dn, preferred_element_type=jnp.float32)
    wr_ref[...] = rr - ii
    wi_ref[...] = ri + ir


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def spectral_apply_pallas(
    xr: jax.Array,
    xi: jax.Array,
    wr: jax.Array,
    wi: jax.Array,
    *,
    block_k: int = 128,
    interpret: bool | None = None,
):
    """Real/imag planes: xr/xi [K,B,CI]; wr/wi [K,CI,CO] -> yr/yi [K,B,CO].

    K must be divisible by block_k (the ops.py wrapper pads).
    ``interpret=None`` sniffs the backend (compiled on TPU, interpreter
    elsewhere) — a direct caller on TPU gets the real kernel, matching the
    ops.py wrapper's default.
    """
    interpret = resolve_interpret(interpret)
    k, b, ci = xr.shape
    co = wr.shape[-1]
    assert k % block_k == 0, (k, block_k)
    grid = (k // block_k,)
    x_spec = pl.BlockSpec((block_k, b, ci), lambda i: (i, 0, 0))
    w_spec = pl.BlockSpec((block_k, ci, co), lambda i: (i, 0, 0))
    y_spec = pl.BlockSpec((block_k, b, co), lambda i: (i, 0, 0))
    out_shape = [
        jax.ShapeDtypeStruct((k, b, co), jnp.float32),
        jax.ShapeDtypeStruct((k, b, co), jnp.float32),
    ]
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[x_spec, x_spec, w_spec, w_spec],
        out_specs=[y_spec, y_spec],
        out_shape=out_shape,
        interpret=interpret,
    )(xr, xi, wr, wi)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def spectral_dw_pallas(
    xr: jax.Array,
    xi: jax.Array,
    gr: jax.Array,
    gi: jax.Array,
    *,
    block_k: int = 128,
    interpret: bool | None = None,
):
    """Weight cotangent of the flattened mix: xr/xi [K,B,CI], gr/gi
    [K,B,CO] -> wr_bar/wi_bar [K,CI,CO]. Same tiling as the forward."""
    interpret = resolve_interpret(interpret)
    k, b, ci = xr.shape
    co = gr.shape[-1]
    assert k % block_k == 0, (k, block_k)
    grid = (k // block_k,)
    x_spec = pl.BlockSpec((block_k, b, ci), lambda i: (i, 0, 0))
    g_spec = pl.BlockSpec((block_k, b, co), lambda i: (i, 0, 0))
    w_spec = pl.BlockSpec((block_k, ci, co), lambda i: (i, 0, 0))
    out_shape = [
        jax.ShapeDtypeStruct((k, ci, co), jnp.float32),
        jax.ShapeDtypeStruct((k, ci, co), jnp.float32),
    ]
    return pl.pallas_call(
        _kernel_dw,
        grid=grid,
        in_specs=[x_spec, x_spec, g_spec, g_spec],
        out_specs=[w_spec, w_spec],
        out_shape=out_shape,
        interpret=interpret,
    )(xr, xi, gr, gi)


# ---------------------------------------------------------------------------
# Fused truncate + mix + pad kernels (natural [b,c,x,y,z,t] layout).
#
# ``trunc`` is a 3-tuple over the (x, y, z) mode dims: entry N (an int)
# means the input dim is the FULL spectrum of size N — the kernel keeps the
# 2m lowest-|k| modes ([:m] and [N-m:], m = K_d // 2 from the weight shape)
# and zero-fills the rest of the output; entry None means the dim was
# already truncated upstream (kept extent == input extent == output
# extent). The trailing time dim is rFFT-style: the kernel always reads
# bins [0:KT] and zero-pads the output tail up to ``t_out``.
#
# TPU layout: every block spans the FULL trailing (z, t) extents of its
# array, so the (8, 128) tiling rule holds for any mode count. The grid
# walks (x, y) positions and output-channel blocks; x/y truncation happens
# in the index maps, z/t truncation in-register (``_z_slabs``).
# ---------------------------------------------------------------------------

def _validate_fused(x_shape, w_shape, trunc, t_out):
    b, ci = x_shape[:2]
    if w_shape[0] != ci:
        raise ValueError(f"w ci={w_shape[0]} != x ci={ci}")
    kt = w_shape[5]
    if x_shape[5] < kt:
        raise ValueError(f"x time bins {x_shape[5]} < weight kt={kt}")
    if t_out is not None and t_out < kt:
        raise ValueError(f"t_out={t_out} < weight kt={kt}")
    for d in range(3):
        e, k, n = x_shape[2 + d], w_shape[2 + d], trunc[d]
        if n is None:
            if e != k:
                raise ValueError(
                    f"dim {d}: pre-truncated input extent {e} != kept {k}"
                )
        else:
            if e != n:
                raise ValueError(f"dim {d}: input extent {e} != full size {n}")
            if k % 2 or k < 2:
                raise ValueError(f"dim {d}: kept extent {k} must be even >= 2")
            if k > n:
                raise ValueError(f"dim {d}: kept {k} > full {n}")


def _kept_index(i, n, m, k_max):
    """Full-spectrum position -> kept-mode index ([:m] keeps identity,
    [n-m:] lands at [m:2m]); clamped for masked (non-kept) rows."""
    return jnp.clip(jnp.where(i < m, i, i - (n - 2 * m)), 0, k_max - 1)


def _full_index(kd, n, m):
    """Kept-mode index -> full-spectrum position (inverse of _kept_index
    restricted to kept rows): [:m] identity, [m:2m] -> [n-m:]."""
    return jnp.where(kd < m, kd, n - 2 * m + kd)


def _z_slabs(e3, k3, n):
    """(spectrum row, weight row, rows) runs pairing z rows of the spectrum
    with kept-mode weight rows: one run when z arrives pre-truncated, the
    [:m] and [N-m:] runs when it is the full spectrum."""
    if n is None:
        return ((0, 0, k3),)
    m = k3 // 2
    return ((0, 0, m), (e3 - m, m, m))


def _co_block(co: int) -> int:
    """Output channels per grid step: bounds the weight block's VMEM."""
    return next(c for c in (8, 4, 2, 1) if co % c == 0)


def _compiler_params(interpret: bool):
    # every block is double-buffered; the (z, t) tiles pad to (8, 128), so
    # raise the scoped VMEM limit above the 16 MiB default (v5e has 128 MiB)
    return None if interpret else pltpu.CompilerParams(vmem_limit_bytes=96 << 20)


@functools.partial(jax.jit, static_argnames=("trunc", "t_out", "interpret"))
def spectral_fused_pallas(
    xr: jax.Array,
    xi: jax.Array,
    wr: jax.Array,
    wi: jax.Array,
    *,
    trunc,
    t_out: int | None = None,
    interpret: bool | None = None,
):
    """Fused S^T · (W ·) · S: xr/xi [B,CI,E1,E2,E3,Tin] float32 planes of
    the spectrum; wr/wi [CI,CO,K1,K2,K3,KT] planes of the kept-mode
    weights (natural w_spec layout) -> yr/yi [B,CO,E1,E2,E3,t_out or KT].

    Each grid step (one output x/y position, one output-channel block)
    streams one [B,CI,E3,Tin] input slab and one [CI,cb,K3,KT] weight
    slab, does the complex mix over ci on the kept (z, t) modes, and writes
    the zero-padded output slab — truncate, mix and pad in a single HBM
    pass. Non-kept (x, y) positions only write zeros.
    """
    interpret = resolve_interpret(interpret)
    trunc = tuple(trunc)
    _validate_fused(xr.shape, wr.shape, trunc, t_out)
    b, ci = xr.shape[:2]
    co = wr.shape[1]
    e1, e2, e3, tin = xr.shape[2:]
    k1, k2, k3, kt = wr.shape[2:]
    tout = kt if t_out is None else int(t_out)
    ms = (k1 // 2, k2 // 2)
    cb = _co_block(co)
    slabs = _z_slabs(e3, k3, trunc[2])

    def kept(d, p):
        if trunc[d] is None:
            return p
        return _kept_index(p, trunc[d], ms[d], (k1, k2)[d])

    def kern(xr_ref, xi_ref, wr_ref, wi_ref, yr_ref, yi_ref):
        yr_ref[...] = jnp.zeros(yr_ref.shape, jnp.float32)
        yi_ref[...] = jnp.zeros(yi_ref.shape, jnp.float32)
        keep = jnp.bool_(True)
        for d in range(2):
            if trunc[d] is not None:
                p = pl.program_id(d)
                keep = keep & ((p < ms[d]) | (p >= trunc[d] - ms[d]))

        @pl.when(keep)
        def _mix():
            for x0, w0, n in slabs:
                def body(c, acc):
                    ar, ai = acc
                    xs = (c, 0, 0, pl.ds(x0, n), pl.ds(0, kt))
                    a = xr_ref[(slice(None),) + xs][:, None]    # [B,1,n,KT]
                    bi = xi_ref[(slice(None),) + xs][:, None]
                    ws = (c, slice(None), 0, 0, pl.ds(w0, n), slice(None))
                    u = wr_ref[ws][None]                        # [1,cb,n,KT]
                    v = wi_ref[ws][None]
                    return ar + (a * u - bi * v), ai + (a * v + bi * u)

                z = jnp.zeros((b, cb, n, kt), jnp.float32)
                ar, ai = jax.lax.fori_loop(0, ci, body, (z, z))
                ys = (slice(None), slice(None), 0, 0, pl.ds(x0, n), pl.ds(0, kt))
                yr_ref[ys] = ar
                yi_ref[ys] = ai

    grid = (e1, e2, co // cb)
    x_spec = pl.BlockSpec(
        (b, ci, 1, 1, e3, tin), lambda i, j, c: (0, 0, i, j, 0, 0)
    )
    w_spec = pl.BlockSpec(
        (ci, cb, 1, 1, k3, kt),
        lambda i, j, c: (0, c, kept(0, i), kept(1, j), 0, 0),
    )
    y_spec = pl.BlockSpec(
        (b, cb, 1, 1, e3, tout), lambda i, j, c: (0, c, i, j, 0, 0)
    )
    out_shape = [
        jax.ShapeDtypeStruct((b, co, e1, e2, e3, tout), jnp.float32),
        jax.ShapeDtypeStruct((b, co, e1, e2, e3, tout), jnp.float32),
    ]
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[x_spec, x_spec, w_spec, w_spec],
        out_specs=[y_spec, y_spec],
        out_shape=out_shape,
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="spectral_fused",
    )(xr, xi, wr, wi)


@functools.partial(jax.jit, static_argnames=("trunc", "kept", "interpret"))
def spectral_fused_dw(
    xr: jax.Array,
    xi: jax.Array,
    gr: jax.Array,
    gi: jax.Array,
    *,
    trunc,
    kept,
    interpret: bool | None = None,
):
    """Weight cotangent of the fused op: w_bar = S(x) ._b S(g) per kept
    mode (plain transpose, no conjugation).

    xr/xi [B,CI,E1,E2,E3,Tx], gr/gi [B,CO,E1,E2,E3,Tg] are the (possibly
    full) spectrum planes the forward consumed/produced; ``kept`` is the
    weight mode shape (K1,K2,K3,KT). The grid walks kept (x, y) coordinates
    only — every output element is written, so no masking is needed — the
    x/g BlockSpec index maps gather the kept full-spectrum positions, and
    the kept z rows are gathered in-register.
    """
    interpret = resolve_interpret(interpret)
    trunc = tuple(trunc)
    k1, k2, k3, kt = kept
    b, ci = xr.shape[:2]
    co = gr.shape[1]
    e3 = xr.shape[4]
    if xr.shape[5] < kt or gr.shape[5] < kt:
        raise ValueError(f"time bins {xr.shape[5]}/{gr.shape[5]} < kt={kt}")
    ms = (k1 // 2, k2 // 2)
    cb = _co_block(co)
    slabs = _z_slabs(e3, k3, trunc[2])

    def full(d, p):
        return p if trunc[d] is None else _full_index(p, trunc[d], ms[d])

    def kern(xr_ref, xi_ref, gr_ref, gi_ref, wr_ref, wi_ref):
        for x0, w0, n in slabs:
            gs = (slice(None), slice(None), 0, 0, pl.ds(x0, n), pl.ds(0, kt))
            g_r = gr_ref[gs]                                    # [B,cb,n,KT]
            g_i = gi_ref[gs]

            def body(c, carry):
                xs = (slice(None), c, 0, 0, pl.ds(x0, n), pl.ds(0, kt))
                a = xr_ref[xs][:, None]                         # [B,1,n,KT]
                bi = xi_ref[xs][:, None]
                ws = (c, slice(None), 0, 0, pl.ds(w0, n), slice(None))
                wr_ref[ws] = jnp.sum(a * g_r - bi * g_i, axis=0)
                wi_ref[ws] = jnp.sum(a * g_i + bi * g_r, axis=0)
                return carry

            jax.lax.fori_loop(0, ci, body, 0)

    grid = (k1, k2, co // cb)
    x_spec = pl.BlockSpec(
        (b, ci, 1, 1, e3, xr.shape[5]),
        lambda i, j, c: (0, 0, full(0, i), full(1, j), 0, 0),
    )
    g_spec = pl.BlockSpec(
        (b, cb, 1, 1, e3, gr.shape[5]),
        lambda i, j, c: (0, c, full(0, i), full(1, j), 0, 0),
    )
    w_spec = pl.BlockSpec(
        (ci, cb, 1, 1, k3, kt), lambda i, j, c: (0, c, i, j, 0, 0)
    )
    out_shape = [
        jax.ShapeDtypeStruct((ci, co, k1, k2, k3, kt), jnp.float32),
        jax.ShapeDtypeStruct((ci, co, k1, k2, k3, kt), jnp.float32),
    ]
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[x_spec, x_spec, g_spec, g_spec],
        out_specs=[w_spec, w_spec],
        out_shape=out_shape,
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="spectral_fused_dw",
    )(xr, xi, gr, gi)
