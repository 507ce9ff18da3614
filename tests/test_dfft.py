"""Truncated-FFT operator properties (the paper's S and F operators)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import dfft, fno


def _rand_complex(key, shape):
    k1, k2 = jax.random.split(key)
    return (jax.random.normal(k1, shape) + 1j * jax.random.normal(k2, shape)).astype(jnp.complex64)


@settings(max_examples=12, deadline=None)
@given(n=st.sampled_from([8, 12, 16]), m=st.integers(1, 4), axis=st.integers(2, 5))
def test_truncate_pad_adjoint(n, m, axis):
    """<S x, y> == <x, S^T y> — S (truncation) and S^T (zero-pad) are adjoints."""
    if 2 * m > n:
        m = n // 2
    shape = [2, 3, n, n, n, n]
    key = jax.random.PRNGKey(n * 10 + m)
    x = _rand_complex(key, tuple(shape))
    tshape = list(shape)
    tshape[axis] = 2 * m
    y = _rand_complex(jax.random.PRNGKey(7), tuple(tshape))
    sx = dfft.truncate_full(x, axis, m)
    sty = dfft.pad_full(y, axis, n)
    lhs = jnp.vdot(sx, y)
    rhs = jnp.vdot(x, sty)
    np.testing.assert_allclose(complex(lhs), complex(rhs), rtol=1e-5, atol=1e-5)


def test_rfft_truncate_pad_adjoint():
    key = jax.random.PRNGKey(0)
    x = _rand_complex(key, (2, 3, 4, 4, 4, 9))
    y = _rand_complex(jax.random.PRNGKey(1), (2, 3, 4, 4, 4, 5))
    lhs = jnp.vdot(dfft.truncate_rfft(x, 5, 5), y)
    rhs = jnp.vdot(x, dfft.pad_rfft(y, 5, 9))
    np.testing.assert_allclose(complex(lhs), complex(rhs), rtol=1e-5)


def test_serial_roundtrip_bandlimited():
    """Band-limiting behaviour of the FNO corner-mode set.

    The classic [:m]+[-m:] corner set is NOT Hermitian-symmetric (index m
    pairs with n-m, which is kept, while m itself is not), so A∘F is a
    CONTRACTION rather than a projection on real fields: the unpaired
    modes halve every pass. We assert (a) the contraction, and (b) exact
    idempotence once the unpaired slice (local index m of each full dim)
    is zeroed — the truly symmetric sub-space."""
    cfg_grid = (16, 16, 8, 8)
    modes = (4, 4, 2, 3)
    x0 = jax.random.normal(jax.random.PRNGKey(2), (1, 2) + cfg_grid)

    def af(x):
        return dfft.serial_adjoint(dfft.serial_forward(x, modes), cfg_grid)

    x1, x2, x3 = af(x0), af(af(x0)), af(af(af(x0)))
    d1 = float(jnp.max(jnp.abs(x2 - x1)))
    d2 = float(jnp.max(jnp.abs(x3 - x2)))
    assert d2 < 0.6 * d1  # geometric contraction of the unpaired modes

    # symmetric sub-space: zero the unpaired mode slice per full-fft dim
    spec = dfft.serial_forward(x0, modes)
    mx, my, mz, _ = modes
    spec = spec.at[:, :, mx].set(0).at[:, :, :, my].set(0).at[:, :, :, :, mz].set(0)
    xs = dfft.serial_adjoint(spec, cfg_grid)
    xs2 = af(xs)
    np.testing.assert_allclose(np.asarray(xs2), np.asarray(xs), rtol=1e-4, atol=1e-5)


def test_forward_matches_numpy_oracle():
    """serial_forward == rfftn + explicit corner selection (independent impl)."""
    x = np.random.default_rng(0).normal(size=(1, 1, 8, 8, 8, 8)).astype(np.float32)
    modes = (2, 3, 2, 3)
    got = np.asarray(dfft.serial_forward(jnp.asarray(x), modes))
    full = np.fft.rfftn(x, axes=(2, 3, 4, 5))
    mx, my, mz, mt = modes
    sel = full[:, :, np.r_[0:mx, 8 - mx : 8], :, :, :]
    sel = sel[:, :, :, np.r_[0:my, 8 - my : 8], :, :]
    sel = sel[:, :, :, :, np.r_[0:mz, 8 - mz : 8], :]
    sel = sel[:, :, :, :, :, :mt]
    np.testing.assert_allclose(got, sel, rtol=1e-4, atol=1e-4)


def _np_corners(full, modes, trunc_x):
    """Corner selection on a numpy rfftn spectrum ([:m] + [-m:] per full dim,
    x only with ``trunc_x``; the first mt rFFT bins)."""
    mx, my, mz, mt = modes
    for axis, m in ((2, mx), (3, my), (4, mz))[0 if trunc_x else 1:]:
        n = full.shape[axis]
        full = np.take(full, np.r_[0:m, n - m:n], axis=axis)
    return full[..., :mt]


def _np_pad_inverse(spec, grid, modes, pad_x):
    """Zero-pad a kept-mode spectrum into the full rFFT spectrum, then
    numpy's irfftn."""
    nx, ny, nz, nt = grid
    mx, my, mz, mt = modes
    full = np.zeros(spec.shape[:2] + (nx, ny, nz, nt // 2 + 1), np.complex128)
    idx = [np.r_[0:m, n - m:n] for n, m in ((nx, mx), (ny, my), (nz, mz))]
    if not pad_x:
        idx[0] = np.arange(nx)
    full[np.ix_(range(spec.shape[0]), range(spec.shape[1]), *idx, range(mt))] = spec
    return np.fft.irfftn(full, s=grid, axes=(2, 3, 4, 5))


@pytest.mark.parametrize("x_in_kernel", [False, True])
@pytest.mark.parametrize(
    "grid, modes",
    [
        ((8, 6, 5, 9), (4, 3, 2, 5)),     # 2m == n on x and y; odd z, odd t, all bins
        ((7, 9, 6, 12), (2, 3, 3, 4)),    # odd x and y; 2m == n on z
        ((16, 8, 8, 8), (5, 2, 4, 5)),    # every t bin incl. Nyquist; 2m == n on z
    ],
)
def test_serial_pair_matches_numpy(grid, modes, x_in_kernel):
    """serial_forward (trunc_x both ways) == numpy rfftn + corner selection;
    serial_adjoint (pad_x both ways) == numpy zero-pad + irfftn; with x left
    to the kernel, the pair is still adjoint in the real pairing."""
    rng = np.random.default_rng(sum(grid) + sum(modes))
    x = rng.normal(size=(2, 3) + grid).astype(np.float32)
    got = np.asarray(dfft.serial_forward(jnp.asarray(x), modes, trunc_x=not x_in_kernel))
    want = _np_corners(np.fft.rfftn(x.astype(np.float64), axes=(2, 3, 4, 5)),
                       modes, not x_in_kernel)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * np.abs(want).max())

    y = (rng.normal(size=got.shape) + 1j * rng.normal(size=got.shape)).astype(np.complex64)
    back = np.asarray(dfft.serial_adjoint(jnp.asarray(y), grid, pad_x=not x_in_kernel))
    want_back = _np_pad_inverse(y.astype(np.complex128), grid, modes, not x_in_kernel)
    np.testing.assert_allclose(back, want_back, rtol=1e-5,
                               atol=1e-5 * np.abs(want_back).max())

    if x_in_kernel:
        # <x, A y>_R == Re <W F x, y>_C: A is F's real-pairing adjoint up to
        # 1/N and the rFFT half spectrum (weight 2 off the DC/Nyquist bins)
        nx, ny, nz, nt = grid
        wt = np.full(modes[-1], 2.0)
        wt[0] = 1.0
        if nt % 2 == 0 and modes[-1] == nt // 2 + 1:
            wt[-1] = 1.0
        lhs = np.vdot(x.astype(np.float64), back.astype(np.float64))
        rhs = np.vdot(got * wt / (nx * ny * nz * nt), y).real
        np.testing.assert_allclose(lhs, rhs, rtol=1e-4)


_FFT_OP = re.compile(r"stablehlo\.fft %\S+, type =\s+(\w+),.*?: \(tensor<([\dx]+)x\w+")


@pytest.mark.parametrize("use_pallas", [False, True])
def test_fno_block_ffts_run_on_truncated_spectra(use_pallas):
    """Every FFT of a serial block other than the t rFFT / irFFT runs on an
    operand already cut to m_t bins, so none sees the full spectrum (sizes
    chosen so m_t and nt//2+1 are each no other dim's)."""
    grid, modes, width = (20, 12, 8, 26), (3, 2, 3, 5), 4
    cfg = fno.FNOConfig(grid=grid, modes=modes, width=width, use_pallas=use_pallas)
    mt, n_bins = modes[-1], grid[-1] // 2 + 1

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    text = jax.jit(lambda x, w, wb, bb: fno.fno_block(x, w, wb, bb, cfg)).lower(
        s((1, width) + grid), s((width, width) + cfg.mode_shape, jnp.complex64),
        s((width, width)), s((width,)),
    ).as_text()
    ffts = [(kind, [int(d) for d in dims.split("x")])
            for kind, dims in _FFT_OP.findall(text)]
    assert sorted(k for k, _ in ffts if k in ("RFFT", "IRFFT")) == ["IRFFT", "RFFT"]
    spatial = [dims for k, dims in ffts if k in ("FFT", "IFFT")]
    assert all(mt in dims and n_bins not in dims for dims in spatial), ffts
    assert len(spatial) == 6, ffts  # x, y, z each way
