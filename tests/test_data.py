"""Data layer: chunked store properties + PDE simulator physics sanity."""
import tempfile

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.data.store import ArrayStore


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(4, 12),
    c0=st.integers(1, 4),
    a=st.integers(0, 3),
    b=st.integers(4, 8),
)
def test_store_slice_matches_numpy(n, c0, a, b):
    with tempfile.TemporaryDirectory() as d:
        data = np.random.default_rng(n).normal(size=(n, 8)).astype(np.float32)
        store = ArrayStore.create(f"{d}/x", (n, 8), "f4", (c0, 8))
        grid = store.chunk_grid()
        for i in range(grid[0]):
            lo = i * c0
            hi = min(lo + c0, n)
            store.write_chunk((i, 0), data[lo:hi])
        got = store.read_slice((slice(a, min(b, n)), slice(0, 8)))
        np.testing.assert_array_equal(got, data[a : min(b, n)])


def test_store_compression_and_dtype():
    with tempfile.TemporaryDirectory() as d:
        store = ArrayStore.create(f"{d}/x", (2, 16), "f2", (1, 16))
        x = np.linspace(0, 1, 16, dtype=np.float16)
        store.write_chunk((0, 0), x[None])
        got = store.read_chunk((0, 0))
        assert got.dtype == np.float16
        np.testing.assert_array_equal(got[0], x)


# ---------------------------------------------------------------------------
# Navier-Stokes
# ---------------------------------------------------------------------------

def test_ns_simulation_physics():
    from repro.data.pde.navier_stokes import NSConfig, simulate, sphere_mask
    import jax

    cfg = NSConfig(n=16, nt_frames=4, steps_per_frame=5)
    center = jnp.asarray([0.4, 0.5, 0.5])
    chi, vort = jax.jit(lambda c: simulate(c, cfg))(center)
    assert chi.shape == (16, 16, 16)
    assert vort.shape == (16, 16, 16, 4)
    assert bool(jnp.all(jnp.isfinite(vort)))
    # a wake forms: vorticity is strongest near the sphere, nonzero overall
    assert float(vort[..., -1].max()) > 0.1
    # sphere mask is where we asked for it
    mask = np.asarray(sphere_mask(cfg, center))
    assert mask.sum() > 0
    com = np.array(np.nonzero(mask)).mean(axis=1) / 16
    np.testing.assert_allclose(com, np.asarray(center), atol=0.1)


def test_ns_divergence_free():
    """Velocity field from the spectral solver must stay solenoidal."""
    import jax
    from repro.data.pde import navier_stokes as ns

    cfg = ns.NSConfig(n=16, nt_frames=1, steps_per_frame=5)
    kx, ky, kz, k2 = ns._wavenumbers(cfg.n)
    chi = ns.sphere_mask(cfg, jnp.asarray([0.5, 0.5, 0.5]))
    u0 = jnp.zeros((3, 16, 16, 16)).at[0].set(1.0)
    uh = jnp.fft.fftn(u0, axes=(1, 2, 3))
    uh = ns._project(uh, kx, ky, kz, k2)
    for _ in range(3):
        r = ns._rhs(uh, chi, cfg, kx, ky, kz, k2)
        uh = ns._project(uh + cfg.dt * r, kx, ky, kz, k2)
    div = kx * uh[0] + ky * uh[1] + kz * uh[2]
    assert float(jnp.abs(div).max()) < 1e-3 * float(jnp.abs(uh).max())


# ---------------------------------------------------------------------------
# Two-phase CO2
# ---------------------------------------------------------------------------

def test_co2_simulation_physics():
    from repro.data.pde.two_phase import simulate_task

    mask, sat = simulate_task(seed=1, n_wells=2, grid=(16, 8, 8), nt=6)
    assert sat.shape == (16, 8, 8, 6)
    assert np.isfinite(sat).all()
    assert (sat >= 0).all() and (sat <= 0.95).all()
    totals = [sat[..., t].sum() for t in range(6)]
    # injection: plume mass grows monotonically
    assert all(b >= a - 1e-3 for a, b in zip(totals, totals[1:]))
    assert totals[-1] > totals[0]
    # plume spreads beyond the well cells
    assert (sat[..., -1] > 0.05).sum() > mask.sum()


def test_co2_buoyancy():
    """CO2 migrates upward (toward z=0) relative to injection depth."""
    from repro.data.pde.two_phase import TwoPhaseConfig, random_well_mask, simulate
    import jax

    cfg = TwoPhaseConfig(grid=(12, 6, 10), nt_frames=8)
    mask = np.zeros(cfg.grid, np.float32)
    mask[6, 3, 7] = 1.0  # single deep injector
    sat = np.asarray(jax.jit(lambda m: simulate(m, cfg))(jnp.asarray(mask)))
    z_first = (sat[..., 1] * np.arange(10)[None, None, :]).sum() / max(sat[..., 1].sum(), 1e-9)
    z_last = (sat[..., -1] * np.arange(10)[None, None, :]).sum() / max(sat[..., -1].sum(), 1e-9)
    assert z_last < z_first + 1e-6  # center of mass rises (z index falls)


def test_store_codec_is_thread_safe():
    """Chunk (de)compression runs on the read pool, the loader's prefetch
    thread and thread-backend datagen at once; every round trip must stay
    exact (a shared zstd context corrupts concurrent calls)."""
    from concurrent.futures import ThreadPoolExecutor

    from repro.data.store import _compress, _decompress

    payloads = [
        np.random.default_rng(i).standard_normal(16384).astype(np.float32).tobytes()
        for i in range(16)
    ]

    def round_trips(i):
        return all(
            _decompress(_compress(payloads[(i + k) % 16])) == payloads[(i + k) % 16]
            for k in range(40)
        )

    with ThreadPoolExecutor(max_workers=16) as pool:
        assert all(pool.map(round_trips, range(64)))
