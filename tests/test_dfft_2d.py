"""Property tests for the truncated-FFT operators: the 1-D pairs (paper
Alg. 2 and the Grady et al. [31] baseline) and the 2-D pencil pair.

Three layers of guarantees:
  * hypothesis-driven dot-product adjoint tests  <F x, y> == <x, F^T y>
    (with the exact rFFT pairing weights) and serial-equivalence over random
    grids/modes, run in-process on a mesh sized to the available devices
    (size-1 axes locally; real all-to-alls under the CI 8-device flag);
  * round-trip identity A(F(x)) == x on the Hermitian-symmetric subspace;
  * a subprocess check on a REAL 2x2 ("mx","my") mesh (4 simulated host
    devices) asserting the acceptance bound: dist_forward_2d/dist_adjoint_2d
    match serial_forward/serial_adjoint to <= 1e-4 relative error.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.common import compat
from repro.core import dfft
from repro.core.partition import CartPartition, make_mesh
from jax.sharding import PartitionSpec as P

from repro.core.dfft import XDIM, YDIM, ZDIM


# ---------------------------------------------------------------------------
# Helpers.
# ---------------------------------------------------------------------------

def _pairing_weights(grid, modes):
    """Diagonal W with <x, A y>_R == Re <W * F(x), y>_C.

    A (= pad + inverse FFT) is the true real-pairing adjoint of F
    (= FFT + truncate) up to the 1/N inverse scaling and the rFFT
    half-spectrum double counting: weight 2 on interior t-bins, 1 on the
    DC bin (and the Nyquist bin when kept).
    """
    nx, ny, nz, nt = grid
    mt = modes[-1]
    wt = np.full((mt,), 2.0, dtype=np.float64)
    wt[0] = 1.0
    if nt % 2 == 0 and mt == nt // 2 + 1:
        wt[-1] = 1.0
    return wt / float(nx * ny * nz * nt)


def _rand_field(seed, shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _rand_spectrum(seed, shape):
    kr, ki = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kr, shape) + 1j * jax.random.normal(ki, shape)).astype(
        jnp.complex64
    )


def _mesh_1d():
    p = 2 if len(jax.devices()) >= 2 else 1
    return make_mesh((p,), ("model",)), p


def _mesh_2d():
    p = 2 if len(jax.devices()) >= 4 else 1
    return make_mesh((p, p), ("mx", "my")), p


_PAIRS_1D = {
    "paper": (dfft.dist_forward, dfft.dist_adjoint),
    "grady31": (dfft.dist_forward_untruncated, dfft.dist_adjoint_untruncated),
}

_PAIRS_2D = {
    "paper": (dfft.dist_forward_2d, dfft.dist_adjoint_2d),
}


def _check_against_serial(fwd, adj, grid, modes, seed, rtol=1e-4):
    """fwd/adj are jit-ed GLOBAL functions (shard_map'd dist or serial)."""
    x = _rand_field(seed, (2, 1) + tuple(grid))
    ref_f = dfft.serial_forward(x, modes)
    got_f = fwd(x)
    scale = float(jnp.max(jnp.abs(ref_f))) or 1.0
    np.testing.assert_allclose(
        np.asarray(got_f), np.asarray(ref_f), rtol=rtol, atol=rtol * scale
    )

    y = _rand_spectrum(seed + 1, ref_f.shape)
    ref_a = dfft.serial_adjoint(y, grid)
    got_a = adj(y)
    scale_a = float(jnp.max(jnp.abs(ref_a))) or 1.0
    np.testing.assert_allclose(
        np.asarray(got_a), np.asarray(ref_a), rtol=rtol, atol=rtol * scale_a
    )

    # dot-product adjoint identity: <x, A y>_R == Re <W * F(x), y>_C
    w = jnp.asarray(_pairing_weights(grid, modes), jnp.float32)
    lhs = float(jnp.vdot(x, got_a).real)
    rhs = complex(jnp.vdot(got_f * w, y)).real
    np.testing.assert_allclose(lhs, rhs, rtol=5e-4, atol=5e-4)

    # round trip: identity on the Hermitian-symmetric subspace (unpaired
    # mode slice of each full-FFT dim zeroed; cf. test_dfft.py).
    mx, my, mz, _ = modes
    spec = ref_f.at[:, :, mx].set(0).at[:, :, :, my].set(0).at[:, :, :, :, mz].set(0)
    xs = dfft.serial_adjoint(spec, grid)
    xs2 = adj(fwd(xs))
    np.testing.assert_allclose(
        np.asarray(xs2), np.asarray(xs), rtol=1e-3, atol=1e-4
    )


# ---------------------------------------------------------------------------
# Hypothesis-driven properties.
# ---------------------------------------------------------------------------

@settings(max_examples=4, deadline=None)
@given(
    nx=st.sampled_from([8, 16]),
    ny=st.sampled_from([8, 12]),
    m=st.integers(1, 3),
    mt=st.integers(1, 4),
    pair=st.sampled_from(sorted(_PAIRS_1D)),
)
def test_dist_1d_adjoint_properties(nx, ny, m, mt, pair):
    grid = (nx, ny, 8, 8)
    modes = (min(m, nx // 2), min(m + 1, ny // 2), m, mt)
    mesh, p = _mesh_1d()
    if (2 * modes[1]) % p or nx % p:
        p = 1
        mesh = make_mesh((1,), ("model",))
    fwd_fn, adj_fn = _PAIRS_1D[pair]
    x_spec = P(None, None, "model", None, None, None)
    f_spec = P(None, None, None, "model", None, None)
    fwd = jax.jit(
        compat.shard_map(lambda a: fwd_fn(a, modes, "model"), mesh, (x_spec,), f_spec)
    )
    adj = jax.jit(
        compat.shard_map(lambda a: adj_fn(a, grid, "model"), mesh, (f_spec,), x_spec)
    )
    _check_against_serial(fwd, adj, grid, modes, seed=nx * 100 + m)


@settings(max_examples=4, deadline=None)
@given(
    nx=st.sampled_from([8, 16]),
    nz=st.sampled_from([4, 8]),
    m=st.integers(1, 2),
    mt=st.integers(1, 3),
    pair=st.sampled_from(sorted(_PAIRS_2D)),
)
def test_dist_2d_pencil_adjoint_properties(nx, nz, m, mt, pair):
    grid = (nx, 8, nz, 8)
    modes = (min(m + 1, nx // 2), m, min(m, nz // 2), mt)
    mesh, p = _mesh_2d()
    # pencil divisibility: Px | nx, Px | 2my, Py | ny, Py | 2mz
    if nx % p or (2 * modes[1]) % p or 8 % p or (2 * modes[2]) % p:
        p = 1
        mesh = make_mesh((1, 1), ("mx", "my"))
    fwd_fn, adj_fn = _PAIRS_2D[pair]
    x_spec = P(None, None, "mx", "my", None, None)
    f_spec = P(None, None, None, "mx", "my", None)
    fwd = jax.jit(
        compat.shard_map(
            lambda a: fwd_fn(a, modes, ("mx", "my")), mesh, (x_spec,), f_spec
        )
    )
    adj = jax.jit(
        compat.shard_map(
            lambda a: adj_fn(a, grid, ("mx", "my")), mesh, (f_spec,), x_spec
        )
    )
    _check_against_serial(fwd, adj, grid, modes, seed=nx * 10 + nz)


@settings(max_examples=10, deadline=None)
@given(
    n=st.sampled_from([8, 12, 16]),
    m=st.integers(1, 4),
    mt=st.integers(1, 4),
)
def test_serial_adjoint_pairing(n, m, mt):
    """<x, A y>_R == Re <W F x, y>_C for the serial oracle itself."""
    grid = (n, 8, 8, 8)
    modes = (min(m, n // 2), min(m, 4), min(m, 4), mt)
    x = _rand_field(n + m, (1, 2) + grid)
    f = dfft.serial_forward(x, modes)
    y = _rand_spectrum(m, f.shape)
    w = jnp.asarray(_pairing_weights(grid, modes), jnp.float32)
    lhs = float(jnp.vdot(x, dfft.serial_adjoint(y, grid)).real)
    rhs = complex(jnp.vdot(f * w, y)).real
    np.testing.assert_allclose(lhs, rhs, rtol=5e-4, atol=5e-4)


@settings(max_examples=10, deadline=None)
@given(
    src=st.sampled_from([XDIM, YDIM]),
    dst=st.sampled_from([YDIM, ZDIM]),
)
def test_cart_partition_multi_axis_moves(src, dst):
    """with_moved composes per-mesh-axis moves exactly like the pencil path."""
    if src == dst:
        return
    part = CartPartition((None, None, "mx", "my", None, None))
    if part.dims[src] is None:
        return
    axis = part.dims[src]
    moved = part.with_moved(src, dst, axis) if dst != src else part
    assert moved.dims[src] is None
    dst_axes = moved.dims[dst]
    if isinstance(dst_axes, tuple):
        assert axis in dst_axes
    else:
        assert dst_axes == axis
    # moving back restores the original partition
    back = moved.with_moved(dst, src, axis)
    assert back.dims[src] == part.dims[src]
    assert back.dims[dst] == part.dims[dst]


def test_cart_partition_pencil_sequence():
    """The exact partition walk of dist_forward_2d, as descriptor algebra."""
    part = CartPartition((None, None, "mx", "my", None, None))
    after_my = part.with_moved(YDIM, ZDIM, "my")
    assert after_my.dims == (None, None, "mx", None, "my", None)
    after_mx = after_my.with_moved(XDIM, YDIM, "mx")
    assert after_mx.dims == (None, None, None, "mx", "my", None)
    # adjoint path reverses both moves
    back = after_mx.with_moved(YDIM, XDIM, "mx").with_moved(ZDIM, YDIM, "my")
    assert back.dims == part.dims


# ---------------------------------------------------------------------------
# Real 2x2 mesh acceptance check (subprocess: needs 4 simulated devices).
# ---------------------------------------------------------------------------

def test_pencil_2x2_mesh_matches_serial_subprocess():
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, %r)
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.common import compat
        from repro.core import dfft
        from repro.core.partition import make_mesh
        from repro.core.repartition import repartition_multi, repartition_multi_t

        mesh = make_mesh((2, 2), ("mx", "my"))

        # repartition_multi: the pencil move sequence round-trips exactly
        # (each all-to-all is a cross-device permutation; the transposed
        # reversed sequence is its inverse).
        XD, YD, ZD = dfft.XDIM, dfft.YDIM, dfft.ZDIM
        moves = ((YD, ZD, "my"), (XD, YD, "mx"))
        a_spec = P(None, None, "mx", "my", None, None)
        b_spec = P(None, None, None, "mx", "my", None)
        a = jax.random.normal(jax.random.PRNGKey(3), (2, 1, 8, 8, 4, 4))
        fwd_m = jax.jit(compat.shard_map(
            lambda t: repartition_multi(t, moves), mesh, (a_spec,), b_spec))
        bwd_m = jax.jit(compat.shard_map(
            lambda t: repartition_multi_t(t, moves), mesh, (b_spec,), a_spec))
        moved = fwd_m(a)
        np.testing.assert_array_equal(np.asarray(bwd_m(moved)), np.asarray(a))
        # pure permutation: global contents are preserved
        np.testing.assert_allclose(
            float(jnp.vdot(moved, moved)), float(jnp.vdot(a, a)), rtol=1e-6)
        x_spec = P(None, None, "mx", "my", None, None)
        f_spec = P(None, None, None, "mx", "my", None)
        for grid, modes in (((16, 8, 8, 8), (4, 2, 2, 3)),
                            ((8, 16, 4, 6), (2, 3, 2, 2))):
            x = jax.random.normal(jax.random.PRNGKey(0), (2, 2) + grid)
            ref = dfft.serial_forward(x, modes)
            fwd = jax.jit(compat.shard_map(
                lambda a: dfft.dist_forward_2d(a, modes, ("mx", "my")),
                mesh, (x_spec,), f_spec))
            adj = jax.jit(compat.shard_map(
                lambda a: dfft.dist_adjoint_2d(a, grid, ("mx", "my")),
                mesh, (f_spec,), x_spec))
            got = fwd(x)
            rel = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
            assert rel <= 1e-4, ("dist_forward_2d", grid, rel)
            back_ref = dfft.serial_adjoint(ref, grid)
            back = adj(got)
            rel_a = float(jnp.max(jnp.abs(back - back_ref)) / jnp.max(jnp.abs(back_ref)))
            assert rel_a <= 1e-4, ("dist_adjoint_2d", grid, rel_a)
        print("PENCIL_2X2_OK")
        """
    ) % (os.path.join(os.path.dirname(__file__), "..", "src"),)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=600, env=env,
    )
    assert "PENCIL_2X2_OK" in proc.stdout, proc.stdout + proc.stderr[-3000:]
