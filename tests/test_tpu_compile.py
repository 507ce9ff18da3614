"""Compile rehearsals for a TPU v5e chip that is described, not attached.

The TPU compiler refuses what interpret mode accepts: block shapes off the
(8, 128) tiling, kernels that exceed VMEM, programs that exceed HBM. These
tests compile the main path's kernels and the one-chip train and serving
steps of ``fno-sleipner`` (``ONE_CHIP_OVERRIDES``: published widths, one
chip's share of the 8x4 pencil), and the four-chip ensemble cell's serving
step on a 2x2 pencil, for a described ``v5e:2x2`` topology, with
``JAX_PLATFORMS=cpu``. Nothing runs; a compile that passes is not a chip run.

The topology is described only inside the fixture below, never while a
module is imported: only one process may load the TPU library.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.common.constants import chip_peaks
from repro.configs import fno_with_overrides
from repro.configs.fno_sleipner import ONE_CHIP_OVERRIDES

# what the process keeps on the chip beside one compiled step (the other
# step's params, the serving cache's device copies, the reference's params)
HEADROOM_BYTES = 3 * 10**9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU program written to the persistent cache cannot be read back
    # without a chip: keep these compiles out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The model code asks the backend (CPU here) whether to interpret its
    Pallas kernels; these compiles are for the chip, so: never."""
    import repro.kernels.interpret as ki

    monkeypatch.setattr(ki, "default_interpret", lambda: False)


def _cfg(use_pallas: bool):
    return dataclasses.replace(
        fno_with_overrides("fno-sleipner", ONE_CHIP_OVERRIDES),
        use_pallas=use_pallas,
    )


def _peak_fits(compiled, device) -> int:
    peak = compiled.memory_analysis().peak_memory_in_bytes
    hbm = chip_peaks(device.device_kind).hbm_bytes
    assert peak + HEADROOM_BYTES <= hbm, (peak, hbm)
    return peak


def test_topology_is_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"
    assert chip_peaks(topo.devices[0].device_kind).hbm_bytes == 16 * 10**9


@pytest.mark.parametrize("layout", ["serial", "pencil"])
@pytest.mark.parametrize("kernel", ["forward", "dw"])
def test_fused_kernel_compiles(one_chip, kernel, layout):
    """The fused truncate + mix + pad kernel and its weight gradient, at
    width 40 on the one-chip modes: the full-spectrum layout ("serial":
    every dim truncated in the kernel, t padded back) and the layout the
    blocks give it ("pencil": y, z and t arrive truncated by the serial
    transforms or the repartition)."""
    from repro.kernels.spectral_conv.kernel import (
        spectral_fused_dw, spectral_fused_pallas,
    )

    cfg = _cfg(True)
    nx, ny, nz, nt = cfg.grid
    kept = cfg.mode_shape
    b, w = 2, cfg.width
    if layout == "serial":
        trunc, spec = (nx, ny, nz), (b, w, nx, ny, nz, nt // 2 + 1)
    else:
        trunc, spec = (nx, None, None), (b, w, nx) + kept[1:]

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    x = s(spec)
    if kernel == "forward":
        wp = s((w, w) + kept)
        fn = jax.jit(lambda a, b_, c, d: spectral_fused_pallas(
            a, b_, c, d, trunc=trunc, t_out=spec[-1], interpret=False))
        compiled = fn.lower(x, x, wp, wp).compile()
    else:
        fn = jax.jit(lambda a, b_, c, d: spectral_fused_dw(
            a, b_, c, d, trunc=trunc, kept=kept, interpret=False))
        compiled = fn.lower(x, x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_one_chip_serving_step_compiles(topo, compiled_kernels):
    """The deep-cache ensemble serving step FNORunner runs at its 2-slot
    bucket, on the fused Pallas path: it holds the kernel and fits the
    chip with headroom."""
    from repro.core import init_params
    from repro.core.fno import contrib_spec, forward_and_specs, params_with_planes

    cfg, n_static, bucket = _cfg(True), 1, 2
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    fwd, x_spec, p_specs = forward_and_specs(
        mesh, cfg, dp_axes=("data",), model_axis=None, planes=True,
        n_static=n_static, prefix="deep",
    )
    c_spec = contrib_spec(("data",), None)
    params = jax.eval_shape(
        lambda: params_with_planes(init_params(jax.random.PRNGKey(0), cfg))
    )

    def ns(spec):
        return NamedSharding(mesh, spec)

    step = jax.jit(
        fwd,
        in_shardings=(
            jax.tree.map(ns, p_specs, is_leaf=lambda s: isinstance(s, P)),
            ns(c_spec), ns(x_spec), ns(x_spec),
        ),
        out_shardings=ns(x_spec),
    )
    compiled = step.lower(
        params,
        jax.ShapeDtypeStruct((bucket, cfg.width) + cfg.mode_shape, jnp.complex64),
        jax.ShapeDtypeStruct((bucket, cfg.width) + cfg.grid, jnp.float32),
        jax.ShapeDtypeStruct(
            (bucket, cfg.in_channels - n_static) + cfg.grid, jnp.float32
        ),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel keeps its name, under the blocks' mix scope
    kernels = [(n, op) for n, op in re.findall(
        r"(\S+) = .*custom-call\(.*op_name=\"([^\"]+)\"", text) if "pallas_call" in op]
    assert kernels and all(n.startswith("%spectral_fused.") and "/blocks/" in op
                           and "/mix/" in op for n, op in kernels), kernels
    _peak_fits(compiled, topo.devices[0])


def test_one_chip_train_step_compiles(topo):
    """train.py's fno step (unfused, batch 1, Adam) at the one-chip
    config fits the chip with headroom."""
    from repro.core import forward_and_specs, init_params, mse_loss
    from repro.train import AdamWConfig, init_opt_state, make_train_step
    from repro.train.train_loop import shard_train_step

    cfg = _cfg(False)
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    fwd, x_spec, p_specs = forward_and_specs(
        mesh, cfg, dp_axes=("data",), model_axis=None
    )

    def loss_fn(params, batch):
        return mse_loss(fwd(params, batch["x"]), batch["y"]), {}

    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    step = shard_train_step(
        make_train_step(loss_fn, AdamWConfig(lr=1e-3)), mesh, p_specs,
        params, {"x": x_spec, "y": x_spec},
    )
    compiled = step.lower(
        params, jax.eval_shape(init_opt_state, params),
        {
            "x": jax.ShapeDtypeStruct((1, cfg.in_channels) + cfg.grid, jnp.float32),
            "y": jax.ShapeDtypeStruct((1, cfg.out_channels) + cfg.grid, jnp.float32),
        },
    ).compile()
    _peak_fits(compiled, topo.devices[0])


# the four-chip ensemble cell's configuration: a 2x2 block of the paper's
# 8x4 pencil, each chip a (32, 16, 48, 88) pencil of the (64, 32, 48, 88)
# grid, with 4x4 (k_y, k_z) kept modes a chip
PENCIL_2X2_OVERRIDES = {
    "grid": (64, 32, 48, 88), "modes": (24, 4, 4, 10), "in_channels": 2,
}


def test_pencil_serving_step_compiles(topo, compiled_kernels):
    """The deep-cache 2-slot serving step of the four-chip ensemble cell,
    on a 2x2 ("mx", "my") pencil of the four described chips: its
    all-to-alls run under the ``a2a`` scope, its fused kernels under
    ``mix``, and each chip's peak fits with headroom."""
    from repro.core import init_params
    from repro.core.fno import contrib_spec, forward_and_specs, params_with_planes

    cfg = dataclasses.replace(
        fno_with_overrides("fno-sleipner", PENCIL_2X2_OVERRIDES), use_pallas=True
    )
    n_static, bucket = 1, 2
    mesh = Mesh(np.array(topo.devices).reshape(1, 2, 2), ("data", "mx", "my"))
    fwd, x_spec, p_specs = forward_and_specs(
        mesh, cfg, dp_axes=("data",), model_axis=("mx", "my"), planes=True,
        n_static=n_static, prefix="deep",
    )
    c_spec = contrib_spec(("data",), ("mx", "my"))
    params = jax.eval_shape(
        lambda: params_with_planes(init_params(jax.random.PRNGKey(0), cfg))
    )

    def ns(spec):
        return NamedSharding(mesh, spec)

    step = jax.jit(
        fwd,
        in_shardings=(
            jax.tree.map(ns, p_specs, is_leaf=lambda s: isinstance(s, P)),
            ns(c_spec), ns(x_spec), ns(x_spec),
        ),
        out_shardings=ns(x_spec),
    )
    compiled = step.lower(
        params,
        jax.ShapeDtypeStruct((bucket, cfg.width) + cfg.mode_shape, jnp.complex64),
        jax.ShapeDtypeStruct((bucket, cfg.width) + cfg.grid, jnp.float32),
        jax.ShapeDtypeStruct(
            (bucket, cfg.in_channels - n_static) + cfg.grid, jnp.float32
        ),
    ).compile()
    text = compiled.as_text()
    a2a = re.findall(r"all-to-all\(.*op_name=\"([^\"]+)\"", text)
    assert a2a and all("/a2a/" in op for op in a2a), a2a
    kernels = [(n, op) for n, op in re.findall(
        r"(\S+) = .*custom-call\(.*op_name=\"([^\"]+)\"", text) if "pallas_call" in op]
    assert kernels and all(n.startswith("%spectral_fused.") and "/mix/" in op
                           for n, op in kernels), kernels
    _peak_fits(compiled, topo.devices[0])
