"""The one FNO forward factory: every mesh layout x cached prefix that
``forward_and_specs`` builds equals ``fno_forward`` on the concatenated
input, and keeps the program name a profile's op paths start with.

The meshes are one device each, so the slab and pencil forwards run their
shard_map'd transform pairs (all-to-alls over size-1 axes) in this
process; tests/distributed_checks.py runs them on real multi-device meshes.
"""
import jax
import numpy as np
import pytest

from repro.core import (
    FNOConfig, encoder_prelift, fno_forward, forward_and_specs, init_params,
    input_spec, spectral_prelift,
)
from repro.core.partition import make_mesh

N_STATIC = 1
CFG = FNOConfig(grid=(8, 8, 4, 4), modes=(2, 2, 1, 2), width=4, n_blocks=2,
                decoder_dim=4, in_channels=N_STATIC + 1)
PARAMS = init_params(jax.random.PRNGKey(0), CFG)
X = np.random.default_rng(0).normal(
    size=(2, CFG.in_channels) + CFG.grid).astype(np.float32)
WANT = np.asarray(jax.jit(lambda p, x: fno_forward(p, x, CFG))(PARAMS, X))

LAYOUTS = {
    "serial": ((1,), ("data",), None),
    "slab": ((1, 1), ("data", "model"), "model"),
    "pencil": ((1, 1, 1), ("data", "mx", "my"), ("mx", "my")),
}


def _cached(prefix):
    """The operands a prefix passes before the dynamic channels."""
    if prefix is None:
        return (), X
    pre_s = encoder_prelift(PARAMS, X[:, :N_STATIC], CFG, slice(0, N_STATIC))
    if prefix == "prelift":
        return (pre_s,), X[:, N_STATIC:]
    _, contrib = spectral_prelift(PARAMS, pre_s, CFG)
    return (contrib, pre_s), X[:, N_STATIC:]


@pytest.mark.parametrize("prefix", [None, "prelift", "deep"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_forward_and_specs_matches_fno_forward(layout, prefix):
    shape, axes, model_axis = LAYOUTS[layout]
    fwd, x_spec, _ = forward_and_specs(
        make_mesh(shape, axes), CFG, model_axis=model_axis,
        n_static=N_STATIC, prefix=prefix)
    assert x_spec == input_spec(("data",), model_axis)
    assert fwd.__name__ == ("forward" if model_axis is None else "shard_fwd")
    cached, x = _cached(prefix)
    got = jax.jit(fwd)(PARAMS, *cached, x)
    np.testing.assert_allclose(np.asarray(got), WANT, rtol=1e-4, atol=1e-5)
