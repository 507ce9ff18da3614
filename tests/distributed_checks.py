"""Distributed-equivalence assertions, run under 8 simulated host devices.

Executed as a subprocess by test_distributed.py (the device-count flag must
be set before jax initializes, so this cannot run inside the main pytest
process, whose device count is environment-dependent).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import (
    FNOConfig, fno_forward, forward_and_specs, init_params, make_dist_forward,
    make_pipeline_forward, param_specs, params_with_planes,
    params_without_planes, repartition, repartition_chunked,
    ulysses_attention,
)
from repro.common.compat import shard_map
from repro.core.partition import make_mesh
from repro.core.ulysses import _dense_attention

CHECKS = []


def check(fn):
    CHECKS.append(fn)
    return fn


@check
def repartition_roundtrip_and_adjoint():
    mesh = make_mesh((8,), ("model",))
    x = jnp.arange(2 * 8 * 16, dtype=jnp.float32).reshape(2, 8, 16) + 1j * 3.0
    x = x.astype(jnp.complex64)

    def rt(a):
        b = repartition(a, src=1, dst=2, axis_name="model")
        return repartition(b, src=2, dst=1, axis_name="model")

    y = jax.jit(shard_map(rt, mesh, P(None, "model", None),
                          P(None, "model", None)))(x)
    assert bool(jnp.all(y == x)), "repartition roundtrip failed"

    # adjoint: <R x, y> == <x, R^T y>
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (2, 8, 16))
    b = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    fwd = jax.jit(shard_map(
        lambda t: repartition(t, 1, 2, "model"), mesh,
        P(None, "model", None), P(None, None, "model")))
    bwd = jax.jit(shard_map(
        lambda t: repartition(t, 2, 1, "model"), mesh,
        P(None, None, "model"), P(None, "model", None)))
    lhs = jnp.vdot(fwd(a), fwd(jnp.zeros_like(a)) * 0 + fwd(a) * 0 + fwd(b) * 0 + fwd(b))
    # simpler: <R a, R b> == <a, b> (R is orthogonal permutation)
    lhs = jnp.vdot(fwd(a), fwd(b))
    rhs = jnp.vdot(a, b)
    np.testing.assert_allclose(float(lhs), float(rhs), rtol=1e-5)
    # and R^T R == I
    np.testing.assert_allclose(np.asarray(bwd(fwd(a))), np.asarray(a), rtol=1e-6)


@check
def fno_dist_matches_serial():
    cfg = FNOConfig(grid=(16, 16, 8, 8), modes=(4, 4, 2, 3), width=6,
                    in_channels=2, out_channels=1, n_blocks=3, decoder_dim=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 16, 16, 8, 8))
    y_ser = jax.jit(lambda p, x: fno_forward(p, x, cfg))(params, x)
    mesh = make_mesh((2, 4), ("data", "model"))
    for variant in ("paper", "grady31"):
        fwd = make_dist_forward(mesh, cfg, dp_axes=("data",), variant=variant)
        y = jax.jit(fwd)(params, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ser), rtol=2e-4, atol=2e-5)
    # gradient equivalence through the distributed path
    g_ser = jax.jit(jax.grad(lambda p: jnp.mean(fno_forward(p, x, cfg) ** 2)))(params)
    fwd = make_dist_forward(mesh, cfg, dp_axes=("data",))
    g_dd = jax.jit(jax.grad(lambda p: jnp.mean(fwd(p, x) ** 2)))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-5),
        g_dd, g_ser,
    )


@check
def fno_dist_2d_pencil_matches_serial():
    """2-D pencil decomposition (2 data x 2 mx x 2 my) == serial oracle."""
    cfg = FNOConfig(grid=(16, 16, 8, 8), modes=(4, 4, 2, 3), width=6,
                    in_channels=2, out_channels=1, n_blocks=3, decoder_dim=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 16, 16, 8, 8))
    y_ser = jax.jit(lambda p, x: fno_forward(p, x, cfg))(params, x)
    mesh = make_mesh((2, 2, 2), ("data", "mx", "my"))
    for variant in ("paper", "eager"):
        fwd = make_dist_forward(mesh, cfg, dp_axes=("data",),
                                model_axis=("mx", "my"), variant=variant)
        y = jax.jit(fwd)(params, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ser), rtol=2e-4, atol=2e-5)
    # gradient equivalence through both all-to-alls
    g_ser = jax.jit(jax.grad(lambda p: jnp.mean(fno_forward(p, x, cfg) ** 2)))(params)
    fwd = make_dist_forward(mesh, cfg, dp_axes=("data",), model_axis=("mx", "my"))
    g_dd = jax.jit(jax.grad(lambda p: jnp.mean(fwd(p, x) ** 2)))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-5),
        g_dd, g_ser,
    )


@check
def pipeline_matches_serial():
    cfg = FNOConfig(grid=(16, 16, 8, 8), modes=(4, 4, 2, 3), width=6,
                    in_channels=1, out_channels=1, n_blocks=4, decoder_dim=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 1, 16, 16, 8, 8))
    y_ser = jax.jit(lambda p, x: fno_forward(p, x, cfg))(params, x)
    mesh = make_mesh((1, 4), ("data", "model"))
    pfwd = make_pipeline_forward(mesh, cfg, n_micro=2)
    y_pp = jax.jit(pfwd)(params, x)
    np.testing.assert_allclose(np.asarray(y_pp), np.asarray(y_ser), rtol=2e-4, atol=2e-5)


@check
def ulysses_matches_dense():
    mesh = make_mesh((8,), ("model",))
    b, s, h, kvh, d = 2, 32, 8, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kvh, d))
    v = jax.random.normal(ks[2], (b, s, kvh, d))
    ref = _dense_attention(q, k, v, causal=True, scale=None)
    fn = shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "model", causal=True),
        mesh,
        (P(None, "model"), P(None, "model"), P(None, "model")),
        P(None, "model"),
    )
    out = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)
    # GQA path (kvh not divisible by axis -> all-gather branch)
    k2 = k[:, :, :2]
    v2 = v[:, :, :2]
    ref2 = _dense_attention(q, k2, v2, causal=True, scale=None)
    fn2 = shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "model", causal=True),
        mesh,
        (P(None, "model"), P(None, "model"), P(None, "model")),
        P(None, "model"),
    )
    out2 = jax.jit(fn2)(q, k2, v2)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2), rtol=2e-4, atol=2e-5)


@check
def moe_a2a_matches_local():
    from repro.models.moe import MoEConfig, init_moe_params, moe_apply
    from repro.models.policy import LOCAL, ParallelPolicy

    moe = MoEConfig(n_experts=8, top_k=2, d_expert=16, n_shared=1,
                    capacity_factor=4.0)  # ample capacity -> no drops
    d = 32
    params = init_moe_params(jax.random.PRNGKey(0), d, moe)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, d))
    y_local, aux_local = jax.jit(lambda p, x: moe_apply(p, x, moe, LOCAL))(params, x)
    mesh = make_mesh((2, 4), ("data", "model"))
    policy = ParallelPolicy(mesh=mesh, dp_axes=("data",), model_axis="model")
    y_dist, aux_dist = jax.jit(lambda p, x: moe_apply(p, x, moe, policy))(params, x)
    np.testing.assert_allclose(np.asarray(y_dist), np.asarray(y_local), rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(float(aux_dist), float(aux_local), rtol=1e-3)


@check
def head_padding_exact():
    """attn_forward with n_heads %% P != 0 (zero-padded heads) == LOCAL."""
    import dataclasses
    from repro.configs import get_arch, reduced
    from repro.models import attention as attn_lib
    from repro.models.policy import LOCAL, ParallelPolicy

    cfg = dataclasses.replace(reduced(get_arch("qwen1.5-32b")), n_heads=6, kv_heads=6)
    p = attn_lib.init_attn_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, cfg.d_model))
    ref = jax.jit(lambda p, x: attn_lib.attn_forward(p, x, cfg, LOCAL))(p, x)
    mesh = make_mesh((1, 4), ("data", "model"))
    pol = ParallelPolicy(mesh=mesh, dp_axes=("data",), model_axis="model")
    out = jax.jit(lambda p, x: attn_lib.attn_forward(p, x, cfg, pol))(p, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-4)


@check
def dist_lm_loss_matches_local():
    """Full LM train loss: pjit on a 2x4 mesh == single-device (same params)."""
    from repro.configs import get_arch, reduced
    from repro.models import init_lm_params, lm_loss
    from repro.models.policy import LOCAL, ParallelPolicy

    for arch in ("chatglm3-6b", "deepseek-moe-16b"):
        cfg = reduced(get_arch(arch))
        params = init_lm_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)
        batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}
        loss_local, _ = jax.jit(lambda p, b: lm_loss(p, b, cfg, LOCAL))(params, batch)
        mesh = make_mesh((2, 4), ("data", "model"))
        pol = ParallelPolicy(mesh=mesh, dp_axes=("data",), model_axis="model", seq_shard=True)
        loss_dist, _ = jax.jit(lambda p, b: lm_loss(p, b, cfg, pol))(params, batch)
        np.testing.assert_allclose(float(loss_dist), float(loss_local), rtol=3e-3)


@check
def checkpoint_elastic_resharding():
    """Save on a (2,4) mesh, restore onto (4,2) and onto 1 device."""
    import tempfile
    from repro.train import checkpoint as ck

    mesh_a = make_mesh((2, 4), ("data", "model"))
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    xa = jax.device_put(x, NamedSharding(mesh_a, P("data", "model")))
    tree = {"w": xa, "b": jnp.ones((8,))}
    with tempfile.TemporaryDirectory() as d:
        ck.save(d, 3, tree)
        abstract = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
        mesh_b = make_mesh((4, 2), ("data", "model"))
        shardings = {
            "w": NamedSharding(mesh_b, P("model", "data")),
            "b": NamedSharding(mesh_b, P()),
        }
        restored, step, _ = ck.restore(d, abstract, shardings=shardings)
        assert step == 3
        np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(x))
        restored1, _, _ = ck.restore(d, abstract)
        np.testing.assert_array_equal(np.asarray(restored1["w"]), np.asarray(x))


@check
def compressed_allreduce_error_feedback():
    from repro.train.compression import compressed_psum_mean, init_error_state

    mesh = make_mesh((8,), ("data",))
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 256))

    def run(gs, ratio):
        def body(g_local, err_local):
            red, new_err = compressed_psum_mean(
                g_local[0], err_local[0], "data", ratio=ratio
            )
            return red, new_err[None]
        return jax.jit(shard_map(
            body, mesh, (P("data", None), P("data", None)),
            (P(None), P("data", None)),
        ))(gs, jnp.zeros((8, 256)))

    # ratio=1.0 -> lossless: equals dense mean
    red, err = run(g, 1.0)
    np.testing.assert_allclose(np.asarray(red), np.asarray(g.mean(0)), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(err), 0.0, atol=1e-6)
    # ratio<1: error feedback retains the residual exactly
    red2, err2 = run(g, 0.1)
    # reduced + mean(err) == dense mean (conservation)
    np.testing.assert_allclose(
        np.asarray(red2 + err2.mean(0)), np.asarray(g.mean(0)), rtol=1e-4, atol=1e-5
    )


@check
def repartition_chunked_bit_identical():
    """Channel-chunked repartition (the all-to-all overlap primitive) is
    pure data movement: bit-identical to the blocking repartition for any
    chunk count, divisible or not, clamped past the extent."""
    mesh = make_mesh((8,), ("model",))
    key = jax.random.PRNGKey(5)
    x = (jax.random.normal(key, (2, 6, 8, 16))
         + 1j * jax.random.normal(jax.random.PRNGKey(6), (2, 6, 8, 16))
         ).astype(jnp.complex64)
    spec_in, spec_out = P(None, None, "model", None), P(None, None, None, "model")
    base = jax.jit(shard_map(
        lambda t: repartition(t, 2, 3, "model"), mesh, spec_in, spec_out))(x)
    for chunks in (1, 2, 3, 6, 16):  # 3 non-divisible; 16 clamps to extent 6
        y = jax.jit(shard_map(
            lambda t, c=chunks: repartition_chunked(
                t, 2, 3, "model", chunks=c, chunk_dim=1),
            mesh, spec_in, spec_out))(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(base))


@check
def fno_comm_chunks_matches_unchunked():
    """comm_chunks>1 (channel-chunked all-to-alls through the whole dist
    FFT pipeline) == the unchunked forward; channels are a pure batch dim."""
    import dataclasses
    cfg = FNOConfig(grid=(16, 16, 8, 8), modes=(4, 4, 2, 3), width=6,
                    in_channels=2, out_channels=1, n_blocks=2, decoder_dim=8)
    cfg_ck = dataclasses.replace(cfg, comm_chunks=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 16, 16, 8, 8))
    mesh = make_mesh((2, 4), ("data", "model"))
    y0 = jax.jit(make_dist_forward(mesh, cfg, dp_axes=("data",)))(params, x)
    y2 = jax.jit(make_dist_forward(mesh, cfg_ck, dp_axes=("data",)))(params, x)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y0), rtol=1e-6, atol=1e-7)
    mesh2 = make_mesh((2, 2, 2), ("data", "mx", "my"))
    y0 = jax.jit(make_dist_forward(
        mesh2, cfg, dp_axes=("data",), model_axis=("mx", "my")))(params, x)
    y2 = jax.jit(make_dist_forward(
        mesh2, cfg_ck, dp_axes=("data",), model_axis=("mx", "my")))(params, x)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y0), rtol=1e-6, atol=1e-7)


@check
def fno_fused_pallas_matches_serial():
    """The ISSUE's gate: every use_pallas=True dist variant == the UNFUSED
    serial oracle to <= 1e-4, gradients included (interpret-mode kernels)."""
    import dataclasses
    cfg = FNOConfig(grid=(16, 16, 8, 8), modes=(4, 4, 2, 3), width=6,
                    in_channels=2, out_channels=1, n_blocks=2, decoder_dim=8,
                    use_pallas=True, comm_chunks=2)
    cfg_ref = dataclasses.replace(cfg, use_pallas=False, comm_chunks=1)
    params = init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 16, 16, 8, 8))
    y_ser = jax.jit(lambda p, x: fno_forward(p, x, cfg_ref))(params, x)

    # serial fused forward + grads
    y_f = jax.jit(lambda p, x: fno_forward(p, x, cfg))(params, x)
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_ser), rtol=1e-4, atol=1e-5)
    g_ser = jax.jit(jax.grad(lambda p: jnp.mean(fno_forward(p, x, cfg_ref) ** 2)))(params)
    g_f = jax.jit(jax.grad(lambda p: jnp.mean(fno_forward(p, x, cfg) ** 2)))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
        g_f, g_ser,
    )

    # every 1-D dist variant, fused, vs the serial oracle
    mesh = make_mesh((2, 4), ("data", "model"))
    for variant in ("paper", "eager", "grady31"):
        fwd = make_dist_forward(mesh, cfg, dp_axes=("data",), variant=variant)
        y = jax.jit(fwd)(params, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ser), rtol=1e-4, atol=1e-5)

    # 2-D pencils, fused
    mesh2 = make_mesh((2, 2, 2), ("data", "mx", "my"))
    for variant in ("paper", "eager"):
        fwd = make_dist_forward(mesh2, cfg, dp_axes=("data",),
                                model_axis=("mx", "my"), variant=variant)
        y = jax.jit(fwd)(params, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ser), rtol=1e-4, atol=1e-5)

    # gradient gate: fused dist vs unfused dist (tight) and vs serial
    fwd_f = make_dist_forward(mesh, cfg, dp_axes=("data",))
    fwd_u = make_dist_forward(mesh, cfg_ref, dp_axes=("data",))
    g_df = jax.jit(jax.grad(lambda p: jnp.mean(fwd_f(p, x) ** 2)))(params)
    g_du = jax.jit(jax.grad(lambda p: jnp.mean(fwd_u(p, x) ** 2)))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
        g_df, g_du,
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-5),
        g_df, g_ser,
    )
    # 2-D grads, fused vs serial
    fwd_f2 = make_dist_forward(mesh2, cfg, dp_axes=("data",), model_axis=("mx", "my"))
    g_df2 = jax.jit(jax.grad(lambda p: jnp.mean(fwd_f2(p, x) ** 2)))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-5),
        g_df2, g_ser,
    )


@check
def fno_deep_split_matches_serial():
    """The deep block-input split — a cached first-block kept-mode static
    contribution summed into the dynamic remainder's pre-activation — ==
    the UNFUSED serial oracle to <= 1e-4 through every serving layout:
    serial (unfused + fused), every 1-D dist variant, and 2-D pencils."""
    import dataclasses
    from repro.core import (
        encoder_prelift, fno_forward_deep_split, make_dist_forward_deep_split,
        spectral_prelift,
    )

    n_static = 1
    cfg = FNOConfig(grid=(16, 16, 8, 8), modes=(4, 4, 2, 3), width=6,
                    in_channels=2, out_channels=1, n_blocks=2, decoder_dim=8,
                    use_pallas=True, comm_chunks=2)
    cfg_ref = dataclasses.replace(cfg, use_pallas=False, comm_chunks=1)
    params = init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 16, 16, 8, 8))
    y_ser = jax.jit(lambda p, x: fno_forward(p, x, cfg_ref))(params, x)

    xd = x[:, n_static:]
    pre_s = encoder_prelift(params, x[:, :n_static], cfg, slice(0, n_static))
    _, contrib = spectral_prelift(params, pre_s, cfg_ref)

    # serial deep split: unfused, then fused Pallas
    for c in (cfg_ref, cfg):
        y = jax.jit(lambda p, ck, ps, xdyn, c=c: fno_forward_deep_split(
            p, ck, ps, xdyn, c, n_static))(params, contrib, pre_s, xd)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(y_ser), rtol=1e-4, atol=1e-5)

    # every 1-D dist variant, fused, contrib sharded along k_y
    mesh = make_mesh((2, 4), ("data", "model"))
    for variant in ("paper", "eager", "grady31"):
        fwd = make_dist_forward_deep_split(
            mesh, cfg, n_static, dp_axes=("data",), variant=variant)
        y = jax.jit(fwd)(params, contrib, pre_s, xd)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(y_ser), rtol=1e-4, atol=1e-5)

    # 2-D pencils, fused, contrib sharded along (k_y, k_z)
    mesh2 = make_mesh((2, 2, 2), ("data", "mx", "my"))
    for variant in ("paper", "eager"):
        fwd = make_dist_forward_deep_split(
            mesh2, cfg, n_static, dp_axes=("data",),
            model_axis=("mx", "my"), variant=variant)
        y = jax.jit(fwd)(params, contrib, pre_s, xd)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(y_ser), rtol=1e-4, atol=1e-5)


@check
def fno_planes_serving_forward_matches_serial():
    """The serving runner's layout: plane-cached params (w_spec_re/_im)
    through the fused dist forward == the serial oracle on complex params,
    and the planes round-trip (params_without_planes) is exact."""
    cfg = FNOConfig(grid=(16, 16, 8, 8), modes=(4, 4, 2, 3), width=6,
                    in_channels=2, out_channels=1, n_blocks=2, decoder_dim=8,
                    use_pallas=True, comm_chunks=2)
    import dataclasses
    cfg_ref = dataclasses.replace(cfg, use_pallas=False, comm_chunks=1)
    params = init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 16, 16, 8, 8))
    y_ser = jax.jit(lambda p, x: fno_forward(p, x, cfg_ref))(params, x)

    mesh = make_mesh((2, 4), ("data", "model"))
    fwd, x_spec, p_specs = forward_and_specs(
        mesh, cfg, dp_axes=("data",), model_axis="model", planes=True)
    pp = params_with_planes(params)
    assert "w_spec" not in pp["blocks"] and "w_spec_re" in pp["blocks"]
    y = jax.jit(fwd)(pp, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ser), rtol=1e-4, atol=1e-5)

    back = params_without_planes(pp)
    np.testing.assert_array_equal(
        np.asarray(back["blocks"]["w_spec"]), np.asarray(params["blocks"]["w_spec"]))


@check
def fno_runner_device_table_matches_uncached():
    """The serving runner on a (data 2 x model 2) mesh: served through its
    device table of static rows (held sharded along the model axis, the
    bucket stacked over data), outputs equal the same runner's uncached
    serving bit for bit, at both cache levels."""
    from repro.serve import FNORunner, GeomodelCache, ScenarioRequest, Scheduler

    cfg = FNOConfig(grid=(16, 16, 8, 8), modes=(4, 4, 2, 3), width=6,
                    in_channels=2, out_channels=1, n_blocks=2, decoder_dim=8,
                    use_pallas=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    geos = [rng.normal(size=(1,) + cfg.grid).astype(np.float32) for _ in range(2)]
    xs = [np.concatenate([geos[g], rng.normal(size=(1,) + cfg.grid).astype(np.float32)])
          for g in (0, 1, 0)]

    def serve(runner):
        sched = Scheduler(runner, 2)
        for i, x in enumerate(xs):
            sched.submit(ScenarioRequest(rid=i, x=x, steps=2))
        done = sorted(sched.run_until_done(max_steps=50), key=lambda r: r.rid)
        assert len(done) == len(xs)
        return [y for r in done for y in r.outputs]

    mesh = make_mesh((2, 2), ("data", "model"))
    for level in ("deep", "prelift"):
        runner = FNORunner(cfg, params, mesh=mesh, model_axis="model", max_slots=2,
                           n_static=1, cache=GeomodelCache(), cache_level=level)
        runner.warmup()
        warm = serve(runner)
        assert runner.resident_fills == 2 and runner.resident_hits == 4, level
        runner.cache = None
        for yw, yc in zip(warm, serve(runner)):
            np.testing.assert_array_equal(yw, yc)


def main():
    failed = []
    for fn in CHECKS:
        try:
            fn()
            print(f"PASS {fn.__name__}")
        except Exception as e:  # noqa: BLE001
            failed.append((fn.__name__, repr(e)))
            print(f"FAIL {fn.__name__}: {e!r}")
    if failed:
        sys.exit(1)
    print("ALL_DISTRIBUTED_CHECKS_PASSED")


if __name__ == "__main__":
    main()
