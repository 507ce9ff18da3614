"""Fourier Neural Operator — serial oracle and model-parallel (paper Alg. 1/2).

Functional, pytree-parameterized. The *same parameter pytree* drives:
  * ``fno_forward``        — single-device oracle (rfftn over all 4 dims),
  * ``fno_forward_dist``   — paper Algorithm 1/2 (call inside shard_map,
                             X sharded along x, spectral weights along k_y),
  * ``fno_forward_dist_31``— Grady et al. [31] baseline schedule (truncation
                             AFTER the repartition; communication-heavy),
so distributed-vs-serial equivalence is testable to numerical precision.

Architecture (paper Alg. 1): 1x1-conv encoder -> n_blocks x [spectral conv
+ 1x1 bypass, GELU] -> 2-layer decoder. Spectral weights are complex64 and
dominate memory (as in the paper, where the FNO fills 80% of an 80GB A100).

Each layer runs under a ``jax.named_scope``, which a profile shows in the
op path of every device op: ``encoder``; ``blocks`` around the scan over
the blocks, and in each block ``fft_fwd`` (with its all-to-alls), ``mix``,
``fft_inv`` and ``bypass`` (1x1 conv, residual add, GELU); ``decoder``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.common import compat
from repro.core import dfft
from repro.core.dfft import BDIM, CDIM, XDIM, YDIM, ZDIM, TDIM
from repro.kernels.spectral_conv import (
    cached_weight_planes,
    spectral_apply,
    spectral_apply_fused,
    spectral_apply_fused_add,
    spectral_static_contribution,
)


@dataclasses.dataclass(frozen=True)
class FNOConfig:
    grid: Tuple[int, int, int, int]  # (nx, ny, nz, nt) of the solution tensor
    modes: Tuple[int, int, int, int]  # (mx, my, mz, mt); 2m kept per full dim
    width: int = 32
    in_channels: int = 1
    out_channels: int = 1
    n_blocks: int = 4
    decoder_dim: int = 128
    # Compute dtype for pointwise/conv ops; the FFT path is always f32.
    dtype: jnp.dtype = jnp.float32
    # Route the spectral conv through the fused Pallas kernel (truncate +
    # complex channel-mix + pad in one HBM pass); equivalence-gated against
    # the unfused path in tests/distributed_checks.py.
    use_pallas: bool = False
    # Channel-chunk the distributed FFT pipelines so each chunk's
    # all-to-all overlaps the next chunk's local FFTs (bit-identical; >1
    # only helps under a latency-hiding scheduler; unmeasured on a TPU).
    comm_chunks: int = 1
    remat: bool = True        # checkpoint each FNO block (A100-80GB -> v5e-16GB)

    @property
    def mode_shape(self) -> Tuple[int, int, int, int]:
        mx, my, mz, mt = self.modes
        return (2 * mx, 2 * my, 2 * mz, mt)

    def validate_for_parallelism(self, n_shards: int) -> None:
        nx = self.grid[0]
        two_my = 2 * self.modes[1]
        if nx % n_shards:
            raise ValueError(f"nx={nx} not divisible by {n_shards} shards")
        if two_my % n_shards:
            raise ValueError(f"2*my={two_my} not divisible by {n_shards} shards")
        self._validate_modes_fit()

    def validate_for_parallelism_2d(self, n_x: int, n_y: int) -> None:
        """Pencil decomposition: x sharded n_x ways, y sharded n_y ways.

        The two repartitions move the x-shard onto the truncated y dim and
        the y-shard onto the truncated z dim, hence the 2my/2mz constraints.
        """
        nx, ny = self.grid[0], self.grid[1]
        two_my, two_mz = 2 * self.modes[1], 2 * self.modes[2]
        if nx % n_x:
            raise ValueError(f"nx={nx} not divisible by {n_x} x-shards")
        if two_my % n_x:
            raise ValueError(f"2*my={two_my} not divisible by {n_x} x-shards")
        if ny % n_y:
            raise ValueError(f"ny={ny} not divisible by {n_y} y-shards")
        if two_mz % n_y:
            raise ValueError(f"2*mz={two_mz} not divisible by {n_y} y-shards")
        self._validate_modes_fit()

    def _validate_modes_fit(self) -> None:
        mx, my, mz, mt = self.modes
        nx, ny, nz, nt = self.grid
        if 2 * mx > nx or 2 * my > ny or 2 * mz > nz or mt > nt // 2 + 1:
            raise ValueError(f"modes {self.modes} exceed grid {self.grid}")


def init_params(key: jax.Array, cfg: FNOConfig) -> dict:
    """Initialize the FNO parameter pytree (block params stacked for scan)."""
    keys = jax.random.split(key, 6)
    w = cfg.width
    kshape = cfg.mode_shape
    scale = 1.0 / (w * w)
    spec_shape = (cfg.n_blocks, w, w) + kshape

    def uniform(k, shape, scale, dtype=jnp.float32):
        return jax.random.uniform(k, shape, dtype, -1.0, 1.0) * scale

    kr, ki = jax.random.split(keys[2])
    return {
        "encoder": {
            "w": uniform(keys[0], (cfg.in_channels, w), (1.0 / cfg.in_channels) ** 0.5),
            "b": jnp.zeros((w,), jnp.float32),
        },
        "blocks": {
            # complex64 spectral weights, the memory-dominant tensor
            "w_spec": (
                uniform(kr, spec_shape, scale) + 1j * uniform(ki, spec_shape, scale)
            ).astype(jnp.complex64),
            "w_bypass": uniform(keys[3], (cfg.n_blocks, w, w), (1.0 / w) ** 0.5),
            "b_bypass": jnp.zeros((cfg.n_blocks, w), jnp.float32),
        },
        "decoder": {
            "w1": uniform(keys[4], (w, cfg.decoder_dim), (1.0 / w) ** 0.5),
            "b1": jnp.zeros((cfg.decoder_dim,), jnp.float32),
            "w2": uniform(keys[5], (cfg.decoder_dim, cfg.out_channels), (1.0 / cfg.decoder_dim) ** 0.5),
            "b2": jnp.zeros((cfg.out_channels,), jnp.float32),
        },
    }


def param_specs(mesh: Mesh, model_axis="model", *, planes: bool = False) -> dict:
    """PartitionSpecs: spectral weights sharded along k_y (paper Alg. 2);
    encoder/decoder/bypass replicated (the paper's broadcast B).

    ``model_axis`` may be a single axis name (1-D: shard k_y), a pair
    (2-D pencil: shard k_y by the x-mesh axis and k_z by the y-mesh axis —
    the dims each shard lands on after the pencil forward's repartitions),
    or None (pure data parallelism: everything replicated).

    ``planes=True`` describes the plane-cached params tree
    (``params_with_planes``): ``w_spec`` replaced by float32
    ``w_spec_re``/``w_spec_im`` leaves. The planes keep the mode dims
    unflattened, so they take the SAME spec as the complex original.
    """
    del mesh
    if model_axis is None:
        w_spec = P()
    elif isinstance(model_axis, (tuple, list)):
        ax_x, ax_y = model_axis
        w_spec = P(None, None, None, None, ax_x, ax_y, None)
    else:
        # [n_blocks, ci, co, kx, ky, kz, kt] -> shard ky
        w_spec = P(None, None, None, None, model_axis, None, None)
    if planes:
        spec_leaves = {"w_spec_re": w_spec, "w_spec_im": w_spec}
    else:
        spec_leaves = {"w_spec": w_spec}
    return {
        "encoder": {"w": P(), "b": P()},
        "blocks": {
            **spec_leaves,
            "w_bypass": P(),
            "b_bypass": P(),
        },
        "decoder": {"w1": P(), "b1": P(), "w2": P(), "b2": P()},
    }


def params_with_planes(params: dict) -> dict:
    """Replace the complex ``w_spec`` with cached float32 re/im planes.

    For frozen params (serving): the re/im split the Pallas kernels need
    is computed ONCE per checkpoint (via the weight-plane cache) instead
    of once per block per rollout step, and the complex original is
    dropped from the tree so device memory is not doubled. The planes
    shard with the same PartitionSpecs (``param_specs(..., planes=True)``).
    """
    blocks = dict(params["blocks"])
    if "w_spec" not in blocks:
        return params
    w = blocks.pop("w_spec")
    wr, wi = cached_weight_planes(w)
    blocks["w_spec_re"] = wr
    blocks["w_spec_im"] = wi
    return {**params, "blocks": blocks}


def params_without_planes(params: dict) -> dict:
    """Inverse of ``params_with_planes``: recombine planes to complex
    ``w_spec`` (used by the serving --verify oracle, which replays through
    the plain serial forward)."""
    blocks = dict(params["blocks"])
    if "w_spec" in blocks:
        return params
    wr = blocks.pop("w_spec_re")
    wi = blocks.pop("w_spec_im")
    blocks["w_spec"] = wr + 1j * wi
    return {**params, "blocks": blocks}


def _block_weights(blk: dict):
    """Per-block spectral weights from a scan slice of params['blocks']:
    the complex ``w_spec`` or, for plane-cached params, the (re, im)
    tuple both ``spectral_apply`` and ``spectral_apply_fused`` accept."""
    if "w_spec" in blk:
        return blk["w_spec"]
    return (blk["w_spec_re"], blk["w_spec_im"])


# The model computes in float32: on a TPU a matmul's default precision is
# one bf16 pass, so every contraction asks for full float32 explicitly.
F32 = jax.lax.Precision.HIGHEST


def _conv1x1(x: jax.Array, w: jax.Array, b: Optional[jax.Array]) -> jax.Array:
    """Channel-mixing 1x1 conv on [b, c, x, y, z, t]."""
    y = jnp.einsum("bixyzt,io->boxyzt", x, w.astype(x.dtype), precision=F32)
    if b is not None:
        y = y + b.astype(x.dtype)[None, :, None, None, None, None]
    return y


@jax.named_scope("encoder")
def _encoder(params: dict, x: jax.Array, cfg: FNOConfig) -> jax.Array:
    x = x.astype(cfg.dtype)
    return jax.nn.gelu(_conv1x1(x, params["encoder"]["w"], params["encoder"]["b"]))


def encoder_prelift(params: dict, x: jax.Array, cfg: FNOConfig, channels=None) -> jax.Array:
    """Partial pre-activation lift of a channel SLICE of the input.

    The encoder's 1x1 conv is linear in x, so the lift of the static
    (geomodel) channels and the lift of the dynamic (well/state) channels
    can be computed independently and summed before bias + GELU. This is
    what lets serving cache the static-channel lift across requests and
    rollout steps (``serve.geomodel_cache``): precompute
    ``encoder_prelift(params, x_static, cfg, slice(0, n_static))`` once per
    geomodel, then only the dynamic slice is lifted per request.

    ``x``: [b, c_sub, nx, ny, nz, nt] where c_sub matches ``channels``
    (a slice into ``in_channels``; default: all). Returns the
    pre-activation partial sum [b, width, ...] — no bias, no GELU.
    """
    w = params["encoder"]["w"]
    if channels is not None:
        w = w[channels]
    x = x.astype(cfg.dtype)
    return jnp.einsum("bixyzt,io->boxyzt", x, w.astype(x.dtype), precision=F32)


def _encoder_from_prelift(params: dict, pre: jax.Array, cfg: FNOConfig) -> jax.Array:
    """bias + GELU over a (summed) pre-activation lift."""
    b = params["encoder"]["b"].astype(pre.dtype)
    return jax.nn.gelu(pre + b[None, :, None, None, None, None])


@jax.named_scope("decoder")
def _decoder(params: dict, x: jax.Array, cfg: FNOConfig) -> jax.Array:
    d = params["decoder"]
    h = jax.nn.gelu(_conv1x1(x, d["w1"], d["b1"]))
    out = _conv1x1(h, d["w2"], d["b2"])
    return out.astype(jnp.float32)


def _bypass(x, w_b, b_b):
    return _conv1x1(x, w_b, b_b)


@jax.named_scope("encoder")
def _split_lift(params: dict, pre_static: jax.Array, x_dyn: jax.Array,
                cfg: FNOConfig, n_static: int) -> jax.Array:
    """First hidden state from a cached static-channel prelift and the
    normalized dynamic channels, lifted here."""
    pre = pre_static.astype(cfg.dtype) + encoder_prelift(
        params, x_dyn, cfg, slice(n_static, None)
    )
    return _encoder_from_prelift(params, pre, cfg)


def _spectral_block(x, w_spec, w_b, b_b, cfg: FNOConfig, forward, adjoint,
                    kernel_dims, t_out=None, *, add_kept=None, bypass_x=None):
    """One FNO block from its transform pair, each stage under its own
    named scope (``fft_fwd``, ``mix``, ``fft_inv``, ``bypass``), so that
    a profile gives every op of a block to a stage.

    ``forward(x, fused)`` transforms and truncates; with ``fused`` it
    leaves the dims the fused kernel truncates itself (``kernel_dims``,
    and t when ``t_out`` is given) at full size, and ``adjoint(yf,
    fused)`` then skips padding them. ``add_kept`` / ``bypass_x`` as in
    ``fno_block``.
    """
    with jax.named_scope("fft_fwd"):
        xf = forward(x, cfg.use_pallas)
    with jax.named_scope("mix"):
        if not cfg.use_pallas:
            yf = spectral_apply(xf, w_spec, use_pallas=False)
            if add_kept is not None:
                yf = yf + add_kept.astype(yf.dtype)
        elif add_kept is None:
            yf = spectral_apply_fused(xf, w_spec, kernel_dims, t_out=t_out)
        else:
            yf = spectral_apply_fused_add(
                xf, w_spec, add_kept, kernel_dims, t_out=t_out
            )
    with jax.named_scope("fft_inv"):
        y = adjoint(yf, cfg.use_pallas)
    with jax.named_scope("bypass"):
        xb = x if bypass_x is None else bypass_x
        return jax.nn.gelu(y + _bypass(xb, w_b, b_b))


def _x_fused_block(forward, adjoint, x, w_spec, w_b, b_b, cfg: FNOConfig,
                   add_kept, bypass_x, *mesh):
    """A block whose transforms truncate y, z and t (on a mesh, before the
    repartition); the fused kernel truncates (and pads) only x. ``mesh``:
    a distributed pair's axis name(s), passed to both transforms with
    ``cfg.comm_chunks``; empty for the serial pair."""
    comm = {"comm_chunks": cfg.comm_chunks} if mesh else {}
    return _spectral_block(
        x, w_spec, w_b, b_b, cfg,
        lambda x, fused: forward(
            x, cfg.modes, *mesh, trunc_x=not fused, **comm
        ),
        lambda yf, fused: adjoint(
            yf, cfg.grid, *mesh, out_dtype=cfg.dtype, pad_x=not fused, **comm
        ),
        (cfg.grid[0], None, None), add_kept=add_kept, bypass_x=bypass_x,
    )


# ---------------------------------------------------------------------------
# Serial oracle.
# ---------------------------------------------------------------------------

def fno_block(x, w_spec, w_b, b_b, cfg: FNOConfig, *, add_kept=None, bypass_x=None):
    """Serial FNO block: irfftn(pad(W . trunc(rfftn(x)))) + bypass, GELU.

    The transforms truncate each dim right after its own FFT (and pad it
    right before its own inverse). With ``use_pallas`` the fused kernel
    does S_x / W· / S_x^T, so x crosses HBM at full size only there.

    Deep-split serving (``fno_forward_deep_split``) passes ``add_kept``, a
    cached kept-mode contribution summed into the spectral output before
    the inverse transform, and ``bypass_x``, the full activation the 1x1
    bypass runs on when ``x`` is only the dynamic remainder.
    """
    return _x_fused_block(dfft.serial_forward, dfft.serial_adjoint, x, w_spec,
                          w_b, b_b, cfg, add_kept, bypass_x)


def _run_blocks(params: dict, h: jax.Array, cfg: FNOConfig, block_apply):
    """Shared tail of every forward: scan the FNO blocks, then decode.
    ``block_apply(h, blk)`` applies one block's params to the hidden state."""

    def body(h, blk):
        return block_apply(h, blk), None

    if cfg.remat:
        body = jax.checkpoint(body)
    with jax.named_scope("blocks"):
        h, _ = jax.lax.scan(body, h, params["blocks"])
    return _decoder(params, h, cfg)


def fno_forward(params: dict, x: jax.Array, cfg: FNOConfig) -> jax.Array:
    """Single-device forward. x: [b, c_in, nx, ny, nz, nt] -> [b, c_out, ...]."""
    h = _encoder(params, x, cfg)
    return _run_blocks(
        params, h, cfg,
        lambda h, blk: fno_block(h, _block_weights(blk), blk["w_bypass"], blk["b_bypass"], cfg),
    )


def fno_forward_split(
    params: dict, pre_static: jax.Array, x_dyn: jax.Array, cfg: FNOConfig, n_static: int
) -> jax.Array:
    """Single-device forward from a precomputed static-channel prelift.

    ``pre_static``: [b, width, ...] — the cached partial lift of the first
    ``n_static`` input channels (``encoder_prelift`` over the NORMALIZED
    static channels). ``x_dyn``: [b, in_channels - n_static, ...] — the
    normalized dynamic channels, lifted here. Equal to ``fno_forward`` on
    the concatenated input up to float-summation order (the cold and warm
    cache paths both go through THIS function, so they are bit-identical
    to each other).
    """
    h = _split_lift(params, pre_static, x_dyn, cfg, n_static)
    return _run_blocks(
        params, h, cfg,
        lambda h, blk: fno_block(h, _block_weights(blk), blk["w_bypass"], blk["b_bypass"], cfg),
    )


def spectral_prelift(params: dict, pre_static: jax.Array, cfg: FNOConfig, *, block: int = 0):
    """Static prefix of the FIRST spectral block, computed once per geomodel.

    The block-input split: write the first hidden state as
    ``h = h_static + h_rem`` with ``h_static = GELU(pre_static + b)`` a pure
    function of the cached static-channel prelift. FFT -> truncate -> mix is
    linear, so block 0's kept-mode output is
    ``W . S(h_rem)  +  W . S(h_static)`` — and the second term (and its
    spectrum) can be cached alongside the prelift and summed into the
    dynamic remainder's pre-activation on every warm request
    (``fno_forward_deep_split``). The nonlinearity after block 0 stops the
    split from going deeper.

    ``pre_static``: [b, width, nx, ny, nz, nt] (or unbatched [width, ...]).
    Returns ``(spectra, contribution)``: the truncated kept-mode spectrum
    S(h_static) [.., width, 2mx, 2my, 2mz, mt] and the weight-mixed
    contribution W_block . S(h_static) of the same shape — cache levels L3
    and L4 of ``serve.geomodel_cache``.
    """
    unbatched = pre_static.ndim == 5
    if unbatched:
        pre_static = pre_static[None]
    h_s = _encoder_from_prelift(params, pre_static.astype(cfg.dtype), cfg)
    spectra = dfft.serial_forward(h_s, cfg.modes)
    blk = jax.tree.map(lambda a: a[block], params["blocks"])
    contrib = spectral_static_contribution(spectra, _block_weights(blk))
    if unbatched:
        spectra, contrib = spectra[0], contrib[0]
    return spectra, contrib


def _fno_forward_deep_impl(params, pre_static, x_dyn, cfg, n_static, block_first, block_rest):
    """Shared deep-split body: rebuild the full first hidden state, run
    block 0 on the dynamic REMAINDER ``h - h_static`` (its static kept-mode
    term arrives precomputed via ``block_first``'s closure), then the
    remaining blocks unchanged."""
    h_full = _split_lift(params, pre_static, x_dyn, cfg, n_static)
    with jax.named_scope("encoder"):
        h_static = _encoder_from_prelift(params, pre_static.astype(cfg.dtype), cfg)
        h_rem = h_full - h_static
    blocks = params["blocks"]
    with jax.named_scope("blocks"):
        blk0 = jax.tree.map(lambda a: a[0], blocks)
        h = block_first(h_rem, blk0, h_full)
        rest = {**params, "blocks": jax.tree.map(lambda a: a[1:], blocks)}
    return _run_blocks(rest, h, cfg, block_rest)


def fno_forward_deep_split(
    params: dict,
    contrib: jax.Array,
    pre_static: jax.Array,
    x_dyn: jax.Array,
    cfg: FNOConfig,
    n_static: int,
) -> jax.Array:
    """Single-device forward from a cached prelift AND a cached first-block
    static contribution (``spectral_prelift``).

    ``contrib``: [b, width, 2mx, 2my, 2mz, mt] complex — the kept-mode
    static contribution ``W_0 . S(h_static)``. Mathematically equal to
    ``fno_forward_split`` (hence ``fno_forward``) up to float-summation
    order; cold and warm cache paths both go through THIS function with
    identical host-computed operands, so they are bit-identical to each
    other.
    """
    ck = contrib.astype(jnp.complex64)

    def first(h_rem, blk, h_full):
        return fno_block(
            h_rem, _block_weights(blk), blk["w_bypass"], blk["b_bypass"], cfg,
            add_kept=ck, bypass_x=h_full,
        )

    def rest(h, blk):
        return fno_block(
            h, _block_weights(blk), blk["w_bypass"], blk["b_bypass"], cfg
        )

    return _fno_forward_deep_impl(
        params, pre_static, x_dyn, cfg, n_static, first, rest
    )


# ---------------------------------------------------------------------------
# Distributed forward (paper Algorithm 1 + 2). Call INSIDE shard_map with:
#   x       sharded P(dp_axes, None, model_axis, None, None, None)
#   w_spec  sharded P(None, None, None, None, model_axis, None, None)
#   everything else replicated.
# ---------------------------------------------------------------------------

def fno_block_dist(x, w_spec, w_b, b_b, cfg: FNOConfig, axis_name: str,
                   *, add_kept=None, bypass_x=None):
    """Paper Alg. 2: local F/S over yzt, R_{x->y}, F/S over x, local spectral
    multiply (weights pre-sharded along k_y), adjoint path back.

    Fused path: y/z/t are truncated before the repartition as always (the
    paper's comm optimization), but S_x / S_x^T move into the kernel —
    the only dims still full-size at the kernel are the post-repartition
    x extent, exactly the three extra HBM passes the fusion removes.

    ``add_kept`` is the LOCAL shard of a cached kept-mode contribution
    ([b, co, 2mx, 2my/P, 2mz, mt] — same k_y sharding as ``w_spec``, see
    ``contrib_spec``); ``bypass_x`` as in ``fno_block``. The all-to-alls
    fall under the ``fft_fwd`` and ``fft_inv`` scopes.
    """
    return _x_fused_block(dfft.dist_forward, dfft.dist_adjoint, x, w_spec,
                          w_b, b_b, cfg, add_kept, bypass_x, axis_name)


def fno_block_dist_31(x, w_spec, w_b, b_b, cfg: FNOConfig, axis_name: str,
                      *, add_kept=None, bypass_x=None):
    """Grady et al. [31] schedule: repartition the UNtruncated spectrum."""
    nx, ny, nz, nt = cfg.grid
    c = cfg.comm_chunks
    return _spectral_block(
        x, w_spec, w_b, b_b, cfg,
        lambda x, fused: dfft.dist_forward_untruncated(
            x, cfg.modes, axis_name, trunc_xzt=not fused, comm_chunks=c
        ),
        lambda yf, fused: dfft.dist_adjoint_untruncated(
            yf, cfg.grid, axis_name, out_dtype=cfg.dtype, pad_xzt=not fused,
            comm_chunks=c,
        ),
        (nx, None, nz), nt // 2 + 1, add_kept=add_kept, bypass_x=bypass_x,
    )


def fno_block_dist_eager(x, w_spec, w_b, b_b, cfg: FNOConfig, axis_name: str,
                         *, add_kept=None, bypass_x=None):
    """Beyond-paper: per-dim eager truncation (bit-equivalent, cheaper FFTs)."""
    return _x_fused_block(dfft.dist_forward_eager, dfft.dist_adjoint_eager, x,
                          w_spec, w_b, b_b, cfg, add_kept, bypass_x, axis_name)


def fno_block_dist_2d(x, w_spec, w_b, b_b, cfg: FNOConfig, axis_names,
                      *, add_kept=None, bypass_x=None):
    """2-D pencil block: x sharded along both x and y, spectral weights
    sharded along k_y x k_z (matching dist_forward_2d's output layout)."""
    return _x_fused_block(dfft.dist_forward_2d, dfft.dist_adjoint_2d, x,
                          w_spec, w_b, b_b, cfg, add_kept, bypass_x, axis_names)


def fno_block_dist_2d_eager(x, w_spec, w_b, b_b, cfg: FNOConfig, axis_names,
                            *, add_kept=None, bypass_x=None):
    """2-D pencil block with per-dim eager truncation."""
    return _x_fused_block(dfft.dist_forward_2d_eager, dfft.dist_adjoint_2d_eager,
                          x, w_spec, w_b, b_b, cfg, add_kept, bypass_x, axis_names)


def _fno_forward_dist_impl(params, x, cfg, axis_name, block_fn):
    # Encoder/decoder weights are replicated (paper's broadcast B); the
    # convs contract channels only, so they are embarrassingly parallel
    # over the sharded x dim (paper Alg. 1).
    h = _encoder(params, x, cfg)
    return _run_blocks(
        params, h, cfg,
        lambda h, blk: block_fn(
            h, _block_weights(blk), blk["w_bypass"], blk["b_bypass"], cfg, axis_name
        ),
    )


def _fno_forward_dist_split_impl(params, pre_static, x_dyn, cfg, n_static, axis_name, block_fn):
    # Split-encoder distributed forward: the prelift add and the dynamic
    # channel contraction are pointwise over the sharded spatial dims, so
    # they need no communication — only the blocks do (as in the fused path).
    h = _split_lift(params, pre_static, x_dyn, cfg, n_static)
    return _run_blocks(
        params, h, cfg,
        lambda h, blk: block_fn(
            h, _block_weights(blk), blk["w_bypass"], blk["b_bypass"], cfg, axis_name
        ),
    )


def fno_forward_dist(params, x, cfg: FNOConfig, axis_name: str = "model"):
    return _fno_forward_dist_impl(params, x, cfg, axis_name, fno_block_dist)


def fno_forward_dist_31(params, x, cfg: FNOConfig, axis_name: str = "model"):
    return _fno_forward_dist_impl(params, x, cfg, axis_name, fno_block_dist_31)


def fno_forward_dist_eager(params, x, cfg: FNOConfig, axis_name: str = "model"):
    return _fno_forward_dist_impl(params, x, cfg, axis_name, fno_block_dist_eager)


def fno_forward_dist_2d(params, x, cfg: FNOConfig, axis_names=("mx", "my")):
    return _fno_forward_dist_impl(params, x, cfg, tuple(axis_names), fno_block_dist_2d)


def fno_forward_dist_2d_eager(params, x, cfg: FNOConfig, axis_names=("mx", "my")):
    return _fno_forward_dist_impl(
        params, x, cfg, tuple(axis_names), fno_block_dist_2d_eager
    )


_VARIANTS = {
    "paper": fno_forward_dist,
    "grady31": fno_forward_dist_31,
    "eager": fno_forward_dist_eager,
}

_VARIANTS_2D = {
    "paper": fno_forward_dist_2d,
    "eager": fno_forward_dist_2d_eager,
}

_BLOCKS = {
    "paper": fno_block_dist,
    "grady31": fno_block_dist_31,
    "eager": fno_block_dist_eager,
}

_BLOCKS_2D = {
    "paper": fno_block_dist_2d,
    "eager": fno_block_dist_2d_eager,
}


def input_spec(dp_axes, model_axis) -> P:
    """PartitionSpec of the solution tensor [b, c, x, y, z, t]: batch over
    the data axes, x (and y, for a pencil pair) over the model axes. The
    single source of truth for make_dist_forward's in/out layout — reuse it
    wherever explicit in_shardings must match the shard_map'd forward.
    ``model_axis=None`` shards the batch dim only (pure data parallelism)."""
    if model_axis is None:
        return P(dp_axes, None, None, None, None, None)
    if isinstance(model_axis, (tuple, list)):
        ax_x, ax_y = model_axis
        return P(dp_axes, None, ax_x, ax_y, None, None)
    return P(dp_axes, None, model_axis, None, None, None)


def make_dist_forward(
    mesh: Mesh,
    cfg: FNOConfig,
    *,
    dp_axes=("data",),
    model_axis="model",
    variant: str = "paper",
    planes: bool = False,
):
    """Build the shard_map'd distributed forward for a mesh.

    ``model_axis``: a single mesh-axis name shards the solution along x
    (paper Alg. 2); a PAIR of names, e.g. ``("mx", "my")``, selects the 2-D
    pencil decomposition (x sharded by the first axis, y by the second),
    lifting the 1-D parallelism cap from nx/2mx to (nx/2mx)*(ny/2my).

    variant: "paper" (truncate-then-repartition), "grady31" (the [31]
    baseline, 1-D only), or "eager" (beyond-paper per-dim truncation).

    ``planes=True``: the params tree carries plane-cached spectral weights
    (``params_with_planes``) — the shard_map in_specs must match that tree.
    """
    if isinstance(model_axis, (tuple, list)):
        model_axes = tuple(model_axis)
        if len(model_axes) != 2:
            raise ValueError(f"expected 2 model axes, got {model_axes}")
        cfg.validate_for_parallelism_2d(*(mesh.shape[a] for a in model_axes))
        if variant not in _VARIANTS_2D:
            raise ValueError(
                f"variant {variant!r} has no 2-D schedule; pick from "
                f"{sorted(_VARIANTS_2D)}"
            )
        fwd = _VARIANTS_2D[variant]
        x_spec = input_spec(dp_axes, model_axes)
        p_specs = param_specs(mesh, model_axes, planes=planes)

        def shard_fwd(params, x):
            return fwd(params, x, cfg, model_axes)

    else:
        cfg.validate_for_parallelism(mesh.shape[model_axis])
        fwd = _VARIANTS[variant]
        x_spec = input_spec(dp_axes, model_axis)
        p_specs = param_specs(mesh, model_axis, planes=planes)

        def shard_fwd(params, x):
            return fwd(params, x, cfg, model_axis)

    return compat.shard_map(
        shard_fwd, mesh, (p_specs, x_spec), x_spec
    )


def make_dist_forward_split(
    mesh: Mesh,
    cfg: FNOConfig,
    n_static: int,
    *,
    dp_axes=("data",),
    model_axis="model",
    variant: str = "paper",
    planes: bool = False,
):
    """shard_map'd distributed forward taking (params, pre_static, x_dyn).

    ``pre_static`` [b, width, ...] and ``x_dyn`` [b, c_dyn, ...] share the
    solution tensor's layout (``input_spec``): the channel dim is never
    sharded, so the same spec covers both. See ``fno_forward_split``.
    """
    if isinstance(model_axis, (tuple, list)):
        model_axes = tuple(model_axis)
        if len(model_axes) != 2:
            raise ValueError(f"expected 2 model axes, got {model_axes}")
        cfg.validate_for_parallelism_2d(*(mesh.shape[a] for a in model_axes))
        if variant not in _BLOCKS_2D:
            raise ValueError(
                f"variant {variant!r} has no 2-D schedule; pick from "
                f"{sorted(_BLOCKS_2D)}"
            )
        block_fn, axis = _BLOCKS_2D[variant], model_axes
        x_spec = input_spec(dp_axes, model_axes)
        p_specs = param_specs(mesh, model_axes, planes=planes)
    else:
        cfg.validate_for_parallelism(mesh.shape[model_axis])
        block_fn, axis = _BLOCKS[variant], model_axis
        x_spec = input_spec(dp_axes, model_axis)
        p_specs = param_specs(mesh, model_axis, planes=planes)

    def shard_fwd(params, pre_static, x_dyn):
        return _fno_forward_dist_split_impl(
            params, pre_static, x_dyn, cfg, n_static, axis, block_fn
        )

    return compat.shard_map(
        shard_fwd, mesh, (p_specs, x_spec, x_spec), x_spec
    )


def contrib_spec(dp_axes, model_axis) -> P:
    """PartitionSpec of the cached kept-mode contribution
    [b, co, 2mx, 2my, 2mz, mt]: batch over the data axes, k_y over the
    model axis (matching ``w_spec``'s sharding, since the contribution is a
    per-mode product with it) — and k_z over the second axis of a pencil
    pair. ``model_axis=None`` shards the batch dim only."""
    if model_axis is None:
        return P(dp_axes, None, None, None, None, None)
    if isinstance(model_axis, (tuple, list)):
        ax_x, ax_y = model_axis
        return P(dp_axes, None, None, ax_x, ax_y, None)
    return P(dp_axes, None, None, model_axis, None, None)


def make_dist_forward_deep_split(
    mesh: Mesh,
    cfg: FNOConfig,
    n_static: int,
    *,
    dp_axes=("data",),
    model_axis="model",
    variant: str = "paper",
    planes: bool = False,
):
    """shard_map'd distributed forward taking
    ``(params, contrib, pre_static, x_dyn)``.

    ``contrib`` is the GLOBAL [b, width, 2mx, 2my, 2mz, mt] kept-mode
    static contribution (``spectral_prelift``), sharded per
    ``contrib_spec`` so each shard holds exactly the k_y (x k_z) modes its
    ``w_spec`` shard would have produced. See ``fno_forward_deep_split``.
    """
    if isinstance(model_axis, (tuple, list)):
        model_axes = tuple(model_axis)
        if len(model_axes) != 2:
            raise ValueError(f"expected 2 model axes, got {model_axes}")
        cfg.validate_for_parallelism_2d(*(mesh.shape[a] for a in model_axes))
        if variant not in _BLOCKS_2D:
            raise ValueError(
                f"variant {variant!r} has no 2-D schedule; pick from "
                f"{sorted(_BLOCKS_2D)}"
            )
        block_fn, axis = _BLOCKS_2D[variant], model_axes
        x_spec = input_spec(dp_axes, model_axes)
        c_spec = contrib_spec(dp_axes, model_axes)
        p_specs = param_specs(mesh, model_axes, planes=planes)
    else:
        cfg.validate_for_parallelism(mesh.shape[model_axis])
        block_fn, axis = _BLOCKS[variant], model_axis
        x_spec = input_spec(dp_axes, model_axis)
        c_spec = contrib_spec(dp_axes, model_axis)
        p_specs = param_specs(mesh, model_axis, planes=planes)

    def shard_fwd(params, contrib, pre_static, x_dyn):
        ck = contrib.astype(jnp.complex64)

        def first(h_rem, blk, h_full):
            return block_fn(
                h_rem, _block_weights(blk), blk["w_bypass"], blk["b_bypass"],
                cfg, axis, add_kept=ck, bypass_x=h_full,
            )

        def rest(h, blk):
            return block_fn(
                h, _block_weights(blk), blk["w_bypass"], blk["b_bypass"],
                cfg, axis,
            )

        return _fno_forward_deep_impl(
            params, pre_static, x_dyn, cfg, n_static, first, rest
        )

    return compat.shard_map(
        shard_fwd, mesh, (p_specs, c_spec, x_spec, x_spec), x_spec
    )


def deep_split_forward_and_specs(
    mesh: Mesh,
    cfg: FNOConfig,
    n_static: int,
    *,
    dp_axes=("data",),
    model_axis=None,
    variant: str = "paper",
    planes: bool = False,
):
    """``split_forward_and_specs`` for the deep (first-block) split: the
    returned ``forward(params, contrib, pre_static, x_dyn)`` additionally
    consumes the cached kept-mode static contribution. Returns
    ``(forward, x_spec, c_spec, p_specs)`` — ``c_spec`` is the
    contribution's layout (``contrib_spec``)."""
    x_spec = input_spec(dp_axes, model_axis)
    c_spec = contrib_spec(dp_axes, model_axis)
    p_specs = param_specs(mesh, model_axis, planes=planes)
    if model_axis is None:
        def forward(params, contrib, pre_static, x_dyn):
            return fno_forward_deep_split(
                params, contrib, pre_static, x_dyn, cfg, n_static
            )
    else:
        forward = make_dist_forward_deep_split(
            mesh, cfg, n_static, dp_axes=dp_axes, model_axis=model_axis,
            variant=variant, planes=planes,
        )
    return forward, x_spec, c_spec, p_specs


def split_forward_and_specs(
    mesh: Mesh,
    cfg: FNOConfig,
    n_static: int,
    *,
    dp_axes=("data",),
    model_axis=None,
    variant: str = "paper",
    planes: bool = False,
):
    """``forward_and_specs`` for the split encoder: the returned
    ``forward(params, pre_static, x_dyn)`` consumes a precomputed (cached)
    static-channel prelift plus the normalized dynamic channels. Layouts
    are identical to the fused path (channel dim unsharded), so the same
    ``x_spec`` serves both operands.
    """
    x_spec = input_spec(dp_axes, model_axis)
    p_specs = param_specs(mesh, model_axis, planes=planes)
    if model_axis is None:
        def forward(params, pre_static, x_dyn):
            return fno_forward_split(params, pre_static, x_dyn, cfg, n_static)
    else:
        forward = make_dist_forward_split(
            mesh, cfg, n_static, dp_axes=dp_axes, model_axis=model_axis,
            variant=variant, planes=planes,
        )
    return forward, x_spec, p_specs


def forward_and_specs(
    mesh: Mesh,
    cfg: FNOConfig,
    *,
    dp_axes=("data",),
    model_axis=None,
    variant: str = "paper",
    planes: bool = False,
):
    """(forward, x_spec, p_specs) for a mesh: the single source of truth for
    how an FNO batch and its params are laid out, shared by the training
    driver and the serving runner (instead of each duplicating the
    serial-vs-distributed branch and the spec plumbing).

    ``model_axis=None`` returns the serial oracle (pure data parallelism:
    params replicated, batch sharded over ``dp_axes``); a mesh-axis name or
    a pair of names returns the shard_map'd distributed forward (paper
    Alg. 2 / 2-D pencils). ``forward(params, x)`` in all cases.

    ``planes=True``: specs and shard_map layouts for a plane-cached params
    tree (``params_with_planes``, serving only).
    """
    x_spec = input_spec(dp_axes, model_axis)
    p_specs = param_specs(mesh, model_axis, planes=planes)
    if model_axis is None:
        def forward(params, x):
            return fno_forward(params, x, cfg)
    else:
        forward = make_dist_forward(
            mesh, cfg, dp_axes=dp_axes, model_axis=model_axis, variant=variant,
            planes=planes,
        )
    return forward, x_spec, p_specs


def mse_loss(pred: jax.Array, target: jax.Array) -> jax.Array:
    return jnp.mean(jnp.square(pred.astype(jnp.float32) - target.astype(jnp.float32)))
